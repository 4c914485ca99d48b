"""Driver ``offline_pipeline``: documents through ``PipelineRunner``.

One child holds the chip and does everything; the parent only waits. The
child makes the weights on the device and, meanwhile on the host, the
documents and their BPE tokenizer from the seed; warms up the map and the
reduce dispatch shapes; then summarizes whole groups of documents, starting
a new group while the window is open. The rate is documents completed over
the wall to the end of the last group, so all work and all time count.

Traffic parameters (``traffic/<mix>.json``): ``approach``, ``doc_tokens``
(one group's document lengths in BPE tokens; a seed permutes them),
``chunks_per_doc``, ``chunk_size``, ``chunk_overlap``, ``token_max``,
``max_new_tokens``, ``bpe_vocab``, ``bpe_train_words``,
``warmup_reduce_summaries``, ``min_group_seconds`` (how many groups to prepare), ``trace_seconds``
(null: trace one whole group).
"""
from __future__ import annotations

import json
import math
import time
from pathlib import Path

from benchmarks import childproc, engine_setup, stats, textgen


def parent(ctx: dict) -> dict:
    return childproc.run_to_end(ctx)


class TimedBackend:
    """The benchmark's own span around ``backend.generate``, and a record of
    what each call was given and gave back."""

    def __init__(self, inner, count_lens: bool) -> None:
        self._inner = inner
        self._count_lens = count_lens
        self.calls: list[dict] = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def generate(self, prompts, **kw):
        import jax

        st = self._inner.stats
        before = (st.prompt_tokens, st.generated_tokens)
        t0 = time.time()
        with jax.profiler.TraceAnnotation("bench:generate"):
            outs = self._inner.generate(prompts, **kw)
        t1 = time.time()
        call = {"t0": t0, "t1": t1, "prompts": len(prompts), "outs": outs,
                "prompt_tokens": st.prompt_tokens - before[0],
                "generated_tokens": st.generated_tokens - before[1]}
        if self._count_lens:
            call["prompt_lens"] = self._inner.count_tokens_batch(prompts)
        self.calls.append(call)
        return outs


def make_documents(gen, hf_tok, targets: list[int], tokens_per_word: float
                   ) -> list[str]:
    count = lambda ps: [len(x) for x in  # noqa: E731
                        hf_tok(ps, add_special_tokens=False)["input_ids"]]
    return [gen.text_of_tokens(t, count, tokens_per_word) for t in targets]


def make_corpus(traffic: dict, work: Path, seed: int, batch: int,
                n_groups: int) -> tuple[str, list[str], list[Path]]:
    """On the host, while the device makes the weights: a BPE tokenizer
    trained on the seed's text, documents that fill one warm-up dispatch,
    and ``n_groups`` groups of documents written to directories of their
    own. Returns (tokenizer spec, warm-up documents, group directories)."""
    gen = textgen.TextGen(seed)
    hf_tok, tok_spec, tokens_per_word = engine_setup.train_bpe(
        gen, traffic, work / "tok")
    warm_docs = make_documents(
        gen, hf_tok,
        [max(traffic["doc_tokens"])] * math.ceil(
            batch / max(traffic["chunks_per_doc"] - 1, 1)),
        tokens_per_word)
    groups = []
    for g in range(n_groups):
        root = work / f"group{g}"
        (root / "doc").mkdir(parents=True)
        texts = make_documents(
            gen, hf_tok, textgen.permuted(traffic["doc_tokens"], seed, g),
            tokens_per_word)
        for i, text in enumerate(texts):
            (root / "doc" / f"doc_{i:03d}.txt").write_text(text, encoding="utf-8")
        groups.append(root)
    return tok_spec, warm_docs, groups


def warm_up(timed: TimedBackend, warm_docs: list[str], traffic: dict,
            batch: int, per_group: int) -> None:
    """One map dispatch at the window's shape, a full batch of the longest
    chunks, then a reduce dispatch of ``per_group`` prompts for each entry of
    ``warmup_reduce_summaries``: that many map outputs joined, so that every
    length bucket a reduce prompt of the window can fall into has run."""
    from vnsum_tpu.strategies.prompts import MAPREDUCE_MAP, MAPREDUCE_REDUCE
    from vnsum_tpu.text.splitter import RecursiveTokenSplitter

    max_new = traffic["max_new_tokens"]
    splitter = RecursiveTokenSplitter(
        traffic["chunk_size"], traffic["chunk_overlap"],
        length_function=timed.count_tokens,
        length_batch_function=timed.count_tokens_batch)
    chunks = [c for d in warm_docs for c in splitter.split_text(d)]
    chunks = sorted(chunks, key=len, reverse=True)[:batch]
    outs = timed.generate([MAPREDUCE_MAP.format(content=c) for c in chunks],
                          max_new_tokens=max_new)
    for k in traffic["warmup_reduce_summaries"]:
        timed.generate(
            [MAPREDUCE_REDUCE.format(docs="\n\n".join((outs * k)[i:i + k]))
             for i in range(per_group)], max_new_tokens=max_new)


def child(ctx: dict) -> dict:
    import jax

    from vnsum_tpu.core.jax_cache import enable_compilation_cache

    traffic, config, rehearsal = ctx["traffic"], ctx["config"], ctx["rehearsal"]
    enable_compilation_cache()
    device = engine_setup.require_device(ctx["cell"]["chips"], rehearsal)
    compiles = engine_setup.watch_compiles()
    seed = textgen.fold_seed(ctx["seed"])
    cfg = engine_setup.model_config(config, rehearsal)
    params = engine_setup.start_weights(config, cfg, seed)  # runs meanwhile

    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig, PipelineConfig
    from vnsum_tpu.pipeline.cli import failures
    from vnsum_tpu.pipeline.runner import PipelineRunner

    work = Path(ctx["work_dir"])
    per_group = len(traffic["doc_tokens"])
    batch = (config["rehearsal"]["batch"] if rehearsal
             else config["engine"]["batch"])
    tok_spec, warm_docs, groups = make_corpus(
        traffic, work, seed, batch,
        n_groups=math.ceil(ctx["seconds"] / traffic["min_group_seconds"]))

    max_new = traffic["max_new_tokens"]
    backend = TpuBackend(
        model_config=cfg, tokenizer=tok_spec, params=params,
        batch_size=batch, max_new_tokens=max_new,
        generation=GenerationConfig(temperature=1.0, seed=seed),
        **engine_setup.backend_kwargs(config, rehearsal))
    timed = TimedBackend(backend, count_lens=bool(ctx["trace"]))

    parity = engine_setup.parity_with_reference(backend, config, seed,
                                                rehearsal)
    warm_up(timed, warm_docs, traffic, batch, per_group)
    warm_buckets = set(backend.stats.by_bucket)
    n_warm_calls = len(timed.calls)
    largest = max(warm_buckets, key=lambda bs: bs[0] * bs[1])
    temp_bytes = engine_setup.one_shot_temp_bytes(backend, *largest, max_new)

    model = config["registry_name"]

    def run_group(root: Path) -> dict:
        pcfg = PipelineConfig(
            approach=traffic["approach"], models=[model], backend="tpu",
            docs_dir=str(root / "doc"), summary_dir="",
            generated_summaries_dir=str(root / "gen"),
            results_dir=str(root / "results"), logs_dir=str(root / "logs"),
            chunk_size=traffic["chunk_size"],
            chunk_overlap=traffic["chunk_overlap"],
            token_max=traffic["token_max"], max_new_tokens=max_new,
            batch_size=batch, doc_group_size=per_group, tokenizer=tok_spec)
        runner = PipelineRunner(pcfg, backend_factory=lambda _m: timed)
        with runner.tracer.span("summarize"):
            rec = runner.run_summarization_for_model(model)
        out_dir = runner._output_dir(model)
        return {"failures": failures(runner.results),
                "successful": rec.successful, "failed": rec.failed,
                "chunks": rec.total_chunks,
                "summaries": [p.read_text(encoding="utf-8")
                              for p in sorted(out_dir.glob("*.txt"))]}

    profiler = engine_setup.Profiler(str(work / "trace"))
    compiles_before = compiles["compiles"]
    t_w0 = time.time()
    done, t_last, traced = [], t_w0, None
    for g, root in enumerate(groups):
        if time.time() - t_w0 >= ctx["seconds"]:
            break
        trace_this = bool(ctx["trace"]) and g == 0
        if trace_this:
            first_call = len(timed.calls)
            if not rehearsal:   # a CPU trace holds no device plane
                profiler.start(traffic.get("trace_seconds") or 3600.0)
        with jax.profiler.TraceAnnotation("bench:pipeline"):
            res = run_group(root)
        t_last = time.time()
        if trace_this:
            profiler.stop()
            print(f"trace: {profiler.wall_s:.1f} s traced, "
                  f"{profiler.stop_s:.1f} s to stop and write", flush=True)
            traced = {"docs": res["successful"], "wall_s": profiler.wall_s,
                      "calls": timed.calls[first_call:]}
        done.append(res)
        if trace_this:
            break   # a traced run measures its traced group and no more
    compiles_in_window = compiles["compiles"] - compiles_before

    calls = timed.calls[n_warm_calls:]
    rows = [o for c in calls for o in c["outs"]]
    bad_rows = sum(stats.degenerate(o, backend.tok.encode(o)) for o in rows)
    summaries = [s for r in done for s in r["summaries"]]
    docs_done = sum(r["successful"] for r in done)
    attempted = per_group * len(done)
    paths = backend.stats.attention_paths
    checks = {
        "platform_is_tpu": device["platform"] == "tpu",
        "attention_paths_kernel": bool(paths) and all(
            p == "kernel" for prog in paths.values() for p in prog.values()),
        "no_compile_in_window": compiles_in_window == 0,
        "no_new_shape_in_window": set(backend.stats.by_bucket) <= warm_buckets,
        "no_pipeline_failures": not any(r["failures"] for r in done),
        "every_document_done": docs_done == attempted and attempted > 0,
        "summaries_written": len(summaries) == docs_done,
        # a row that sampled EOS at its first step is empty and no fault
        # (one in 4096 with this BPE); a NaN fault spoils a whole dispatch
        "outputs_not_degenerate": stats.at_most(bad_rows, len(rows), 1),
        "parity_with_reference": parity["ok"],
    }
    window_s = t_last - t_w0
    raw = {
        "device": {**device, **engine_setup.memory_bytes(temp_bytes)},
        "setup_s": t_w0 - ctx["t_start"],
        "window": {"seconds": window_s},
        "values": {"docs_per_min": stats.rate(docs_done, window_s, per=60.0)
                   if docs_done else None},
        "attempted": attempted, "failed": attempted - docs_done,
        "checks": checks,
        "counts": {"docs": docs_done, "groups": len(done),
                   "map_chunks": sum(r["chunks"] for r in done),
                   "generate_calls": len(calls), "output_rows": len(rows),
                   "degenerate_rows": bad_rows,
                   "empty_summaries": sum(not s.strip() for s in summaries),
                   "compiles_in_window": compiles_in_window,
                   "compiles_total": compiles["compiles"],
                   "compile_cache_hits": compiles["cache_hits"],
                   "parity": parity,
                   "dispatches": {f"B={b},S={s}": n for (b, s), n
                                  in backend.stats.by_bucket.items()}},
        "spans": {"generate": [[c["t0"] - t_w0, c["t1"] - t_w0] for c in calls]},
        "sizes": engine_setup.sizes_of(config, rehearsal),
        "precision": engine_setup.precision_of(config),
        "trace": None, "traced": None,
    }
    if traced is not None:
        raw["traced"] = {
            "docs": traced["docs"], "wall_s": traced["wall_s"],
            "dispatches": [
                {"prompt_lens": lens[i:i + batch], "steps": max_new}
                for c in traced["calls"]
                for lens in [sorted(c["prompt_lens"])]
                for i in range(0, len(lens), batch)],
        }
        if not rehearsal:
            raw["trace"] = profiler.reduce()
    print(json.dumps({k: raw[k] for k in ("setup_s", "window", "values",
                                           "checks", "counts")}), flush=True)
    return raw
