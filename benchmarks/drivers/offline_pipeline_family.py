"""Driver ``offline_pipeline_family``: documents through ``PipelineRunner``
with a model of ANY family that has a set-up module of its own.

The traffic, the corpus, the warm-up and the span around
``backend.generate`` are ``offline_pipeline``'s own (taken from that module,
not copied). What differs between families is the model's set-up, and this
driver takes it by name: the configuration file's ``setup_module`` is a
module of ``benchmarks`` with ``model_config(config, rehearsal)``,
``start_weights(config, cfg, seed)``, ``sizes_of(config, rehearsal)`` and
``parity_with_reference(backend, config, seed, rehearsal)`` (today
``engine_setup_smallthinker``; ``engine_setup_deepseek_v2`` has the same
four, and ``drivers/offline_pipeline_ep.py`` predates this driver and
imports it by name). The next family brings a set-up module and a
configuration file, not a driver.

Weights and tokenizer come from the run's seed, as in the dense cells,
unless the configuration file fixes a ``checkpoint_seed`` (then both are
that one synthetic checkpoint's and the run's seed draws the documents, the
sampling and the parity prompt: ``offline_pipeline_ep`` says why a cell
would).

The raw record has ``offline_pipeline``'s keys, so the readers that serve
it serve this driver, plus ``counts.experts`` where the engine counts
experts (the window's counters: ``slots_routed``, ``slots_held``,
``tokens``, ``decode_touched``, ``decode_layer_steps`` and
``decode_reads_possible`` = layer steps x experts held) and, in a traced
run, each traced dispatch's own share of its call's counters
(``traced.dispatches[i].experts``).

Traffic parameters: as ``offline_pipeline``.
"""
from __future__ import annotations

import importlib
import json
import math
import time
from pathlib import Path

from benchmarks import cells, engine_setup, stats, textgen

_offline = cells.load_module("drivers", "offline_pipeline")
parent = _offline.parent
make_documents = _offline.make_documents
warm_up = _offline.warm_up

_SCALARS = {"slots_routed": "expert_slots_routed",
            "slots_held": "expert_slots_held",
            "decode_touched": "expert_decode_touched",
            "decode_layer_steps": "expert_decode_layer_steps"}


def snapshot(st) -> dict:
    """The engine's expert counters now (0 where it has none)."""
    return {**{k: getattr(st, f, 0) for k, f in _SCALARS.items()},
            "tokens": [list(r) for r in getattr(st, "expert_tokens", [])]}


def expert_counts(st, before: dict) -> dict:
    """The engine's expert counters since ``before`` (a ``snapshot``)."""
    now = snapshot(st)
    was = before["tokens"] or [[0] * len(r) for r in now["tokens"]]
    out = {k: now[k] - before[k] for k in _SCALARS}
    out["tokens"] = [[a - b for a, b in zip(row, old)]
                     for row, old in zip(now["tokens"], was)]
    held = len(now["tokens"][0]) if now["tokens"] else 0
    out["decode_reads_possible"] = out["decode_layer_steps"] * held
    return out


def share(experts: dict, n: int) -> dict:
    """One of ``n`` equal dispatches' part of a call's counters."""
    return {k: ([[t / n for t in row] for row in v] if k == "tokens"
                else v / n) for k, v in experts.items()}


class CountedBackend(_offline.TimedBackend):
    """``TimedBackend`` that also keeps each call's expert counters."""

    def generate(self, prompts, **kw):
        before = snapshot(self._inner.stats)
        outs = super().generate(prompts, **kw)
        self.calls[-1]["experts"] = expert_counts(self._inner.stats, before)
        return outs


def make_corpus(traffic: dict, work: Path, seed: int, tokenizer_seed: int,
                batch: int, n_groups: int) -> tuple[str, list[str], list[Path]]:
    """``offline_pipeline.make_corpus`` with the tokenizer's seed apart from
    the documents': the BPE is trained on ``tokenizer_seed``'s text (the
    run's, or a fixed checkpoint's), the documents are the run seed's.
    Returns (tokenizer spec, warm-up documents, group directories)."""
    hf_tok, tok_spec, tokens_per_word = engine_setup.train_bpe(
        textgen.TextGen(tokenizer_seed), traffic, work / "tok")
    gen = textgen.TextGen(seed)
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


def child(ctx: dict) -> dict:
    import jax

    from vnsum_tpu.core.jax_cache import enable_compilation_cache

    traffic, config, rehearsal = ctx["traffic"], ctx["config"], ctx["rehearsal"]
    family_setup = importlib.import_module(
        f"benchmarks.{config['setup_module']}")
    enable_compilation_cache()
    device = engine_setup.require_device(ctx["cell"]["chips"], rehearsal)
    compiles = engine_setup.watch_compiles()
    seed = textgen.fold_seed(ctx["seed"])
    cfg = family_setup.model_config(config, rehearsal)
    # the run's seed makes the weights and the tokenizer, unless the file
    # fixes one synthetic checkpoint for every run
    checkpoint_seed = (textgen.fold_seed(config["checkpoint_seed"])
                       if config.get("checkpoint_seed") is not None else seed)
    params = family_setup.start_weights(config, cfg, checkpoint_seed)

    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig, PipelineConfig
    from vnsum_tpu.pipeline.cli import failures
    from vnsum_tpu.pipeline.runner import PipelineRunner

    work = Path(ctx["work_dir"])
    per_group = len(traffic["doc_tokens"])
    batch = (config["rehearsal"]["batch"] if rehearsal
             else config["engine"]["batch"])
    tok_spec, warm_docs, groups = make_corpus(
        traffic, work, seed, checkpoint_seed, batch,
        n_groups=math.ceil(ctx["seconds"] / traffic["min_group_seconds"]))

    max_new = traffic["max_new_tokens"]
    backend = TpuBackend(
        model_config=cfg, tokenizer=tok_spec, params=params,
        batch_size=batch, max_new_tokens=max_new,
        generation=GenerationConfig(temperature=1.0, seed=seed),
        **engine_setup.backend_kwargs(config, rehearsal))
    timed = CountedBackend(backend, count_lens=bool(ctx["trace"]))

    parity = family_setup.parity_with_reference(backend, config, seed,
                                                rehearsal)
    print(json.dumps({"parity": parity}), flush=True)
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
    counted_before = snapshot(backend.stats)
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
    counts = expert_counts(backend.stats, counted_before)
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
                   # None for a family that counts no experts
                   "experts": counts if counts["slots_routed"] else None,
                   "prefill_blocks": dict(backend.stats.prefill_blocks),
                   "dispatches": {f"B={b},S={s}": n for (b, s), n
                                  in backend.stats.by_bucket.items()}},
        "spans": {"generate": [[c["t0"] - t_w0, c["t1"] - t_w0] for c in calls]},
        "sizes": family_setup.sizes_of(config, rehearsal),
        "precision": engine_setup.precision_of(config),
        "trace": None, "traced": None,
    }
    if traced is not None:
        raw["traced"] = {
            "docs": traced["docs"], "wall_s": traced["wall_s"],
            "dispatches": [
                {"prompt_lens": lens[i:i + batch], "steps": max_new,
                 "experts": share(c["experts"], math.ceil(len(lens) / batch))
                 if c["experts"]["slots_routed"] else None}
                for c in traced["calls"]
                for lens in [sorted(c["prompt_lens"])]
                for i in range(0, len(lens), batch)],
        }
        if not rehearsal:
            raw["trace"] = profiler.reduce()
    print(json.dumps({k: raw[k] for k in ("setup_s", "window", "values",
                                           "checks", "counts", "spans")}),
          flush=True)
    return raw
