"""Benchmark: map-step throughput + end-to-end pipelines on one TPU chip.

Phases, one shared set of int8 Llama-3.2-3B weights:

1. **Map-step microbench** — batched map-phase generation (bucket-1024
   prompts + 128 new tokens, batch 96), the engine doing what the reference
   does serially over HTTP. Reference total throughput is ~0.25 chunks/s
   (BASELINE.md, llama3.2:3b iterative — its best 3B number).
2. **End-to-end mapreduce pipeline** — synthesize a corpus at TRUE
   VN-LongSum per-doc scale (avg 36,959 words / ~210k bytes per doc,
   metadata/doc_metadata.json), then run the real `PipelineRunner`: split →
   batched map → collapse rounds → final reduce → write summaries → ROUGE +
   BERTScore + semsim evaluation, with sampled ragged-EOS decode so the
   termination/compaction behavior matches a real checkpoint's. Wall-clock
   covers ALL of it; vs_baseline is docs/min against the reference's
   fastest 3B run on the same-sized docs (20.0 s/doc).
3. **Other strategies** — iterative, hierarchical, and mapreduce_critique
   summarize-only runs on the same corpus (4 docs), against their
   BASELINE.md rows. With the 16k truncated row
   (artifacts/bench_16k.json) every one of the five approaches has an
   on-chip measurement.

Prints ONE JSON line: the map-step metric stays the headline (comparable
across rounds), with the pipeline numbers nested under "e2e",
"e2e_iterative", and "e2e_hierarchical".
"""
from __future__ import annotations

import json
import sys
import tempfile
import time

REFERENCE_CHUNKS_PER_SEC = 0.25  # BASELINE.md: llama3.2:3b iterative, total
# reference wall-clock on the SAME per-doc text volume: llama3.2:3b
# iterative, 151 docs in 3014 s = 20.0 s/doc (BASELINE.md; its fastest 3B
# run — mapreduce was only timed with qwen3:8b at 65.8 s/doc)
REFERENCE_DOCS_PER_MIN = 3.01

# e2e corpus shape: TRUE VN-LongSum scale per document —
# /root/reference/metadata/doc_metadata.json: avg 36,959 words / 166,920
# chars (~210k bytes) / 54,566 Qwen-tokens per doc. 16 docs keeps the bench
# round under ~10 min; docs/min extrapolates linearly in doc count
E2E_DOCS = 16
E2E_WORDS_PER_DOC = 37_000  # reference's average_words_per_file

# Published per-chip peaks, keyed by the device_kind JAX reports, used for
# the MFU / roofline fields. A device that is not in the table is an error,
# not a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e"
    "TPU v5 lite": {
        "flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_per_s": 819e9,
    },
}


def require_chip() -> dict:
    """The device this run times, as JAX reports it — or no run at all: a
    benchmark that finds no chip fails instead of timing the CPU."""
    import jax

    d = jax.devices()
    if d[0].platform != "tpu":
        raise SystemExit(
            f"bench.py times a TPU; JAX found platform {d[0].platform!r} "
            f"({d[0].device_kind} x{len(d)}) — nothing was run"
        )
    device_peaks()  # an unknown device kind fails here, not after the run
    return {"platform": d[0].platform, "device_kind": d[0].device_kind,
            "count": len(d)}


def device_peaks() -> dict:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"no published peaks for device kind {kind!r}: add it to "
            "bench.DEVICE_PEAKS with its source"
        )
    return DEVICE_PEAKS[kind]


def run_map_step_bench(backend) -> dict:
    prompt_tokens = 1000  # buckets to S=1024
    batch = backend.batch_size
    rounds = 3

    base = (
        "Bạn là một chuyên gia tóm tắt nội dung. "
        "Vui lòng viết một bản tóm tắt chi tiết cho đoạn văn bản sau bằng tiếng Việt. "
    )
    filler = "Quốc hội đã thông qua nghị quyết về phát triển kinh tế xã hội. "
    prompt = base + filler * ((prompt_tokens - len(base.encode())) // len(filler.encode()))
    prompts = [prompt + f" (tài liệu {i})" for i in range(batch)]

    t0 = time.time()
    backend.generate(prompts, max_new_tokens=128)  # compile + warmup
    print(f"warmup (incl. compile): {time.time() - t0:.1f}s", file=sys.stderr)

    t1 = time.time()
    done = 0
    for r in range(rounds):
        outs = backend.generate(
            [p + f" vòng {r}" for p in prompts], max_new_tokens=128
        )
        done += len(outs)
    elapsed = time.time() - t1

    stats = backend.stats
    print(
        f"map bench: {done} chunks in {elapsed:.1f}s; engine totals: "
        f"{stats.prompt_tokens} prompt tok, {stats.generated_tokens} gen tok, "
        f"{stats.tokens_per_second:.0f} tok/s overall",
        file=sys.stderr,
    )
    return {"chunks_per_sec": done / elapsed}


def _pick_ragged_eos(outs: list[str], tok, budget: int = 128) -> tuple[int, ...]:
    """Pick the token id whose per-row frequency makes the EXPECTED
    termination step ~budget/3 under sampled decode: with ~f occurrences per
    ``budget``-token row, per-step hit probability is ~f/budget, so
    E[termination] ~ budget/f. f~3 puts the average stop around step 40 of
    128 — most rows finish well before the budget at scattered depths (the
    shape real summaries produce), which is also what gives tail compaction
    something to harvest."""
    from collections import Counter

    rows = [tok.encode(o) for o in outs if o]
    rows = [r for r in rows if r]
    if not rows:
        return (10,)
    counts: Counter = Counter()
    for r in rows:
        counts.update(r)
    target = 3.0 * len(rows)  # ~3 occurrences per row on average
    best = min(counts, key=lambda b: (abs(counts[b] - target), b))
    # Round-4 comparability note: the tokenizer's NATIVE eos is now always a
    # terminator too (the ADVICE-r3 sampleability fix). For the trained-BPE
    # bench tokenizer that adds a ~1/4096-per-step hazard on top of this
    # picked token's ~3/128 — a <2% shift in expected termination depth, so
    # r04 docs/min stays workload-comparable with the committed r03 numbers.
    return (int(best),)


def e2e_engine_kwargs(tok_spec, params) -> dict:
    """ONE copy of the e2e engine configuration — the headline e2e run, the
    instrumented budget pass, and the weight-only A/B row must all measure
    the same shape (chunk_size 7800 -> S=8192 bucket, B=8 at the HBM
    ceiling, int8 weights).

    W8A8 prefill is the DEFAULT here as of round 5: its quality cost is
    measured and bounded (artifacts/quality_lossy_ab.json — within 0.5pp
    string agreement / 0.005 ROUGE-L of the int8-weights+int8-KV arm on
    the four trained family fixtures, per the pre-registered promotion
    rule), and it buys 1.25x on the dominant prefill dispatch
    (artifacts/w8a8_ab.json, PERF.md finding 18). The weight-only-exact
    path stays one flag away (quantize_act=False) and keeps its own bench
    row.

    B=16 + chunked prefill is ALSO the round-5 default: whole-prompt
    prefill transients were what capped the batch at 8 next to the int8 KV
    cache; prefill_chunk_tokens=2048 caps them at a chunk's worth, and the
    measured A/B (artifacts/b16_chunked_prefill.json) shows one B=16
    dispatch beating two B=8 dispatches 1.10x overall (decode 1.36x —
    weight reads amortize over twice the rows; prefill flat; exact same
    math, engine-level chunked==whole equivalence test)."""
    from vnsum_tpu.models import llama32_3b

    return dict(
        model_config=llama32_3b(max_seq_len=8448),
        tokenizer=tok_spec,
        params=params,
        batch_size=16,
        max_new_tokens=128,
        quantize=True,
        quantize_act=True,
        prefill_chunk_tokens=2048,
    )


def run_e2e_bench(params) -> tuple[dict, str, object, str, tuple]:
    # returns (metrics, corpus root, live backend, tokenizer spec, eos ids)
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig, PipelineConfig
    from vnsum_tpu.data.synthesize import synthesize_corpus
    from vnsum_tpu.pipeline.runner import PipelineRunner

    root = tempfile.mkdtemp(prefix="vnsum_bench_")
    t0 = time.time()
    stats = synthesize_corpus(
        f"{root}/corpus", n_docs=E2E_DOCS, tokens_per_doc=E2E_WORDS_PER_DOC,
        summary_tokens=714, seed=7, ragged=0.5,
    )
    print(
        f"e2e corpus: {E2E_DOCS} docs, "
        f"avg {stats['documents']['avg_tokens_per_file']:.0f} words "
        f"(synth {time.time() - t0:.1f}s)",
        file=sys.stderr,
    )

    # The QUALITY-RUN configuration tokenizes with the checkpoint's HF BPE
    # tokenizer (pipeline --weights-dir path), not raw bytes — and byte
    # tokens cost ~4-6x the forward passes per unit of text. Train a
    # byte-level BPE on this corpus (seconds; the fixture trainer the
    # parity artifact uses) so the e2e bench measures the real
    # configuration. Compression is reported: the synthetic grammar
    # compresses better (~5.7 B/tok) than real VN under Llama BPE
    # (~3.8 B/tok), so tokens/doc lands near ~44k vs VN-LongSum's 54.5k —
    # same words and chars per doc, ~20% fewer model tokens.
    import pathlib as _pl

    from vnsum_tpu.models.fixtures import train_bpe_tokenizer

    t0 = time.time()
    doc_paths = sorted(_pl.Path(f"{root}/corpus/doc").glob("*.txt"))
    hf_tok = train_bpe_tokenizer(
        (p.read_text(encoding="utf-8") for p in doc_paths), vocab_size=4096
    )
    hf_tok.save_pretrained(f"{root}/tok")
    tok_spec = f"hf:{root}/tok"
    sample_text = doc_paths[0].read_text(encoding="utf-8")
    bytes_per_tok = len(sample_text.encode()) / len(hf_tok.encode(sample_text))
    print(
        f"e2e tokenizer: BPE vocab {len(hf_tok)}, "
        f"{bytes_per_tok:.2f} bytes/token (train {time.time() - t0:.1f}s)",
        file=sys.stderr,
    )

    # chunk_size 7800 BPE tokens lands prompts in the S=8192 bucket; int8 KV
    # keeps 8 rows of 8320-token cache (+ int8 weights + the ~4 GB of
    # prefill transients at S=8192) inside one v5e chip — B=16 OOMs.
    # continuous="auto" correctly resolves to the ONE-SHOT program at B=8:
    # the measured A/B (artifacts/compaction_ab.json) shows the segmented
    # path losing ~33% token-normalized at this shape
    backend = TpuBackend(**e2e_engine_kwargs(tok_spec, params))
    cfg = PipelineConfig(
        approach="mapreduce",
        models=["llama3.2-3b"],
        backend="tpu",
        docs_dir=f"{root}/corpus/doc",
        summary_dir=f"{root}/corpus/summary",
        generated_summaries_dir=f"{root}/gen",
        results_dir=f"{root}/results",
        logs_dir=f"{root}/logs",
        chunk_size=7_800,
        chunk_overlap=200,
        # collapse budget in whitespace WORDS (reference-parity gating);
        # capped low enough that a worst-case all-ASCII grouping still fits
        # the model's 8320-byte-token input — reduce prompts must never be
        # silently truncated by the engine
        token_max=6_000,
        max_new_tokens=128,
        batch_size=16,
        tokenizer=tok_spec,
    )
    # random-init weights never emit the true EOS, so decode would always
    # pay the full budget and early-exit would sit idle — and under GREEDY
    # decode the rollouts degenerate (round 2's summaries were all empty:
    # the near-constant argmax stream hit its EOS at position 0). Run the
    # e2e with SAMPLED decode instead: temperature 1.0 over a random-init
    # model gives high-entropy streams, and _pick_ragged_eos declares the
    # token id observed ~3x per probe row as EOS (expected termination
    # ~budget/3), so rows finish early at scattered depths — the workload
    # shape a real checkpoint produces — and summaries stay non-empty for a
    # realistic evaluation pass.
    # Probe slices come from several docs' concatenation (one doc is ~210 KB
    # but 8 slices of ~7.3k BPE tokens need ~330 KB), sliced by BYTES scaled
    # by the measured compression so every probe prompt lands in the S=8192
    # bucket the pipeline uses (pre-warming its compile).
    raw = b" ".join(
        p.read_text(encoding="utf-8").encode("utf-8") for p in doc_paths[:6]
    )
    step = int(7_300 * bytes_per_tok)  # ~7.3k BPE tokens -> S=8192 bucket
    nb = backend.batch_size  # probe at FULL batch so the dominant
    # (B, S=8192) bucket's program is the one warmed
    assert len(raw) >= nb * step, (len(raw), step)
    probe_prompts = [
        "Tóm tắt: " + raw[i * step : (i + 1) * step].decode("utf-8", "ignore")
        for i in range(nb)
    ]
    probe = backend.generate(
        probe_prompts, config=GenerationConfig(temperature=1.0, seed=11)
    )
    eos = _pick_ragged_eos(probe, backend.tok)
    backend.gen_cfg = GenerationConfig(
        max_new_tokens=128, temperature=1.0, seed=11, eos_ids=eos
    )
    print(f"e2e ragged-eos token id: {eos}", file=sys.stderr)

    runner = PipelineRunner(cfg, backend_factory=lambda model: backend)

    t1 = time.time()
    results = runner.run()
    elapsed = time.time() - t1

    # itemized wall-clock budget (tracer spans) — the e2e number is only
    # actionable with its breakdown (where does non-generation time go?)
    spans = results.tracing.get("spans", {})
    budget = {
        name: round(s["total_s"], 1)
        for name, s in spans.items()
        if name in (
            "analyze", "summarize", "evaluate",
            "evaluate/embedder_init", "evaluate/embed",
            "evaluate/bertscore", "evaluate/rouge",
        )
    }
    for name, secs in sorted(budget.items()):
        print(f"e2e span {name}: {secs}s", file=sys.stderr)

    rec = results.summarization["llama3.2-3b"]
    total_chunks = rec["total_chunks"]
    docs = rec["successful"]
    if not docs:
        raise RuntimeError(f"e2e bench: all documents failed — see {root}/logs")
    chunks_per_sec = total_chunks / elapsed
    ok_names = {
        d["filename"] for d in rec["processing_details"]
        if d["status"] == "success"
    }
    input_bytes = sum(
        p.stat().st_size for p in doc_paths if p.name in ok_names
    )
    ev = results.evaluation.get("llama3.2-3b", {})
    rougel = ev.get("rouge_scores", {}).get("rougeL_f1", float("nan"))
    print(
        f"e2e pipeline: {docs} docs / {total_chunks} chunks in {elapsed:.1f}s "
        f"(map+collapse+reduce+eval); engine: {backend.stats.batches} batches, "
        f"{backend.stats.compactions} compactions, "
        f"{backend.stats.tokens_per_second:.0f} tok/s; rougeL={rougel:.4f}",
        file=sys.stderr,
    )
    docs_per_min = docs / (elapsed / 60)
    return {
        "chunks_per_sec": round(chunks_per_sec, 4),
        "docs_per_min": round(docs_per_min, 2),
        "seconds_total": round(elapsed, 1),
        "chunks": total_chunks,
        "docs": docs,
        "avg_doc_bytes": round(input_bytes / max(docs, 1)),
        "input_bytes_per_sec": round(input_bytes / elapsed),
        "compactions": backend.stats.compactions,
        # docs/min against the reference run on same-sized documents
        # (llama3.2:3b iterative, 20.0 s/doc) — the honest end-to-end ratio
        "vs_baseline": round(docs_per_min / REFERENCE_DOCS_PER_MIN, 2),
        "vs_baseline_chunks": round(
            chunks_per_sec / REFERENCE_CHUNKS_PER_SEC, 2
        ),
        "time_budget": budget,
    }, root, backend, tok_spec, eos


def run_device_budget(params, root: str, tok_spec, eos) -> dict:
    """Per-phase DEVICE time inside summarize (VERDICT r3 #1): rerun 4 docs
    of the same mapreduce workload on an instrument=True engine — split
    prefill/decode programs with a result-fetch sync between phases (same
    traced bodies as the one-shot program) — then turn the per-dispatch
    {B, S, steps} records into MFU / HBM-roofline numbers.

    The pipeline runs TWICE: the first pass compiles every bucket the
    workload touches (split programs are new in this mode), the second is
    the measured one — so phase times carry no compile pollution."""
    import pathlib as _pl

    from vnsum_tpu.backend.engine import EngineStats, TpuBackend
    from vnsum_tpu.core.config import GenerationConfig, PipelineConfig
    from vnsum_tpu.pipeline.runner import PipelineRunner

    backend = TpuBackend(
        **e2e_engine_kwargs(tok_spec, params), instrument=True
    )
    if eos is None:
        # standalone use (scripts/measure_device_budget.py): run the same
        # ragged-EOS probe the e2e phase does, on this backend — which also
        # warms the dominant S=8192 bucket's split programs
        doc_paths = sorted(_pl.Path(f"{root}/corpus/doc").glob("*.txt"))
        raw = b" ".join(
            p.read_text(encoding="utf-8").encode("utf-8")
            for p in doc_paths[:3]
        )
        sample = doc_paths[0].read_text(encoding="utf-8")
        bpt = len(sample.encode()) / max(backend.count_tokens(sample), 1)
        step = int(7_300 * bpt)
        n = max(1, min(8, len(raw) // step))
        probe = backend.generate(
            [
                "Tóm tắt: "
                + raw[i * step : (i + 1) * step].decode("utf-8", "ignore")
                for i in range(n)
            ],
            config=GenerationConfig(temperature=1.0, seed=11),
        )
        eos = _pick_ragged_eos(probe, backend.tok)
        print(f"device budget ragged-eos: {eos}", file=sys.stderr)
    backend.gen_cfg = GenerationConfig(
        max_new_tokens=128, temperature=1.0, seed=11, eos_ids=eos
    )

    def make_cfg(tag: str) -> PipelineConfig:
        return PipelineConfig(
            approach="mapreduce",
            models=["llama3.2-3b"],
            backend="tpu",
            docs_dir=f"{root}/corpus/doc",
            summary_dir=f"{root}/corpus/summary",
            generated_summaries_dir=f"{root}/{tag}",
            results_dir=f"{root}/results",
            logs_dir=f"{root}/logs",
            chunk_size=7_800,
            chunk_overlap=200,
            token_max=6_000,
            max_new_tokens=128,
            batch_size=16,
            tokenizer=tok_spec,
            max_samples=4,
        )

    for tag in ("gen_budget_warm", "gen_budget"):
        if tag == "gen_budget":  # measured pass starts from clean counters
            backend.stats = EngineStats()
        runner = PipelineRunner(
            make_cfg(tag), backend_factory=lambda model: backend
        )
        t0 = time.time()
        rec = runner.run_summarization_for_model("llama3.2-3b")
        wall = time.time() - t0
    if not rec.successful:
        raise RuntimeError("device budget pass: all documents failed")

    st = backend.stats
    pre = st.phase_seconds.get("prefill", 0.0)
    dec = st.phase_seconds.get("decode", 0.0)
    tok_h = st.phase_seconds.get("tokenize_host", 0.0)
    pack_h = st.phase_seconds.get("pack_host", 0.0)

    # FLOP / byte model from the engine's actual dispatch shapes
    import jax

    cfg_m = backend.cfg
    live_params = backend.params  # == the shared weights when passed in
    leaves = jax.tree.leaves(live_params)
    n_params = sum(int(l.size) for l in leaves)
    weight_bytes = sum(int(l.nbytes) for l in leaves)
    # embedding rows are gathered, not multiplied, during the body; with
    # tied embeddings the same table returns as the LM head and is only
    # applied to the LAST position (last_only prefill) — either way the
    # per-prompt-token matmul FLOPs come from the non-embed body
    embed = live_params["embed"]  # {"q","s"} when int8-quantized
    n_body = n_params - int(
        embed["q"].size if isinstance(embed, dict) else embed.size
    )
    ahd = cfg_m.n_layers * cfg_m.n_heads * cfg_m.head_dim
    pre_flops = sum(
        d["B"] * d["S"] * 2 * n_body        # dense matmuls, 2 FLOP/MAC
        # causal attention at the same 2-FLOP/MAC convention: QK^T + PV are
        # 2 * (2*hd*S^2/2) per head = 2*hd*S^2
        + d["B"] * 2 * ahd * d["S"] ** 2
        for d in st.dispatches
    )
    # the weights are int8 but the default-exact matmuls accumulate from
    # bf16 activations, so the bf16 peak is this field's denominator
    peaks = device_peaks()
    mfu_prefill = pre_flops / (pre * peaks["flops_bf16"]) if pre else 0.0

    # decode is HBM-bound: every step streams the full weight set plus each
    # row's valid KV cache (int8 + per-(token, head) f32 scales when the
    # quantized-cache kernels are active)
    kv_elt = 1 if backend.quantize_kv else 2
    kv_scale = 4 if backend.quantize_kv else 0
    per_tok_layer = 2 * cfg_m.n_kv_heads * (cfg_m.head_dim * kv_elt + kv_scale)
    dec_bytes = sum(
        d["steps"]
        * (
            weight_bytes
            + d["B"] * cfg_m.n_layers * per_tok_layer
            * (d["S"] + d["steps"] / 2)
        )
        for d in st.dispatches
    )
    roofline = dec_bytes / (dec * peaks["hbm_bytes_per_s"]) if dec else 0.0

    # one-shot comparison pass (VERDICT r4 weak #5): the SAME 4 docs through
    # a production (instrument=False) engine sharing these weights, so the
    # few-percent structural delta of the split instrument programs is
    # MEASURED on identical input rather than asserted from compaction_ab
    oneshot = TpuBackend(**e2e_engine_kwargs(tok_spec, live_params))
    oneshot.gen_cfg = backend.gen_cfg
    # warm pass first (trace + cache-load), mirroring the instrument arm's
    # two-pass discipline — otherwise the delta is swamped by compile
    for tag in ("gen_budget_oneshot_warm", "gen_budget_oneshot"):
        t0 = time.time()
        rec_1 = PipelineRunner(
            make_cfg(tag), backend_factory=lambda model: oneshot
        ).run_summarization_for_model("llama3.2-3b")
        oneshot_wall = time.time() - t0
    if not rec_1.successful:
        raise RuntimeError("one-shot comparison pass: all documents failed")

    out = {
        "docs": rec.successful,
        "chunks": rec.total_chunks,
        "wall_s": round(wall, 1),
        "oneshot_wall_s": round(oneshot_wall, 1),
        "instrument_overhead_frac": round(wall / oneshot_wall - 1, 4),
        "prefill_s": round(pre, 1),
        "decode_s": round(dec, 1),
        "tokenize_host_s": round(tok_h, 1),
        "pack_host_s": round(pack_h, 1),
        "other_host_s": round(wall - pre - dec - tok_h - pack_h, 1),
        "decode_steps": sum(d["steps"] for d in st.dispatches),
        "dispatches": st.dispatches,
        "mfu_prefill": round(mfu_prefill, 4),
        "decode_roofline_frac": round(roofline, 4),
        "peak_flops_bf16": peaks["flops_bf16"],
        "hbm_bytes_per_s": peaks["hbm_bytes_per_s"],
    }
    print(f"device budget: {out}", file=sys.stderr)
    return out


def run_strategy_bench(backend, approach: str, root: str, tok_spec) -> dict:
    """Summarization-phase timing for the other strategies on the SAME
    corpus + engine + compiled programs (VERDICT r2 #5): 4 docs,
    summarize-only — the reference's comparable numbers are its
    summarization records (BASELINE.md: iterative llama3.2:3b 20.0 s/doc;
    hierarchical phi4:14b 211 s/doc)."""
    from vnsum_tpu.core.config import PipelineConfig
    from vnsum_tpu.pipeline.runner import PipelineRunner

    cfg = PipelineConfig(
        approach=approach,
        models=["llama3.2-3b"],
        backend="tpu",
        docs_dir=f"{root}/corpus/doc",
        summary_dir=f"{root}/corpus/summary",
        generated_summaries_dir=f"{root}/gen_{approach}",
        results_dir=f"{root}/results",
        logs_dir=f"{root}/logs",
        chunk_size=7_800,
        chunk_overlap=200,
        iterative_chunk_size=7_800,
        iterative_chunk_overlap=200,
        token_max=6_000,
        max_new_tokens=128,
        batch_size=16,
        tokenizer=tok_spec,
        max_samples=4,
        tree_json_path=f"{root}/corpus/document_tree.json",
    )
    runner = PipelineRunner(cfg, backend_factory=lambda model: backend)
    t0 = time.time()
    rec = runner.run_summarization_for_model("llama3.2-3b")
    elapsed = time.time() - t0
    docs = rec.successful
    out = {
        "docs": docs,
        "chunks": rec.total_chunks,
        "llm_calls": sum(d.llm_calls for d in rec.processing_details),
        "seconds": round(elapsed, 1),
        "docs_per_min": round(docs / (elapsed / 60), 2) if docs else 0.0,
        "compactions": backend.stats.compactions,  # cumulative engine stat
    }
    print(f"{approach} bench: {out}", file=sys.stderr)
    if not docs:
        raise RuntimeError(f"{approach} bench: all documents failed")
    return out


def main() -> int:
    device = require_chip()
    print(f"bench device: {device}", file=sys.stderr)

    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import llama32_3b

    # measured sweet spot on v5e with the vectorized Pallas decode kernel +
    # int8 KV cache (B=64: 14.9, B=96: 15.8, B=128: OOM); the int8 cache
    # freed enough HBM for 96 rows
    backend = TpuBackend(
        model_config=llama32_3b(max_seq_len=4096),
        tokenizer="byte",
        batch_size=96,
        max_new_tokens=128,
        quantize=True,
    )

    map_res = run_map_step_bench(backend)

    # release the B=96 map-bench programs before the e2e phase: their
    # executables (and any buffers they pin) otherwise stay resident next to
    # the e2e engine's own programs, squeezing the evaluation encoder into
    # fragmented HBM (round-2's 442s eval tail)
    params = backend.params
    del backend
    import gc

    gc.collect()

    # ONE engine (weights already quantized, programs already compiled)
    # serves the e2e run and all three extra strategy phases
    e2e_res, corpus_root, e2e_backend, tok_spec, eos = run_e2e_bench(params)
    iter_res = run_strategy_bench(
        e2e_backend, "iterative", corpus_root, tok_spec
    )
    hier_res = run_strategy_bench(
        e2e_backend, "mapreduce_hierarchical", corpus_root, tok_spec
    )
    crit_res = run_strategy_bench(
        e2e_backend, "mapreduce_critique", corpus_root, tok_spec
    )

    # release the main engine's executables before the remaining phases
    # (same HBM-fragmentation reasoning as the map->e2e handoff above)
    del e2e_backend
    gc.collect()

    # weight-only-exact A/B at the e2e workload (4 docs, summarize-only):
    # W8A8 is the headline default since round 5 (quality bound:
    # artifacts/quality_lossy_ab.json); this row keeps the exact path's
    # cost visible so the 1.25x prefill claim stays continuously measured
    from vnsum_tpu.core.config import GenerationConfig

    exact_backend = TpuBackend(
        **{**e2e_engine_kwargs(tok_spec, params), "quantize_act": False},
        generation=GenerationConfig(
            max_new_tokens=128, temperature=1.0, seed=11, eos_ids=eos
        ),
    )
    exact_res = run_strategy_bench(
        exact_backend, "mapreduce", corpus_root, tok_spec
    )
    del exact_backend
    gc.collect()

    budget_res = run_device_budget(params, corpus_root, tok_spec, eos)

    chunks_per_sec = map_res["chunks_per_sec"]
    print(
        json.dumps(
            {
                "metric": "map_step_chunks_per_sec_per_chip_llama32_3b",
                "device": device,
                "value": round(chunks_per_sec, 4),
                "unit": "chunks/s",
                "vs_baseline": round(chunks_per_sec / REFERENCE_CHUNKS_PER_SEC, 2),
                "mfu_prefill": budget_res["mfu_prefill"],
                "decode_roofline_frac": budget_res["decode_roofline_frac"],
                "e2e": e2e_res,
                "e2e_iterative": iter_res,
                "e2e_hierarchical": hier_res,
                "e2e_critique": crit_res,
                "e2e_weight_only_mapreduce": exact_res,
                "device_budget": budget_res,
            }
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
