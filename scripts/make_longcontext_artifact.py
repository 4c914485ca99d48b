"""Produce the long-context capability artifact.

Exercises the flagship capability the reference fundamentally lacks (its
truncated strategy cuts every document to 16384−2048 tokens,
runners/run_summarization_ollama.py:8-13): REAL trained weights, documents
LONGER than the model's one-chip max_seq_len, summarized in ONE un-truncated
forward via ring-attention prefill + seq-sharded decode, then scored with
ROUGE against reference summaries.

The model is the same tiny real-format HF checkpoint the quality-parity
artifact uses (models.fixtures, LM-trained on the corpus so greedy decoding
emits corpus-like Vietnamese) — but built with a SMALL max_position window so
the synthesized documents genuinely exceed the one-chip ceiling, and run over
an 8-virtual-device (data=2, seq=4) mesh: the exact mesh program a v5e-8
would execute.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python scripts/make_longcontext_artifact.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# must be set before any jax import (tests/conftest.py recipe)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--out", default=str(REPO / "artifacts/longcontext_e2e_tiny.json")
    )
    ap.add_argument("--docs", type=int, default=4)
    ap.add_argument("--tokens-per-doc", type=int, default=900)
    ap.add_argument("--train-steps", type=int, default=300)
    args = ap.parse_args()

    import jax

    from vnsum_tpu.core.config import PipelineConfig
    from vnsum_tpu.data.synthesize import synthesize_corpus
    from vnsum_tpu.models.fixtures import make_tiny_hf_checkpoint
    from vnsum_tpu.pipeline.runner import PipelineRunner

    work = Path(tempfile.mkdtemp(prefix="longctx_"))
    t0 = time.time()
    corpus_stats = synthesize_corpus(
        work / "corpus", n_docs=args.docs,
        tokens_per_doc=args.tokens_per_doc, summary_tokens=80, seed=3,
    )
    docs = [
        p.read_text(encoding="utf-8")
        for p in sorted((work / "corpus/doc").glob("*.txt"))
    ]
    # one-chip ceiling 512 tokens; the ~900-word docs run 1.3-2k BPE tokens
    one_chip_ceiling = 512
    ckpt_info = make_tiny_hf_checkpoint(
        work / "ckpt", docs, vocab_size=1024,
        max_seq_len=one_chip_ceiling, train_steps=args.train_steps,
    )

    cfg = PipelineConfig(
        approach="truncated",
        models=["tiny-long"],
        backend="tpu",
        long_context=True,
        mesh_shape={"data": 2, "seq": 4},
        weights_dir=str(work / "ckpt"),
        max_context=4096,
        max_new_tokens=96,
        batch_size=2,
        docs_dir=str(work / "corpus/doc"),
        summary_dir=str(work / "corpus/summary"),
        generated_summaries_dir=str(work / "gen"),
        results_dir=str(work / "results"),
        logs_dir=str(work / "logs"),
    )
    # a CPU artifact (JAX_PLATFORMS=cpu, 8 forced host devices): the dense
    # decode partial is asked for by name
    runner = PipelineRunner(cfg)
    runner.backend_factory = lambda model: runner._default_backend_factory(
        model, decode_kernel=False
    )
    results = runner.run()

    model = cfg.models[0]
    evaluation = results.evaluation.get(model, {})
    summarization = results.summarization.get(model, {})
    samples = sorted(runner._output_dir(model).glob("*.txt"))
    if not samples or not summarization.get("successful"):
        raise RuntimeError(f"long-context run failed: {summarization}")

    # document lengths in the checkpoint's OWN BPE tokens, to prove they
    # exceed the one-chip ceiling
    from transformers import AutoTokenizer

    hf_tok = AutoTokenizer.from_pretrained(str(work / "ckpt"))
    doc_bpe_lens = [len(hf_tok.encode(d)) for d in docs]
    # enforce the artifact's headline claims — a parameter choice that
    # falsifies them must fail the run, not write a misleading artifact
    if not all(n > one_chip_ceiling for n in doc_bpe_lens):
        raise RuntimeError(
            f"doc lengths {doc_bpe_lens} do not all exceed the one-chip "
            f"ceiling ({one_chip_ceiling}); raise --tokens-per-doc"
        )
    strategy_cut = cfg.max_context - cfg.max_new_tokens
    if any(n > strategy_cut for n in doc_bpe_lens):
        raise RuntimeError(
            f"doc lengths {doc_bpe_lens} exceed the truncated strategy's "
            f"cut ({strategy_cut}); the 'UN-truncated' claim would be false "
            "— raise --max-context or lower --tokens-per-doc"
        )

    artifact = {
        "what": (
            "long-context capability chain: REAL trained HF checkpoint "
            "(max_position_embeddings=512, the one-chip ceiling) -> "
            "--long-context truncated pipeline over a (data=2, seq=4) mesh "
            "-> every document summarized UN-truncated in one ring-prefill "
            "forward -> ROUGE. The reference cuts all inputs to its 16k "
            "context (runners/run_summarization_ollama.py:8-13); this "
            "framework's ceiling scales with the mesh seq axis."
        ),
        "mesh": {"data": 2, "seq": 4},
        "jax_devices": len(jax.devices("cpu")),
        "one_chip_max_seq_len": one_chip_ceiling,
        "doc_bpe_token_lengths": doc_bpe_lens,
        "all_docs_exceed_one_chip_ceiling": all(
            n > one_chip_ceiling for n in doc_bpe_lens
        ),
        "corpus": {
            "docs": corpus_stats["documents"]["total_files"],
            "avg_doc_words": corpus_stats["documents"]["avg_tokens_per_file"],
        },
        "checkpoint": ckpt_info,
        "summarization": {
            k: summarization.get(k)
            for k in ("successful", "failed", "total_chunks", "total_time")
        },
        "evaluation": evaluation,
        "sample_generated_summary": samples[0].read_text(encoding="utf-8")[:400],
        "wall_seconds": round(time.time() - t0, 1),
        "tpu_note": (
            "run on 8 virtual CPU devices (no multi-chip hardware on this "
            "host); the compiled program is the same SPMD module a v5e-8 "
            "executes — see tests/test_backend_long_context.py for the "
            "greedy-parity proofs"
        ),
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(
        json.dumps(artifact, indent=1, ensure_ascii=False), encoding="utf-8"
    )
    print(json.dumps({
        "rougeL": evaluation.get("rouge_scores", {}).get("rougeL_f1"),
        "docs_exceed_ceiling": artifact["all_docs_exceed_one_chip_ceiling"],
        "out": str(out),
        "wall_seconds": artifact["wall_seconds"],
    }))


if __name__ == "__main__":
    main()
