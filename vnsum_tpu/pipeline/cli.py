"""CLI entry point, flag-compatible with the reference's argparse surface
(run_full_evaluation_pipeline.py:956-970) plus the TPU-era knobs
(--backend, --mesh, --tokenizer, --batch-size per BASELINE.json).
"""
from __future__ import annotations

import argparse
import sys

from ..core.config import APPROACHES, PipelineConfig, approach_defaults


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="vnsum-pipeline",
        description="Run the summarization evaluation pipeline",
    )
    p.add_argument("--approach", choices=APPROACHES, default="mapreduce")
    p.add_argument(
        "--models", nargs="+", default=["llama3.2:3b"],
        help="Models to evaluate (TPU backend: names in MODEL_REGISTRY)",
    )
    p.add_argument("--max-samples", type=int, default=None)
    p.add_argument("--tree-json", default="data_1/document_tree.json")
    p.add_argument("--max-depth", type=int, default=1)
    p.add_argument(
        "--backend", choices=["tpu", "ollama", "hf", "fake"], default="tpu"
    )
    p.add_argument("--ollama-url", default="http://localhost:11434")
    p.add_argument("--docs-dir", default="data_1/doc")
    p.add_argument("--summary-dir", default="data_1/summary")
    p.add_argument("--generated-summaries-dir", default="data_1/generated_summaries")
    p.add_argument("--results-dir", default="evaluation_results")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--tokenizer", default="byte", help="byte or hf:<name-or-path>")
    p.add_argument(
        "--mesh", default="", help='device mesh, e.g. "data=2,model=4"'
    )
    p.add_argument(
        "--quantize", action="store_true",
        help="int8 weight-only quantization for the tpu backend (halves "
        "decode HBM traffic). The one-chip engine's KV cache quantizes "
        "automatically whenever its Pallas kernels are active (independent "
        "of this flag); the long-context prefill cache stays exact — its "
        "lossy int8 mode is opt-in via --quantize-kv-long",
    )
    p.add_argument(
        "--quantize-act", action="store_true",
        help="W8A8 prefill: int8-quantize activations (per-token absmax) "
        "into the int8-weight matmuls — double-rate MXU dots on prefill. "
        "LOSSY (activation rounding); A/B against --quantize alone for "
        "quality runs. Requires --quantize",
    )
    p.add_argument(
        "--quantize-kv-long", action="store_true",
        help="int8-quantize the long-context prefill KV cache (halves "
        "ring-decode HBM traffic per step). LOSSY: cached K/V round-trip "
        "through per-(position,head) int8, so logits drift slightly vs the "
        "exact cache — greedy summaries can differ in late tokens. "
        "Measured drift is small (tests/test_backend_long_context.py "
        "quantize_kv parity bounds); quality-gate runs should A/B it",
    )
    p.add_argument(
        "--long-context", action="store_true",
        help="ring-attention prefill + seq-sharded decode: prompts run "
        "un-truncated up to seq_axis × the one-chip limit (requires "
        "--backend tpu and --mesh with seq>1); pair with --approach "
        "truncated --max-context <long limit> for one-shot full-document "
        "summaries",
    )
    p.add_argument(
        "--weights-dir", default=None,
        help="local HF checkpoint dir for the tpu backend (config.json + "
        "safetensors + tokenizer); e.g. a Llama-3.2-3B checkout. Converted "
        "via models.convert; the checkpoint's tokenizer is used.",
    )
    p.add_argument(
        "--embedding-dir", default=None,
        help="local HF BERT-family checkpoint dir for the embedding metrics "
        "(e.g. an all-MiniLM-L6-v2 checkout); converted via "
        "models.convert_encoder so BERTScore/semsim are pretrained-calibrated",
    )
    p.add_argument(
        "--chunk-size", type=int, default=None,
        help="override the approach-default chunk size (tokens)",
    )
    p.add_argument(
        "--token-max", type=int, default=None,
        help="override the approach-default collapse budget (tokens)",
    )
    p.add_argument(
        "--max-new-tokens", type=int, default=None,
        help="override the approach-default generation budget",
    )
    p.add_argument(
        "--max-context", type=int, default=None,
        help="truncated approach: context budget in tokens (ref default "
        "16384); with --long-context this may exceed the one-chip limit",
    )
    p.add_argument(
        "--include-llm-eval", action="store_true",
        help="run the G-Eval correctness/coherence column (reference "
        "include_llm_eval); needs OPENROUTER_API_KEY/OPENAI_API_KEY or "
        "--judge-backend",
    )
    p.add_argument(
        "--judge-backend", default=None,
        help="offline G-Eval judge over the Backend protocol: 'fake' (CI), "
        "'ollama:<model>', or 'tpu:<registry-name>'; implies "
        "--include-llm-eval",
    )
    return p


def config_from_args(args: argparse.Namespace) -> PipelineConfig:
    overrides = approach_defaults(args.approach)
    mesh_shape = {}
    if args.mesh:
        for part in args.mesh.split(","):
            k, v = part.split("=")
            mesh_shape[k.strip()] = int(v)
    for key in ("chunk_size", "token_max", "max_new_tokens", "max_context"):
        val = getattr(args, key)
        if val is not None:
            overrides[key] = val
    if args.chunk_size is not None:
        # keep overlap a small fraction of the chunk (ref default is
        # 200/12000); an overlap near chunk_size would shrink the splitter
        # stride to almost nothing
        overrides["chunk_overlap"] = min(
            overrides.get("chunk_overlap", 200), max(0, args.chunk_size // 10)
        )
        overrides["iterative_chunk_size"] = args.chunk_size
        overrides["iterative_chunk_overlap"] = overrides["chunk_overlap"]
    cfg = PipelineConfig(
        approach=args.approach,
        weights_dir=args.weights_dir,
        models=list(args.models),
        backend=args.backend,
        ollama_url=args.ollama_url,
        docs_dir=args.docs_dir,
        summary_dir=args.summary_dir,
        generated_summaries_dir=args.generated_summaries_dir,
        results_dir=args.results_dir,
        max_samples=args.max_samples,
        batch_size=args.batch_size,
        tokenizer=args.tokenizer,
        mesh_shape=mesh_shape,
        long_context=args.long_context,
        long_context_quantize_kv=args.quantize_kv_long,
        quantize=args.quantize,
        quantize_act=args.quantize_act,
        tree_json_path=args.tree_json,
        max_depth=args.max_depth,
        **{
            k: v
            for k, v in overrides.items()
            if k not in ("max_depth", "tree_json_path")
        },
    )
    if args.embedding_dir:
        cfg.evaluation.embedding_dir = args.embedding_dir
    if args.include_llm_eval:
        cfg.evaluation.include_llm_eval = True
    if args.judge_backend:
        cfg.evaluation.include_llm_eval = True
        cfg.evaluation.judge_backend = args.judge_backend
    return cfg


def failures(results) -> list[str]:
    """One line per model whose summarization or evaluation failed, or that
    left failed documents behind. The runner's per-document and per-model
    catches keep partial progress; the exit code still has to say so."""
    out = []
    for model, rec in results.summarization.items():
        if rec.get("status") == "failed":
            out.append(f"{model}: summarization failed: {rec.get('error')}")
        elif rec.get("failed", 0):
            out.append(f"{model}: {rec['failed']} document(s) failed")
    for model, ev in results.evaluation.items():
        if ev.get("status") == "failed":
            out.append(f"{model}: evaluation failed: {ev.get('error')}")
    return out


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    from .runner import PipelineRunner

    runner = PipelineRunner(config_from_args(args))
    failed = failures(runner.run())
    for line in failed:
        print(f"vnsum-pipeline: {line}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
