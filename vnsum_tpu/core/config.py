"""Typed configuration for the pipeline and strategies.

Mirrors the semantics of the reference's dict-based config
(run_full_evaluation_pipeline.py:973-1027) — same knob names and defaults —
but as dataclasses with validation, serialization, and per-approach defaults,
so every run record embeds the exact config it ran with.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Literal

ApproachName = Literal[
    "mapreduce",
    "mapreduce_critique",
    "iterative",
    "truncated",
    "mapreduce_hierarchical",
    "skeleton",
]

APPROACHES: tuple[str, ...] = (
    "mapreduce",
    "mapreduce_critique",
    "iterative",
    "truncated",
    "mapreduce_hierarchical",
    "skeleton",
)


@dataclass(frozen=True)
class GenerationConfig:
    """Decoding parameters for one backend.generate() call."""

    # None = inherit the backend's constructor default; a config passed only
    # to set temperature/eos must not silently override the decode budget
    max_new_tokens: int | None = None
    temperature: float = 0.0  # 0.0 => greedy (ref: run_summarization.py:44)
    top_k: int = 0            # 0 => disabled
    top_p: float = 1.0
    eos_ids: tuple[int, ...] = ()
    seed: int = 0
    # reference-guided speculative decoding (vnsum_tpu.spec): propose up to
    # spec_k continuation tokens per row by n-gram matching the emitted
    # stream against the request's reference text (backend.generate's
    # per-prompt `references`), verified in one batched forward. 0 = off —
    # the default engine decode path is untouched and outputs are
    # bit-identical to pre-spec builds. Greedy outputs are identical at ANY
    # spec_k (acceptance is exact argmax prefix match); sampling stays
    # distribution-lossless but consumes randomness differently.
    spec_k: int = 0
    # longest emitted-stream suffix the drafter tries to match (>=1)
    spec_ngram: int = 3

    def with_(self, **kw) -> "GenerationConfig":
        return dataclasses.replace(self, **kw)


@dataclass
class EvalConfig:
    """Evaluation stack settings (ref run_full_evaluation_pipeline.py:984-990)."""

    embedding_model: str = "all-MiniLM-L6-v2"
    # local HF BERT-family checkpoint dir (config.json + safetensors +
    # tokenizer); when set, BERTScore/semsim run with converted pretrained
    # weights (comparable to BASELINE.md) instead of random init
    embedding_dir: str | None = None
    include_llm_eval: bool = False
    use_openrouter: bool = True
    llm_model: str = "openai/gpt-4o-mini"
    # local judge: run G-Eval through the Backend protocol instead of an
    # HTTP endpoint — the offline path for air-gapped hosts. Forms:
    # "fake" (CI), "ollama:<model>", "tpu:<registry-name>" (random weights —
    # plumbing/containment only). Takes precedence over API keys.
    judge_backend: str | None = None
    max_samples: int | None = None
    bert_batch_size: int = 32


@dataclass
class PipelineConfig:
    """Full pipeline configuration.

    Defaults follow the reference base_config + per-approach configs
    (run_full_evaluation_pipeline.py:973-1027); `approach_defaults()` applies
    the per-approach overrides.
    """

    approach: str = "mapreduce"
    models: list[str] = field(default_factory=lambda: ["llama3.2-3b"])
    backend: str = "tpu"  # tpu | ollama | fake
    ollama_url: str = "http://localhost:11434"
    max_new_tokens: int = 1024
    docs_dir: str = "data_1/doc"
    summary_dir: str = "data_1/summary"
    generated_summaries_dir: str = "data_1/generated_summaries"
    results_dir: str = "evaluation_results"
    logs_dir: str = "logs"
    max_samples: int | None = None

    # chunking (mapreduce / critique / hierarchical)
    chunk_size: int = 12000
    chunk_overlap: int = 200
    token_max: int = 10000

    # iterative
    iterative_chunk_size: int = 12000
    iterative_chunk_overlap: int = 200

    # truncated
    max_context: int = 16384

    # critique
    max_critique_iterations: int = 2

    # hierarchical
    max_depth: int = 1
    tree_json_path: str = "data_1/document_tree.json"

    # failure containment: re-submit a failed document batch this many extra
    # times before recording its documents as failed (reference: none —
    # SURVEY.md §5 "No retries anywhere")
    max_batch_retries: int = 1
    retry_backoff: float = 1.0

    # engine
    batch_size: int = 8
    # documents submitted to the strategy per round; 0 = auto (4x batch_size).
    # Bigger groups pack map/collapse/reduce calls into fuller device batches
    # (a group of batch_size docs leaves reduce rounds running B=2/B=4
    # half-empty dispatches — each a fresh bucket compile); the cost is
    # coarser resume granularity (summaries write per group)
    doc_group_size: int = 0
    tokenizer: str = "byte"  # byte | hf:<name-or-path>
    mesh_shape: dict[str, int] = field(default_factory=dict)
    # ring-attention prefill + seq-sharded decode (backend/long_context.py):
    # prompts run UN-truncated up to seq_axis × the one-chip limit; requires
    # backend=tpu and a mesh with a seq axis > 1
    long_context: bool = False
    # int8-quantize the long-context prefill KV cache. LOSSY (per-position
    # int8 round-trip on cached K/V) but halves ring-decode HBM traffic —
    # the dominant cost of long-context decode. Off by default because
    # `quantize` alone promises exact weight-only quantization
    long_context_quantize_kv: bool = False
    # int8 weight-only quantization (per-output-channel scales — exact
    # w.r.t. the quantized weights; models/quant.py). The engine's decode is
    # weight-bandwidth-bound, so this is most of the single-chip speedup
    quantize: bool = False
    # W8A8 prefill: ALSO int8-quantize activations (per-token absmax) into
    # the prefill matmuls — double-rate s8xs8 MXU dots. LOSSY (activation
    # rounding ~1/127 per matmul input), so off by default; quality runs
    # should A/B it. Requires quantize=True
    quantize_act: bool = False
    dtype: str = "bfloat16"
    # local HF checkpoint dir (config.json + *.safetensors + tokenizer files)
    # for the tpu backend: weights are converted via models.convert and the
    # checkpoint's tokenizer is used unless `tokenizer` is explicitly hf:<..>
    weights_dir: str | None = None

    evaluation: EvalConfig = field(default_factory=EvalConfig)

    def __post_init__(self) -> None:
        if self.approach not in APPROACHES:
            raise ValueError(
                f"unknown approach {self.approach!r}; expected one of {APPROACHES}"
            )
        if self.long_context_quantize_kv and not self.long_context:
            raise ValueError(
                "long_context_quantize_kv requires long_context=True — the "
                "one-chip engine ignores it, so the run would claim an int8 "
                "prefill cache while using the exact one"
            )
        if self.chunk_overlap >= self.chunk_size:
            raise ValueError("chunk_overlap must be smaller than chunk_size")
        if self.iterative_chunk_overlap >= self.iterative_chunk_size:
            raise ValueError(
                "iterative_chunk_overlap must be smaller than iterative_chunk_size"
            )
        if self.weights_dir and len(self.models) > 1:
            raise ValueError(
                "weights_dir points at ONE checkpoint; with multiple models "
                "every entry would silently run the same weights — run one "
                "model per weights_dir"
            )
        if self.weights_dir and self.backend != "tpu":
            raise ValueError(
                f"weights_dir requires backend='tpu' (got {self.backend!r}); "
                "other backends would silently ignore the checkpoint and "
                "evaluate a different model"
            )
        if self.quantize and self.backend != "tpu":
            raise ValueError(
                f"quantize requires backend='tpu' (got {self.backend!r}); "
                "other backends would silently run full-precision while the "
                "run record claims int8"
            )
        if self.quantize_act and not self.quantize:
            raise ValueError(
                "quantize_act (W8A8 prefill) requires quantize=True — "
                "without int8 weights there is no s8xs8 matmul to run"
            )
        if self.quantize_act and self.long_context:
            raise ValueError(
                "quantize_act is one-chip-engine only; the long-context "
                "ring prefill would silently run weight-only while the run "
                "record claims W8A8"
            )
        if self.long_context:
            if self.backend != "tpu":
                raise ValueError(
                    f"long_context requires backend='tpu' (got {self.backend!r})"
                )
            if self.mesh_shape.get("seq", 1) < 2:
                raise ValueError(
                    "long_context requires a mesh with a seq axis > 1 "
                    "(e.g. --mesh seq=4,data=2) — the seq axis is what "
                    "multiplies the context ceiling"
                )

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, ensure_ascii=False)

    @classmethod
    def from_dict(cls, d: dict) -> "PipelineConfig":
        d = dict(d)
        ev = d.pop("evaluation", None)
        known = {f.name for f in dataclasses.fields(cls)}
        extra = {k: v for k, v in d.items() if k not in known}
        if extra:
            raise ValueError(f"unknown config keys: {sorted(extra)}")
        cfg = cls(**d)
        if ev is not None:
            cfg.evaluation = EvalConfig(**ev) if isinstance(ev, dict) else ev
        return cfg


def approach_defaults(approach: str) -> dict:
    """Per-approach config overrides, matching the reference's approach_config
    blocks (run_full_evaluation_pipeline.py:993-1027)."""
    if approach == "mapreduce":
        return {"chunk_size": 12000, "chunk_overlap": 200, "token_max": 10000}
    if approach == "iterative":
        return {"iterative_chunk_size": 12000, "iterative_chunk_overlap": 200}
    if approach == "truncated":
        return {"max_context": 16384}
    if approach == "mapreduce_critique":
        return {
            "chunk_size": 12000,
            "chunk_overlap": 200,
            "token_max": 10000,
            "max_critique_iterations": 2,
            "max_new_tokens": 2048,
        }
    if approach == "mapreduce_hierarchical":
        return {"chunk_size": 12000, "chunk_overlap": 200, "max_depth": 1}
    if approach == "skeleton":
        # Skeleton-of-Thought (arXiv 2307.15337): same context contract as
        # truncated — the outline/expand fan-out runs over what fits
        return {"max_context": 16384}
    raise ValueError(f"unknown approach: {approach}")
