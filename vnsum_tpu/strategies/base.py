"""Strategy layer scaffolding.

The reference wraps each approach in a LangGraph StateGraph whose fan-out is
serial in practice (SURVEY.md §1). Here a strategy is a plain driver object:
host-side Python owns the (data-dependent) control flow — collapse-until-fits,
critique accept checks, tree recursion — and every round's LLM calls are
submitted to the backend as ONE batch, across chunks and across documents
(SURVEY.md §7: "parallelism moves from the orchestration layer into XLA").
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Protocol

from ..backend.base import Backend
from ..core.profiling import host_span
from ..text.tokenizer import whitespace_token_count


@dataclass
class StrategyResult:
    summary: str
    num_chunks: int = 1
    llm_calls: int = 0
    rounds: int = 0
    meta: dict = field(default_factory=dict)


class Strategy(Protocol):
    """Re-entrancy contract (the serving layer depends on it): a strategy
    instance holds only configuration — every run's mutable state is local
    to the summarize_batch call — so ONE instance may serve concurrent
    calls from many threads. The optional ``backend`` override lets each
    call submit its rounds through a different Backend (vnsum_tpu.serve
    passes a per-request, deadline-bound QueuedBackend into a shared
    strategy instance); token counting stays on the construction-time
    backend, which is host-side and thread-safe."""

    name: str

    def summarize_batch(
        self, docs: list[str], *, backend: Backend | None = None
    ) -> list[StrategyResult]: ...

    def summarize(
        self, doc: str, *, backend: Backend | None = None
    ) -> StrategyResult: ...


def strategy_span(strategy, name: str, **args):
    """A host span of the strategy layer (``strategy/<name>`` to the
    profiler): through the run's Tracer where ``get_strategy`` was handed
    one, so the run record's ``tracing`` holds it too, else the bare
    primitive. One a round or a document stage, never one a chunk."""
    tracer = getattr(strategy, "tracer", None)
    if tracer is not None:
        return tracer.span(name, layer="strategy", **args)
    return host_span("strategy", name, **args)


class _BatchCounter:
    """Wraps backend.generate to count calls for StrategyResult accounting.

    Although rounds batch prompts across documents, every prompt belongs to
    exactly one document — callers pass ``owners`` (one doc index per prompt)
    so `calls_by_owner` carries TRUE per-document llm_calls, matching what the
    reference's serial loop records (run_full_evaluation_pipeline.py:575-582)."""

    def __init__(self, backend: Backend, max_new_tokens: int | None = None):
        self.backend = backend
        self.max_new_tokens = max_new_tokens
        self.calls_by_owner: dict[int, int] = {}

    def __call__(
        self,
        prompts: list[str],
        owners: list[int],
        references: list[str | None] | None = None,
        cache_hints: list[str | None] | None = None,
    ) -> list[str]:
        """``references`` optionally aligns one source text per prompt —
        the seam reference-guided speculative decoding rides (strategies
        pass the chunk being summarized). ``cache_hints`` aligns one
        expected-to-recur prompt PREFIX per prompt — the prefix KV cache
        seam (strategies pass their template header, prompts.py
        template_header). Backends without either feature ignore them."""
        if not prompts:
            return []
        if len(owners) != len(prompts):
            raise ValueError("owners must tag every prompt")
        if references is not None and len(references) != len(prompts):
            raise ValueError("references must align with prompts")
        if cache_hints is not None and len(cache_hints) != len(prompts):
            raise ValueError("cache_hints must align with prompts")
        for o in owners:
            self.calls_by_owner[o] = self.calls_by_owner.get(o, 0) + 1
        # keep the legacy call shape for backends (and test doubles) that
        # predate the advisory kwargs: pass each only when it carries data
        kw = {}
        if references is not None and any(references):
            kw["references"] = references
        if cache_hints is not None and any(cache_hints):
            kw["cache_hints"] = cache_hints
        return self.backend.generate(
            prompts, max_new_tokens=self.max_new_tokens, **kw
        )


def split_by_token_budget(
    texts: list[str],
    budget: int,
    count: Callable[[str], int] = whitespace_token_count,
) -> list[list[str]]:
    """Greedy grouping: consecutive texts accumulate until adding one would
    exceed ``budget`` (langchain split_list_of_docs semantics used by the
    reference collapse, runners/..._mapreduce.py:130-137). A single oversized
    text forms its own group."""
    groups: list[list[str]] = []
    cur: list[str] = []
    cur_total = 0
    for t in texts:
        n = count(t)
        if cur and cur_total + n > budget:
            groups.append(cur)
            cur, cur_total = [], 0
        cur.append(t)
        cur_total += n
    if cur:
        groups.append(cur)
    return groups


STRATEGY_REGISTRY: dict[str, type] = {}


def register_strategy(cls):
    STRATEGY_REGISTRY[cls.name] = cls
    return cls


def get_strategy(name: str, backend: Backend, config, tracer=None, **kw):
    """Instantiate a strategy from PipelineConfig-style settings; ``tracer``
    (a ``core.profiling.Tracer``) receives its host spans."""
    if name not in STRATEGY_REGISTRY:
        raise ValueError(
            f"unknown strategy {name!r}; have {sorted(STRATEGY_REGISTRY)}"
        )
    strategy = STRATEGY_REGISTRY[name].from_config(backend, config, **kw)
    strategy.tracer = tracer
    return strategy
