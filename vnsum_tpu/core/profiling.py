"""Tracing / profiling subsystem.

The reference has no profiler integration — only LangSmith `@traceable` on one
driver (runners/run_summarization_ollama_mapreduce_critique.py:21,403, active
only when LangSmith env vars are set) and manual wall-clock spans stored in the
run record (run_full_evaluation_pipeline.py:439,572-591). This module keeps
those capabilities and makes them first-class:

- `Tracer.span(name)` — nested wall-clock spans with aggregated statistics,
  thread-safe (strategy batches may fan out over a thread pool), persisted in
  the structured run record instead of log lines. Rebased onto the obs span
  model (`obs/trace.SpanRecorder`): pipeline runs and the serving layer now
  share ONE span primitive, so a pipeline run can export the same
  Perfetto-loadable Chrome trace the serving `/debug/trace` endpoint serves
  (`Tracer.chrome_trace()`, written next to results by pipeline/runner.py
  when profiling is armed).
- `device_profile(log_dir)` — `jax.profiler.trace` wrapper producing TensorBoard
  / Perfetto traces of the on-device work (the TPU-native analog of the
  reference's LangSmith tracing). Gated: no-op unless a directory is given or
  `VNSUM_PROFILE_DIR` is set, mirroring the reference's env-gated LangSmith
  activation (...critique.py:22-23).
- `annotate(name)` — `jax.profiler.TraceAnnotation` passthrough so host-side
  phases show up inside device traces.
- `hlo_scope_map(hlo_text)` — the device half: which `jax.named_scope` each
  instruction of a compiled program was written under. The device trace
  names an operation by its HLO line alone, so the program hands this map
  to whoever reads the trace (`TpuBackend.scope_maps`,
  `scripts/trace_by_scope.py`).
"""
from __future__ import annotations

import contextlib
import os
import re
import threading
import time
from collections import Counter
from dataclasses import dataclass

from ..obs.trace import Span, SpanRecorder


@dataclass
class SpanStats:
    count: int = 0
    total_s: float = 0.0
    min_s: float = float("inf")
    max_s: float = 0.0

    def add(self, duration: float) -> None:
        self.count += 1
        self.total_s += duration
        self.min_s = min(self.min_s, duration)
        self.max_s = max(self.max_s, duration)

    def to_dict(self) -> dict:
        return {
            "count": self.count,
            "total_s": self.total_s,
            "mean_s": self.total_s / self.count if self.count else 0.0,
            "min_s": self.min_s if self.count else 0.0,
            "max_s": self.max_s,
        }


class Tracer:
    """Aggregating wall-clock tracer over the shared obs span model.

    Span names are hierarchical: nested spans get `parent/child` keys, so the
    run record shows e.g. `summarize/batch` under `summarize`. One Tracer is
    shared per pipeline run; use `reset()` between runs.

    Two views of the same spans: `stats()` aggregates per name (bounded
    state, any run length — what lands in the run record), and `timeline()`
    keeps the first `timeline_maxlen` raw spans for `chrome_trace()` export.
    The recorder's `on_close` hook feeds aggregation, so the two views can
    never disagree about a span's duration.
    """

    def __init__(self, timeline_maxlen: int = 4096) -> None:
        self._stats: dict[str, SpanStats] = {}
        self._lock = threading.Lock()
        self._rec = SpanRecorder(maxlen=timeline_maxlen,
                                 on_close=self._aggregate)

    def _aggregate(self, full_name: str, duration: float) -> None:
        with self._lock:
            self._stats.setdefault(full_name, SpanStats()).add(duration)

    def span(self, name: str):
        return self._rec.span(name)

    def record(self, name: str, duration: float) -> None:
        """Record an externally-timed span (e.g. a device-side step time)."""
        self._aggregate(name, duration)
        self._rec.add(name, time.monotonic() - duration, duration)

    def stats(self) -> dict[str, dict]:
        with self._lock:
            return {k: v.to_dict() for k, v in sorted(self._stats.items())}

    def timeline(self) -> list[Span]:
        """Raw spans in completion order (bounded by timeline_maxlen)."""
        return self._rec.spans()

    def chrome_trace(self, process_name: str = "pipeline") -> dict:
        """Perfetto-loadable Chrome trace-event JSON of the timeline — the
        offline twin of the serving layer's /debug/trace dump."""
        from ..obs.export import spans_to_chrome

        return spans_to_chrome(self.timeline(), process_name)

    def to_dict(self) -> dict:
        return {"spans": self.stats()}

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()
        self._rec.clear()


@contextlib.contextmanager
def device_profile(log_dir: str | None = None):
    """Capture a JAX device profile for the enclosed block.

    `log_dir` falls back to `$VNSUM_PROFILE_DIR`; when neither is set this is
    a no-op, so production paths can wrap their hot sections unconditionally.
    View with TensorBoard (`tensorboard --logdir <dir>`) or Perfetto.
    """
    log_dir = log_dir or os.environ.get("VNSUM_PROFILE_DIR")
    if not log_dir:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield


@contextlib.contextmanager
def annotate(name: str):
    """Named region inside a device trace (XPlane TraceMe annotation)."""
    try:
        import jax

        cm = jax.profiler.TraceAnnotation(name)
    except Exception:  # pragma: no cover - jax always present in this image
        cm = contextlib.nullcontext()
    with cm:
        yield


_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# what JAX puts into an op_name besides the scopes someone wrote: the
# transformations (``jit(generate)``, ``vmap(...)``: anything called with
# brackets) and the bodies of control flow and calls
_OP_NAME_WRAPPERS = frozenset({
    "while", "body", "cond", "closed_call", "core_call", "checkpoint",
    "remat", "custom_jvp_call", "custom_vjp_call", "pjit",
})


def hlo_scope_map(hlo_text: str) -> dict[str, str]:
    """{instruction name: scope path} for every instruction of a compiled
    module's text (``compiled.as_text()``).

    The scope path is the instruction's ``op_name`` without its last part
    (the primitive) and without JAX's own wrappers, joined by ``/``:
    ``jit(generate)/decode/while/body/mlp/dot_general`` gives ``decode/mlp``.
    An instruction without metadata, or written under no scope, maps to
    ``""``. A fusion carries its own metadata, that of one of the
    instructions fused into it: a fusion that spans two scopes is booked
    under one of them. A fusion the compiler left without metadata (a
    multi-output fusion, whose root is the compiler's own tuple) takes the
    scope most of the instructions fused into it were written under. Names
    are as the device trace spells them (``fusion.989``, no ``%``).
    """
    scopes: dict[str, str] = {}
    inside: dict[str, Counter] = {}      # computation -> its scopes, counted
    bare_calls: dict[str, str] = {}      # instruction without metadata -> callee
    computation = ""
    for line in hlo_text.splitlines():
        inst = _HLO_INSTRUCTION.match(line)
        if inst is None:
            header = _HLO_COMPUTATION.match(line)
            if header:
                computation = header.group(1)
            continue
        op_name = _HLO_OP_NAME.search(line)
        parts = op_name.group(1).split("/")[:-1] if op_name else []
        scope = "/".join(
            p for p in parts if p not in _OP_NAME_WRAPPERS and "(" not in p)
        scopes[inst.group(1)] = scope
        if scope:
            inside.setdefault(computation, Counter())[scope] += 1
        elif op_name is None and (callee := _HLO_CALLS.search(line)):
            bare_calls[inst.group(1)] = callee.group(1)
    for name, callee in bare_calls.items():
        if callee in inside:
            scopes[name] = inside[callee].most_common(1)[0][0]
    return scopes
