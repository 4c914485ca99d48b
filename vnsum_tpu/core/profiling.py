"""Tracing / profiling subsystem.

The reference has no profiler integration — only LangSmith `@traceable` on one
driver (runners/run_summarization_ollama_mapreduce_critique.py:21,403, active
only when LangSmith env vars are set) and manual wall-clock spans stored in the
run record (run_full_evaluation_pipeline.py:439,572-591). This module keeps
those capabilities and makes them first-class:

- `host_span(layer, name, sink)` — THE span primitive for host work: one
  `time.monotonic()` interval given to the profiler (a `TraceAnnotation`
  named `layer/name`, so a device trace's idle gaps carry the program's own
  names), to the installed obs collector (`obs.trace.emit`, bare name) and to
  an always-on aggregate (`sink`: `{layer/name: SpanStats}`). Names are a
  contract like the kernels' `name=`: fixed strings, no shape or number in
  one; shapes, rows and indices go in the keyword arguments.
- `Tracer.span(name)` — nested wall-clock spans with aggregated statistics,
  thread-safe (strategy batches may fan out over a thread pool), persisted in
  the structured run record instead of log lines. Each is a `host_span` (so
  it reaches the profiler too) kept under a hierarchical `parent/child` key
  and on a timeline of the obs span model (`obs/trace.Span`), so a pipeline
  run can export the same Perfetto-loadable Chrome trace the serving
  `/debug/trace` endpoint serves (`Tracer.chrome_trace()`, written next to
  results by pipeline/runner.py when profiling is armed).
- `device_profile(log_dir)` — `jax.profiler.trace` wrapper producing TensorBoard
  / Perfetto traces of the on-device work (the TPU-native analog of the
  reference's LangSmith tracing). Gated: no-op unless a directory is given or
  `VNSUM_PROFILE_DIR` is set, mirroring the reference's env-gated LangSmith
  activation (...critique.py:22-23).
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
import sys
import threading
import time
from collections import Counter
from dataclasses import dataclass

from ..obs.trace import Span, SpanRecorder, emit


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


_TraceAnnotation = None   # jax.profiler's, once jax is in the process


class host_span:
    """One interval of host work, read once and given to three readers.

    ``with host_span("engine", "wait", sink, B=8, S=8192):`` reads
    ``time.monotonic()`` at entry and at exit; the block runs inside a
    ``jax.profiler.TraceAnnotation("engine/wait", B=8, S=8192)`` (the
    profiler's clock, the device plane's file: `benchmarks/trace_reduce.py`
    names idle gaps by it); at exit the interval is added to ``sink``
    (``{"engine/wait": SpanStats}``, always on) and emitted to the obs
    collector under ``event`` (default: the bare ``name``), if one is
    installed. ``note(**kw)`` adds arguments known only inside the block;
    they reach the collector, not the annotation, which is open by then.
    ``t0`` and ``dur`` stay readable after the block. With no profiler
    session and no collector a span costs two clock reads, one dict update
    and an empty TraceMe. The import of ``jax.profiler`` is this module's
    (``obs/`` stays stdlib-only), and it is taken only once something else
    has imported jax: no profiler session runs in a process that has not,
    and a FakeBackend server must not pay a cold jax import for a span.
    """

    __slots__ = ("full", "event", "sink", "args", "t0", "dur", "_ann")

    def __init__(self, layer: str, name: str, sink: dict | None = None,
                 event: str | None = None, **args) -> None:
        self.full = f"{layer}/{name}"
        self.event = event or name
        self.sink = sink
        self.args = args
        self.t0 = self.dur = 0.0

    def note(self, **args) -> None:
        self.args.update(args)

    def __enter__(self) -> "host_span":
        global _TraceAnnotation
        if _TraceAnnotation is None and "jax" in sys.modules:
            from jax.profiler import TraceAnnotation as _TraceAnnotation
        self._ann = None
        if _TraceAnnotation is not None:
            self._ann = _TraceAnnotation(self.full, **self.args)
            self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur = time.monotonic() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.sink is not None:
            st = self.sink.get(self.full)
            if st is None:
                st = self.sink[self.full] = SpanStats()
            st.add(self.dur)
        emit(self.event, self.t0, self.dur, **self.args)
        return False


class Tracer:
    """Aggregating wall-clock tracer over the shared obs span model.

    Span names are hierarchical: nested spans get `parent/child` keys, so the
    run record shows e.g. `summarize/batch` under `summarize`. One Tracer is
    shared per pipeline run; use `reset()` between runs.

    Two views of the same spans: `stats()` aggregates per name (bounded
    state, any run length — what lands in the run record), and `timeline()`
    keeps the first `timeline_maxlen` raw spans for `chrome_trace()` export.
    Both take the one interval the span's `host_span` read, so they can
    never disagree about a span's duration. The profiler sees the span as
    `layer/name` (fixed strings: the hierarchy is this record's alone).
    """

    def __init__(self, timeline_maxlen: int = 4096,
                 layer: str = "pipeline") -> None:
        self.layer = layer
        self._stats: dict[str, SpanStats] = {}
        self._lock = threading.Lock()
        self._rec = SpanRecorder(maxlen=timeline_maxlen)
        self._local = threading.local()

    def _aggregate(self, full_name: str, duration: float) -> None:
        with self._lock:
            self._stats.setdefault(full_name, SpanStats()).add(duration)

    @contextlib.contextmanager
    def span(self, name: str, layer: str | None = None, **args):
        if not hasattr(self._local, "stack"):
            self._local.stack = []
        stack = self._local.stack
        key = "/".join([*stack, name])
        stack.append(name)
        sp = host_span(layer or self.layer, name, **args)
        try:
            with sp:
                yield sp
        finally:
            stack.pop()
            self._aggregate(key, sp.dur)
            self._rec.add(key, sp.t0, sp.dur, **sp.args)

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
