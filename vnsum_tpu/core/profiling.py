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
- `execution_span(...)` — the `host_span` around one device execution (or
  its first or last part): it also takes a `host_snapshot()` at the
  execution's entry and at its exit — what the process and the machine were
  doing meanwhile (`snapshot_delta`), kept only where the engine's account
  finds the execution held past its pace (`EngineStats.note_execution`). A
  program's first call takes none, and between executions the probe's
  thread is parked and its collector callbacks are off the list.
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
import gc
import os
import re
import resource
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


# -- what the host was doing during one device execution -----------------------

SLEEPER_PERIOD_S = 0.05
_TICK_S = 1.0 / os.sysconf("SC_CLK_TCK")


class _HostProbe:
    """The process-wide sources a snapshot reads, set up with the first
    snapshot: the two /proc files held open (one ``pread`` at offset 0 a
    snapshot regenerates a seq file; a file that is not there is left out),
    a ``gc.callbacks`` pair that adds up the collector's seconds and count,
    and a daemon thread that only sleeps ``SLEEPER_PERIOD_S`` at a time and
    keeps the largest lateness of a wake-up — a process that was frozen, or
    a thread that held the interpreter, shows there and nowhere else.

    The pair and the thread work only while an execution is open (``arm``
    at its entry snapshot, ``disarm`` at its exit one): between executions,
    and through every execution that takes no snapshot — a program's first
    call: its trace, lowering and compile — the callbacks are off the
    collector's list and the thread is parked on an event, so the process
    runs no code of the probe there."""

    def __init__(self) -> None:
        self.stat_fd = self._open("/proc/stat")
        self.pressure_fd = self._open("/proc/pressure/cpu")
        self.gc_seconds = 0.0
        self.gc_count = 0
        self._gc_t0 = 0.0
        self.late_max = 0.0
        self.asleep_since = 0.0
        self.pid = os.getpid()
        self._lock = threading.Lock()
        self.open = 0               # executions open now
        self._awake = threading.Event()
        self.sleeper = threading.Thread(
            target=self._sleep, name="vnsum-host-probe", daemon=True)
        self.sleeper.start()

    @staticmethod
    def _open(path: str) -> int | None:
        try:
            return os.open(path, os.O_RDONLY)
        except OSError:
            return None

    def arm(self) -> None:
        """An execution opens: with the first one open, the collector's
        pair goes on its list, the sleeper wakes, and its largest lateness
        starts from zero — the exit's reads what fell inside."""
        with self._lock:
            self.open += 1
            if self.open == 1:
                gc.callbacks.append(self._on_gc)
                self.late_max = 0.0
                self.asleep_since = time.monotonic()
                self._awake.set()

    def disarm(self) -> None:
        with self._lock:
            self.open -= 1
            if self.open == 0:
                gc.callbacks.remove(self._on_gc)
                self._awake.clear()

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0:
            # (a stop whose start ran before the pair was listed counts
            # nothing)
            self.gc_seconds += time.perf_counter() - self._gc_t0
            self.gc_count += 1
            self._gc_t0 = 0.0

    def _sleep(self) -> None:
        while True:
            self._awake.wait()      # parked while no execution is open
            t = self.asleep_since = time.monotonic()
            time.sleep(SLEEPER_PERIOD_S)
            late = time.monotonic() - t - SLEEPER_PERIOD_S
            if late > self.late_max:
                self.late_max = late


_probe: _HostProbe | None = None

SNAPSHOT_FIELDS = (
    "process_cpu_s", "thread_cpu_s", "machine_user_s", "machine_system_s",
    "machine_iowait_s", "machine_steal_s", "cpu_pressure_some_s",
    "loadavg_1m", "involuntary_switches", "gc_s", "gc_collections",
    "sleeper_late_max_s")
# read as they stand at the exit, not as a difference of the two snapshots
_SNAPSHOT_LEVELS = frozenset({"loadavg_1m", "sleeper_late_max_s"})


def host_snapshot(opens: bool = False, closes: bool = False) -> tuple:
    """What this process and its machine have done so far, in the order of
    ``SNAPSHOT_FIELDS``: a dozen numbers, two ``pread``s and no file
    opened after the first call. ``opens=True`` is the snapshot at an
    execution's entry and ``closes=True`` the one at its exit: the
    collector's seconds and the sleeper's lateness are kept between the
    two alone (``_HostProbe.arm``). A number whose source the machine lacks
    is None."""
    global _probe
    p = _probe
    if p is None or p.pid != os.getpid() or not p.sleeper.is_alive():
        # the first snapshot of this process (a fork's child starts anew:
        # threads do not survive a fork)
        p = _probe = _HostProbe()
    if opens:
        p.arm()
    user = system = iowait = steal = pressure = None
    if p.stat_fd is not None:
        # "cpu  user nice system idle iowait irq softirq steal ..."
        f = os.pread(p.stat_fd, 512, 0).split(b"\n", 1)[0].split()
        user, system = int(f[1]) * _TICK_S, int(f[3]) * _TICK_S
        iowait, steal = int(f[5]) * _TICK_S, int(f[8]) * _TICK_S
    if p.pressure_fd is not None:
        # "some avg10=0.00 avg60=0.00 avg300=0.00 total=<microseconds>"
        line = os.pread(p.pressure_fd, 256, 0).split(b"\n", 1)[0]
        pressure = int(line.rsplit(b"=", 1)[1]) * 1e-6
    late = p.late_max
    if p.open:
        # a thread that comes out of a freeze together with the sleeper may
        # get here first: a wake-up that is overdue right now counts as
        # late already
        late = max(late, time.monotonic() - p.asleep_since - SLEEPER_PERIOD_S)
    snapshot = (
        time.process_time(), time.thread_time(), user, system, iowait, steal,
        pressure, os.getloadavg()[0],
        resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw,
        p.gc_seconds, p.gc_count, late)
    if closes:
        p.disarm()
    return snapshot


def snapshot_delta(before: tuple, after: tuple) -> dict:
    """What happened between two snapshots, by ``SNAPSHOT_FIELDS``'s names."""
    out = {}
    for name, a, b in zip(SNAPSHOT_FIELDS, before, after):
        if b is None:
            continue
        out[name] = b if name in _SNAPSHOT_LEVELS else b - a
    return out


class execution_span(host_span):
    """The ``host_span`` around one device execution, or around its first
    (``closes=False``) or last (``opens=False``) part where two spans share
    it: ``before`` is a ``host_snapshot`` taken ahead of the span's entry,
    ``after`` one taken behind its exit, so the span's own interval is what
    it was without them. The engine's account compares the execution with
    its shape's pace and keeps the pair's difference only where it was held
    (``EngineStats.note_execution``); otherwise the two tuples die with
    the span. ``probe=False`` — a program's first call, which is never
    judged — takes no snapshot and is a plain ``host_span``. Not for a span
    that brackets no execution."""

    __slots__ = ("opens", "closes", "before", "after")

    def __init__(self, layer: str, name: str, sink: dict | None = None,
                 event: str | None = None, *, opens: bool = True,
                 closes: bool = True, probe: bool = True, **args) -> None:
        super().__init__(layer, name, sink, event, **args)
        self.opens, self.closes = opens and probe, closes and probe
        self.before = self.after = None

    def __enter__(self) -> "execution_span":
        if self.opens:
            self.before = host_snapshot(opens=True)
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        if self.closes:
            self.after = host_snapshot(closes=True)
        elif self.before is not None and exc[0] is not None:
            # the execution ends here: its last part will never open
            _probe.disarm()
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
