"""The engine's account of its device executions (PR 52):
``EngineStats.executions`` keeps count, blocked seconds, work and pace for
every (program, B, S); an execution held past its shape's pace records what
the host was doing meanwhile (``core.profiling.host_snapshot``), one that is
not costs two snapshots and nothing else; the account reaches ``/metrics``
and the offline run record."""
from __future__ import annotations

import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path
from types import SimpleNamespace

import pytest

from vnsum_tpu.backend.engine import (
    HELD_FACTOR,
    HELD_KEPT,
    EngineStats,
    TpuBackend,
    execution_key,
)
from vnsum_tpu.core import profiling
from vnsum_tpu.core.profiling import (
    SNAPSHOT_FIELDS,
    execution_span,
    host_snapshot,
    host_span,
    snapshot_delta,
)
from vnsum_tpu.models import tiny_llama
from vnsum_tpu.testing import faults

ROOT = Path(__file__).resolve().parents[1]


def make_backend(**kw):
    kw.setdefault("model_config", tiny_llama(max_seq_len=128))
    kw.setdefault("batch_size", 4)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("segment_tokens", 4)
    kw.setdefault("flash", False)   # off-chip: the dense path, by name
    return TpuBackend(**kw)


class Warnings(logging.Handler):
    """What logger ``vnsum.engine`` says at WARNING (it does not propagate)."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.lines: list[str] = []

    def emit(self, record):
        self.lines.append(record.getMessage())

    def __enter__(self):
        logging.getLogger("vnsum.engine").addHandler(self)
        return self

    def __exit__(self, *exc):
        logging.getLogger("vnsum.engine").removeHandler(self)


# -- the snapshot and the span that takes it ----------------------------------


def test_a_snapshot_is_a_dozen_numbers_and_their_difference_has_names():
    before = host_snapshot(opens=True)
    sum(i * i for i in range(200_000))          # some CPU of this thread's
    after = host_snapshot(closes=True)
    assert len(before) == len(after) == len(SNAPSHOT_FIELDS) == 12
    doing = snapshot_delta(before, after)
    assert set(doing) <= set(SNAPSHOT_FIELDS)
    assert {"process_cpu_s", "thread_cpu_s", "loadavg_1m",
            "involuntary_switches", "gc_s", "gc_collections",
            "sleeper_late_max_s"} <= set(doing)
    assert 0 < doing["thread_cpu_s"] <= doing["process_cpu_s"] + 1e-3
    assert doing["gc_collections"] >= 0 and doing["sleeper_late_max_s"] >= 0
    if os.path.exists("/proc/stat"):
        assert {"machine_user_s", "machine_system_s", "machine_iowait_s",
                "machine_steal_s"} <= set(doing)
    assert ("cpu_pressure_some_s" in doing) == os.path.exists(
        "/proc/pressure/cpu")


def test_the_collector_callback_counts_a_collection():
    import gc

    before = host_snapshot(opens=True)
    gc.collect()
    doing = snapshot_delta(before, host_snapshot(closes=True))
    assert doing["gc_collections"] >= 1 and doing["gc_s"] > 0


def test_the_sleeper_starts_with_the_first_snapshot_and_only_sleeps():
    host_snapshot(opens=True)
    probe = profiling._probe
    assert probe.sleeper.is_alive() and probe.sleeper.daemon
    time.sleep(3 * profiling.SLEEPER_PERIOD_S)
    # awake and on time: nothing froze this process meanwhile (a loaded
    # machine may be late by some milliseconds, never by a period's tens)
    assert host_snapshot(closes=True)[-1] < 1.0


def test_between_executions_the_probe_runs_no_code():
    """The collector's pair is on its list and the sleeper awake only while
    an execution is open: a first call's trace, lowering and compile, and
    everything between two executions, runs as if the probe were not there."""
    import gc

    host_snapshot(opens=True)
    probe = profiling._probe
    assert probe.open == 1 and probe._on_gc in gc.callbacks
    host_snapshot(opens=True)                   # a second thread's execution
    host_snapshot(closes=True)
    assert probe.open == 1 and gc.callbacks.count(probe._on_gc) == 1
    host_snapshot(closes=True)
    assert probe.open == 0 and probe._on_gc not in gc.callbacks
    assert not probe._awake.is_set()
    time.sleep(3 * profiling.SLEEPER_PERIOD_S)  # the sleeper's last sleep ends
    collections, parked_at = probe.gc_count, probe.asleep_since
    gc.collect()
    time.sleep(3 * profiling.SLEEPER_PERIOD_S)
    assert probe.gc_count == collections        # nothing counted ...
    assert probe.asleep_since == parked_at      # ... and no wake-up
    # a snapshot outside an execution reads no lateness from a parked thread
    assert host_snapshot()[-1] == probe.late_max
    # an execution whose first part raises ends there: its last never opens
    with pytest.raises(RuntimeError):
        with execution_span("engine", "enqueue", closes=False):
            assert probe.open == 1
            raise RuntimeError("the program's call failed")
    assert probe.open == 0


def test_a_first_call_takes_no_snapshot():
    sink: dict = {}
    host_snapshot()                             # the probe is there
    before = profiling._probe.open
    with execution_span("slot", "prefill", sink, probe=False, B=4) as span:
        assert profiling._probe.open == before
    assert span.before is None and span.after is None
    assert sink["slot/prefill"].count == 1


def test_an_execution_span_is_a_host_span_with_a_snapshot_on_each_side():
    sink: dict = {}
    with execution_span("slot", "segment", sink, event="decode_seg",
                        B=4) as whole:
        pass
    assert isinstance(whole, host_span)
    assert whole.full == "slot/segment" and sink["slot/segment"].count == 1
    assert len(whole.before) == len(whole.after) == 12
    # two spans share one execution: the first opens it, the last closes it
    with execution_span("engine", "enqueue", sink, closes=False) as first:
        pass
    with execution_span("engine", "wait", sink, opens=False) as last:
        pass
    assert first.before is not None and first.after is None
    assert last.before is None and last.after is not None
    assert set(snapshot_delta(first.before, last.after)) <= set(
        SNAPSHOT_FIELDS)


# -- the pace and what is held: the table of ISSUE 52 -------------------------


def spans(*durs):
    """Closed spans of these durations, as ``note_execution`` reads them:
    the clock is injected by handing it the ``dur`` a span would hold."""
    out = [SimpleNamespace(dur=d, t0=100.0, full=name, before=None, after=None)
           for d, name in zip(durs, ("engine/enqueue", "engine/wait"))]
    return out if len(out) > 1 else [out[0], None]


def dispatch(stats, enqueue_s, wait_s, live=24, steps=256, B=8, S=8192):
    first, last = spans(enqueue_s, wait_s)
    stats.note_execution("generate", B, S, first, last, rows=B,
                         pieces=(32 - live, 32), steps=steps)
    return stats.executions[("generate", B, S)]


@pytest.mark.parametrize("pace_s, blocked_s", [
    (8.711, 10.778),    # one Qwen3 dispatch, 10.778 s for 8.687
    (3.551, 5.009),     # Nemotron-H, one in 36
    (1.299, 2.470),     # a SmallThinker reduce, `wait 2.470s` for 1.29
    (9.46, 19.102),     # `wait 19.102s` between dispatches of 9.02 and 9.46
])
def test_an_execution_past_its_pace_by_the_margin_is_held(pace_s, blocked_s):
    stats = EngineStats()
    dispatch(stats, 0.02, pace_s - 0.02)
    with Warnings() as heard:
        ex = dispatch(stats, 0.02, blocked_s - 0.02)
    assert (ex.held, stats.executions_held) == (1, 1)
    assert stats.held_excess_seconds == pytest.approx(blocked_s - pace_s)
    (entry,) = stats.held
    assert entry["program"] == "generate" and entry["side"] == "engine/wait"
    assert entry["blocked_s"] == pytest.approx(blocked_s)
    assert entry["pace_s"] == pytest.approx(pace_s)
    (line,) = heard.lines
    assert line.startswith("execution held: generate B=8 S=8192 blocked "
                           f"{blocked_s:.3f}s on a pace of {pace_s:.3f}s")
    # one stall moves the pace by the factor at most
    assert ex.pace_s == pytest.approx(HELD_FACTOR * pace_s)


def test_one_stall_does_not_hide_the_next():
    stats = EngineStats()
    dispatch(stats, 0.02, 9.44)
    dispatch(stats, 0.02, 19.082)
    ex = dispatch(stats, 0.02, 19.082)
    assert ex.held == 2 and ex.pace_s == pytest.approx(HELD_FACTOR ** 2 * 9.46)
    assert stats.held_excess_seconds == pytest.approx(
        (19.102 - 9.46) + (19.102 - HELD_FACTOR * 9.46))


@pytest.mark.parametrize("earlier_s, blocked_s", [
    (7.46, 8.711),      # the tails dispatch, then an all-live one, one bucket
    (1.866, 1.944),
])
def test_an_execution_within_the_margin_is_not_held(earlier_s, blocked_s):
    stats = EngineStats()
    dispatch(stats, 0.02, earlier_s - 0.02)
    with Warnings() as heard:
        ex = dispatch(stats, 0.02, blocked_s - 0.02)
    assert ex.held == 0 and not stats.held and not heard.lines
    assert ex.pace_s == pytest.approx(blocked_s)    # the largest so far


def join(stats, seconds, live, rows=4, total=16):
    (span, _) = spans(seconds)
    span.full = "slot/prefill"
    stats.note_execution("slot_prefill", 4, 8192, span, units=live,
                         rows=rows, pieces=(total - live, total))
    return stats.executions[("slot_prefill", 4, 8192)]


def test_a_join_of_four_long_prompts_after_four_short_ones_is_not_held():
    """Four short rows are four live pieces of sixteen, four long ones all
    sixteen: six times the seconds, and a piece of a long row costs half as
    much again as a short row's (a later chunk attends to more keys). The
    pace is a live piece's, and an execution that carries more work than any
    before it is not judged: it sets the pace."""
    stats = EngineStats()
    join(stats, 0.30, live=4)
    with Warnings() as heard:
        ex = join(stats, 1.90, live=16)
        assert ex.held == 0 and not heard.lines
        assert ex.pace_s == pytest.approx(1.90 / 16)
        # ... which a short join is then judged by, piece for piece
        assert join(stats, 0.45, live=4).held == 0
        ex = join(stats, 1.20, live=4)
    assert ex.held == 1 and len(heard.lines) == 1
    assert stats.held[0]["pace_s"] == pytest.approx(4 * 1.90 / 16)
    assert stats.held[0]["side"] == "slot/prefill"


def test_a_segment_is_paced_by_the_step():
    stats = EngineStats()

    def segment(seconds, steps):
        (span, _) = spans(seconds)
        span.full = "slot/segment"
        stats.note_execution("segment", 4, 8192, span, units=steps, rows=4,
                             steps=steps, kv_blocks=(3, 10))
        return stats.executions[("segment", 4, 8192)]

    segment(1.80, 128)
    assert segment(0.92, 64).held == 0         # half the steps, half the time
    # one step and the boundary fetch: under the floor, and too little work
    # to set a step's pace by
    ex = segment(0.02, 1)
    assert ex.held == 0 and ex.pace_s == pytest.approx(0.92 / 64)
    assert segment(1.85, 64).held == 1         # 64 steps in 128 steps' time
    ex = segment(1.81, 128)
    assert (ex.held, ex.steps, ex.kv_blocks, ex.kv_blocks_skipped) == (
        1, 128 * 2 + 64 * 2 + 1, 50, 15)


def test_a_first_call_sets_no_pace_and_is_never_held():
    stats = EngineStats()
    first, last = spans(14.0, 9.0)              # the compile, then the run
    stats.note_execution("generate", 8, 8192, first, last, first=True,
                         pieces=(0, 32), steps=256)
    ex = stats.executions[("generate", 8, 8192)]
    assert (ex.count, ex.first_calls, ex.pace_s) == (1, 1, 0.0)
    assert dispatch(stats, 0.02, 9.0, live=32).held == 0    # sets the pace
    assert dispatch(stats, 0.02, 9.2, live=32).held == 0
    assert ex.pace_s == pytest.approx(9.22) and ex.blocked.count == 3


def test_the_side_that_grew_is_named_and_the_list_is_bounded():
    stats = EngineStats()
    dispatch(stats, 0.05, 9.0)
    with Warnings() as heard:
        dispatch(stats, 9.0, 9.0)               # the call itself stalled
    assert stats.held[-1]["side"] == "engine/enqueue"
    assert "side engine/enqueue" in heard.lines[0]
    for i in range(HELD_KEPT + 8):
        dispatch(stats, 0.05, 1e3 * 2 ** i)
    assert len(stats.held) == HELD_KEPT
    assert stats.executions_held == HELD_KEPT + 9


# -- the engine books its four programs ---------------------------------------


@pytest.fixture(scope="module")
def engine():
    return make_backend()


def test_the_four_programs_appear_under_their_keys():
    engine = make_backend()
    engine.generate([f"văn bản số {i} " * (1 + i % 3) for i in range(5)])
    loop = engine.start_slot_loop(4, max_new_tokens=8, prompt_tokens=64)
    loop.admit([(i, p, None) for i, p in enumerate(["một", "hai hai", "ba"])])
    segments = []
    while loop.active:
        segments.append(loop.step())
    loop.close()
    st = engine.stats
    by_program: dict[str, list] = {}
    for (program, B, S), ex in st.executions.items():
        by_program.setdefault(program, []).append(((B, S), ex))
    assert set(by_program) == {"generate", "slot_prefill", "adopt", "segment"}
    count = {p: sum(ex.count for _, ex in v) for p, v in by_program.items()}
    spans_ = {k: v.count for k, v in st.host_spans.items()}
    # the counts the spans have, program by program
    assert count["generate"] == spans_["engine/enqueue"] \
        == spans_["engine/wait"] == 2
    assert count["slot_prefill"] == spans_["slot/prefill"] == 1
    assert count["adopt"] == spans_["slot/adopt"] == 1
    assert count["segment"] == spans_["slot/segment"] == len(segments)
    # ... and their seconds: the account reads the spans' own ``dur``
    for program, names in (("slot_prefill", ["slot/prefill"]),
                           ("segment", ["slot/segment"]),
                           ("generate", ["engine/enqueue", "engine/wait"])):
        assert sum(ex.blocked.total_s for _, ex in by_program[program]) \
            == pytest.approx(sum(st.host_spans[n].total_s for n in names))
    for _, gen in by_program["generate"]:
        assert gen.enqueue.count == gen.wait.count == gen.count
    assert sum(ex.rows for _, ex in by_program["generate"]) == 5
    # every program's first call is told apart from its warm ones, key by
    # key: one first call a program, whatever else compiled in between
    assert all(ex.first_calls == 1 for v in by_program.values()
               for _, ex in v)
    assert count["segment"] > 1
    ((shape, seg),) = by_program["segment"]
    assert shape == (4, 64)
    assert seg.steps == sum(r.steps for r in segments) > 0
    assert seg.rows == sum(r.live for r in segments)
    ((_, pre),) = by_program["slot_prefill"]
    assert pre.rows == 3 and pre.pieces_live + pre.pieces_dead \
        == st.prefill_row_chunks_total - sum(
            ex.pieces_live + ex.pieces_dead
            for _, ex in by_program["generate"])
    assert st.executions_held == 0 and not st.held
    record = engine.engine_record()
    assert set(record["executions"]) == {
        execution_key(k) for k in st.executions}
    assert "segment[B=4,S=64]" in record["executions"]
    json.dumps(record)                          # a run record holds it


def test_a_program_outside_the_account_flips_no_first_call():
    """``prefill_then_decode_logits`` (and the spec programs) compile through
    ``_timed_first_call`` too and are none of the four: the warm execution
    that follows one is booked as warm, snapshots and pace and all."""
    backend = make_backend()
    prompts = ["một văn bản", "hai hai", "ba"]
    backend.generate(prompts)                   # this shape's first call
    (ex,) = backend.stats.executions.values()
    assert (ex.count, ex.first_calls, ex.pace_s) == (1, 1, 0.0)
    compiled = backend.stats.compile_seconds
    backend.prefill_then_decode_logits([5, 6, 7], [8])
    assert backend.stats.compile_seconds > compiled
    backend.generate(prompts)
    assert (ex.count, ex.first_calls) == (2, 1) and ex.pace_s > 0
    assert len(backend.stats.executions) == 1


@pytest.mark.parametrize("n_prompts, dispatches", [(1, 1), (5, 2)])
def test_generate_still_opens_the_fixed_count_of_spans(n_prompts, dispatches):
    """1 a call and 6 a dispatch, as tests/test_host_spans.py pins: the
    account adds no span, it reads the two that bracket the execution."""
    backend = make_backend()
    backend.generate([f"văn bản số {i} " * (1 + i % 3)
                      for i in range(n_prompts)])
    counts = {k: v.count for k, v in backend.stats.host_spans.items()}
    assert sum(counts.values()) == 1 + 6 * dispatches
    assert sum(ex.count for ex in backend.stats.executions.values()) \
        == dispatches


class CountingOs:
    """``os`` as core.profiling sees it, counting what a snapshot does."""

    def __init__(self):
        self.opens = self.preads = 0

    def open(self, *a, **kw):
        self.opens += 1
        return os.open(*a, **kw)

    def pread(self, *a):
        self.preads += 1
        return os.pread(*a)

    def __getattr__(self, name):
        return getattr(os, name)


def test_an_execution_that_is_not_held_costs_two_snapshots_and_no_line(
        engine, monkeypatch):
    prompts = ["một văn bản", "hai hai", "ba"]
    engine.generate(prompts)                    # this shape's first call
    engine.generate(prompts)                    # ... and its pace
    counting = CountingOs()
    monkeypatch.setattr(profiling, "os", counting)
    files = sum(fd is not None for fd in (profiling._probe.stat_fd,
                                          profiling._probe.pressure_fd))
    held_before = engine.stats.executions_held
    with Warnings() as heard:
        engine.generate(prompts)
    # counted, not timed: no file opened, one read a /proc file a snapshot,
    # two snapshots the execution; nothing logged, nothing kept
    assert counting.opens == 0
    assert counting.preads == 2 * files
    assert not heard.lines
    assert engine.stats.executions_held == held_before


# -- a frozen process and a held program are told apart -----------------------

CHILD = r"""
import json, sys, threading, time
sys.path.insert(0, {root!r})
from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.models import tiny_llama
from vnsum_tpu.testing import faults

backend = TpuBackend(model_config=tiny_llama(max_seq_len=128), batch_size=4,
                     max_new_tokens=8, segment_tokens=4, flash=False)
prompts = ["một văn bản", "hai hai", "ba"]
for _ in range(3):                       # the first call, then the pace
    backend.generate(prompts)
for what, delay in (("sleep", 2.0), ("freeze", 1.0)):
    plan = faults.FaultPlan([faults.FaultSpec(
        "engine.wait", "latency", on_call=1, delay_s=delay)])

    def say(plan=plan, what=what):
        while not plan.fired:
            time.sleep(0.005)
        print("IN_WAIT", what, flush=True)

    threading.Thread(target=say, daemon=True).start()
    with faults.injected(plan):
        backend.generate(prompts)
print("HELD", json.dumps(list(backend.stats.held)), flush=True)
"""


def test_a_freeze_and_a_sleep_inside_the_wait_are_told_apart_by_the_sleeper():
    """The child's engine waits on a dispatch twice. The first wait holds a
    forced sleep of 2 s (``vnsum_tpu.testing.faults``, site ``engine.wait``):
    the process is awake and its sleeper on time. During the second this
    process, the sibling, stops the whole child with SIGSTOP and lets it go
    2 s later: the sleeper's next wake-up is late by about 2 s. Both are
    held; the sleeper's lateness says which was which."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    child = subprocess.Popen(
        [sys.executable, "-c", CHILD.format(root=str(ROOT))], cwd=ROOT,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    held = None
    try:
        for line in child.stdout:
            if line.startswith("IN_WAIT freeze"):
                os.kill(child.pid, signal.SIGSTOP)
                time.sleep(2.0)
                os.kill(child.pid, signal.SIGCONT)
            elif line.startswith("HELD "):
                held = json.loads(line[5:])
        assert child.wait(timeout=120) == 0
    finally:
        if child.poll() is None:
            child.kill()
    assert held is not None and len(held) == 2
    slept, frozen = held
    for entry in held:
        assert entry["program"] == "generate"
        assert entry["side"] == "engine/wait"
        assert entry["excess_s"] > 0.9
    assert slept["blocked_s"] >= 2.0 and frozen["blocked_s"] >= 2.0
    # the forced sleep: the process was awake all along
    assert slept["sleeper_late_max_s"] < 1.0
    # the freeze: one wake-up late by about the two seconds it lasted
    assert 1.5 < frozen["sleeper_late_max_s"] < 3.5
    assert frozen["sleeper_late_max_s"] > slept["sleeper_late_max_s"] + 1.0
    # neither burnt the CPU meanwhile
    assert slept["process_cpu_s"] < 1.0 and frozen["process_cpu_s"] < 1.0


def test_a_forced_sleep_inside_the_wait_is_held_and_reaches_the_collector(
        engine):
    from vnsum_tpu.obs.trace import BatchTrace, reset_collector, set_collector

    prompts = ["một văn bản", "hai hai", "ba"]
    engine.generate(prompts)
    engine.generate(prompts)
    held_before = engine.stats.executions_held
    excess_before = engine.stats.held_excess_seconds
    plan = faults.FaultPlan([faults.FaultSpec(
        "engine.wait", "latency", on_call=1, delay_s=0.6)])
    bt = BatchTrace(batch_id=0, occupancy=1)
    token = set_collector(bt)
    try:
        with Warnings() as heard, faults.injected(plan):
            engine.generate(prompts)
    finally:
        reset_collector(token)
    assert engine.stats.executions_held == held_before + 1
    assert engine.stats.held_excess_seconds - excess_before > 0.4
    (line,) = [m for m in heard.lines if m.startswith("execution held")]
    entry = engine.stats.held[-1]
    for word in ("generate", f"B={entry['B']}", f"S={entry['S']}", "blocked",
                 "pace", "side engine/wait", "process_cpu_s", "thread_cpu_s",
                 "loadavg_1m", "involuntary_switches", "gc_s",
                 "sleeper_late_max_s"):
        assert word in line, word
    assert entry["sleeper_late_max_s"] < 0.5    # awake: the program was held
    (ev,) = [e for e in bt.events if e.name == "held"]
    assert ev.args["program"] == "generate"
    assert ev.args["excess_s"] == pytest.approx(entry["excess_s"])
    counters = engine.engine_counters()
    assert counters["executions_held"] == engine.stats.executions_held
    assert counters["held_excess_seconds"] == pytest.approx(
        engine.stats.held_excess_seconds)


# -- where the account goes: /metrics and the run record ----------------------

INFLIGHT = ("inflight_join_seconds_total", "inflight_join_rows_total",
            "inflight_segment_seconds_total", "inflight_segment_steps_total")
ENGINE = ("engine_prefill_row_chunks_total",
          "engine_prefill_row_chunks_dead_total",
          "engine_decode_kv_blocks_total",
          "engine_decode_kv_blocks_skipped_total",
          "engine_executions_held_total",
          "engine_held_excess_seconds_total")


def served_metrics(backend, prompts) -> dict:
    """Serve ``prompts`` through the in-flight loop over HTTP and scrape
    /metrics: {family: value}, labels folded."""
    from vnsum_tpu.serve.server import ServeState, make_server

    state = ServeState(backend, max_batch=2, inflight=True, slots=2,
                       slot_prompt_tokens=64, max_wait_s=0.005)
    server = make_server(state, "127.0.0.1", 0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        for prompt in prompts:
            req = urllib.request.Request(
                base + "/v1/generate",
                data=json.dumps({"prompt": prompt}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                assert json.loads(r.read())["completions"]
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
    finally:
        server.shutdown()
        thread.join()
        server.server_close()
        state.close(drain_timeout_s=10.0)
    out: dict[str, float] = {}
    for line in text.splitlines():
        if line.startswith("vnsum_serve_"):
            head, _, value = line.rpartition(" ")
            name = head.split("{", 1)[0][len("vnsum_serve_"):]
            out[name] = out.get(name, 0.0) + float(value)
    return out


def test_metrics_carries_the_ten_families_with_a_tpu_backend():
    backend = make_backend(batch_size=2)
    prompts = ["tóm tắt: một hai ba", "tóm tắt: bốn năm sáu bảy", "tám"]
    got = served_metrics(backend, prompts)
    assert set(INFLIGHT + ENGINE) <= set(got)
    assert got["inflight_join_rows_total"] == len(prompts)
    assert got["inflight_join_seconds_total"] > 0
    assert got["inflight_segment_steps_total"] > 0
    # no oversized request took the one-shot fallback: the two add up
    assert (got["inflight_join_seconds_total"]
            + got["inflight_segment_seconds_total"]) == pytest.approx(
        got["engine_seconds_total"], abs=2e-6)
    st = backend.stats
    assert got["engine_prefill_row_chunks_total"] \
        == st.prefill_row_chunks_total > 0
    assert got["engine_prefill_row_chunks_dead_total"] \
        == st.prefill_row_chunks_dead
    assert got["engine_decode_kv_blocks_total"] == st.decode_kv_blocks_total
    assert got["engine_executions_held_total"] == st.executions_held
    # the segments' steps are the engine's: the two accounts agree
    assert got["inflight_segment_steps_total"] == sum(
        ex.steps for (p, _, _), ex in st.executions.items() if p == "segment")


def test_metrics_omits_the_engine_families_with_a_fake_backend():
    from vnsum_tpu.backend import FakeBackend

    got = served_metrics(
        FakeBackend(segment_overhead_s=0.005, segment_words=2),
        ["câu hỏi số một " * 3, "câu hỏi số hai " * 3])
    assert set(INFLIGHT) <= set(got)
    assert not any(name.startswith("engine_") and name != "engine_seconds_total"
                   for name in got)
    assert got["inflight_join_rows_total"] == 2
    assert got["inflight_segment_steps_total"] > 0
    assert (got["inflight_join_seconds_total"]
            + got["inflight_segment_seconds_total"]) == pytest.approx(
        got["engine_seconds_total"], abs=2e-6)


def test_an_offline_run_record_holds_the_engines_account(tmp_path):
    from vnsum_tpu.core.config import PipelineConfig
    from vnsum_tpu.eval import EmbeddingModel
    from vnsum_tpu.models.encoder import tiny_encoder
    from vnsum_tpu.pipeline.runner import PipelineRunner

    docs, refs = tmp_path / "doc", tmp_path / "summary"
    docs.mkdir()
    refs.mkdir()
    for i in range(2):
        (docs / f"d{i}.txt").write_text("một hai ba bốn năm " * 12)
        (refs / f"d{i}.txt").write_text("tóm tắt " * 5)
    cfg = PipelineConfig(
        approach="truncated", models=["tiny"], backend="tpu",
        max_new_tokens=8, max_context=256,
        docs_dir=str(docs), summary_dir=str(refs),
        generated_summaries_dir=str(tmp_path / "gen"),
        results_dir=str(tmp_path / "results"), logs_dir=str(tmp_path / "logs"))
    backend = make_backend(model_config=tiny_llama(max_seq_len=512))
    runner = PipelineRunner(
        cfg, backend_factory=lambda model: backend,
        embedding_model=EmbeddingModel(config=tiny_encoder(), max_len=64,
                                       batch_size=4))
    results = runner.run()
    account = results.tracing["engine"]
    assert {"host_spans", "executions", "held", "prefill_row_chunks",
            "prefill_row_chunks_dead", "decode_kv_blocks",
            "decode_kv_blocks_skipped"} <= set(account)
    assert "spans" in results.tracing           # next to the pipeline's own
    assert account["host_spans"]["engine/wait"]["count"] >= 1
    (key,) = [k for k in account["executions"] if k.startswith("generate[")]
    assert account["executions"][key]["count"] \
        == account["host_spans"]["engine/wait"]["count"]
    assert account["held"] == []
    assert account["prefill_row_chunks"] == backend.stats.prefill_row_chunks_total
    (path,) = (tmp_path / "results").glob("pipeline_results_*.json")
    saved = json.loads(path.read_text())["results"]["tracing"]["engine"]
    assert saved["executions"][key]["count"] == account["executions"][key][
        "count"]                                # it is what was written
