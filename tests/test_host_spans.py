"""core.profiling.host_span: one interval of host work given to three
readers — the always-on aggregate, the obs collector and the profiler — and
where the program opens it: a fixed count a generate call and a dispatch
(never one a row or a token), the two sides of the device queue, the
collector's old names, the slot loop's host-gap counters."""
from __future__ import annotations

import logging
import sys
import time
from pathlib import Path

import pytest

from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.core.profiling import SpanStats, Tracer, host_span
from vnsum_tpu.models import tiny_llama
from vnsum_tpu.obs.trace import BatchTrace, reset_collector, set_collector

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


class Collected:
    """An obs collector installed for a block; ``.events`` by name."""

    def __enter__(self):
        self.bt = BatchTrace(batch_id=0, occupancy=1)
        self._token = set_collector(self.bt)
        return self

    def __exit__(self, *exc):
        reset_collector(self._token)

    def named(self, name):
        return [e for e in self.bt.events if e.name == name]


def make_backend(**kw):
    kw.setdefault("model_config", tiny_llama(max_seq_len=128))
    kw.setdefault("batch_size", 4)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("segment_tokens", 4)
    kw.setdefault("flash", False)   # off-chip: the dense path, by name
    return TpuBackend(**kw)


@pytest.fixture(scope="module")
def engine():
    return make_backend()


# -- the primitive ------------------------------------------------------------


def test_one_interval_reaches_the_aggregate_and_the_collector():
    sink: dict = {}
    with Collected() as c:
        with host_span("engine", "wait", sink, B=8, S=64) as sp:
            time.sleep(0.002)
            sp.note(rows=3)
    assert list(sink) == ["engine/wait"]
    st = sink["engine/wait"]
    assert isinstance(st, SpanStats)
    assert (st.count, st.total_s, st.max_s) == (1, sp.dur, sp.dur)
    (ev,) = c.named("wait")            # the bare name, not "engine/wait"
    assert (ev.t0, ev.dur) == (sp.t0, sp.dur) and sp.dur >= 0.002
    assert ev.args == {"B": 8, "S": 64, "rows": 3}


def test_the_collector_may_know_a_span_by_another_name():
    with Collected() as c:
        with host_span("slot", "segment", event="decode_seg", live=2):
            pass
    assert [e.name for e in c.bt.events] == ["decode_seg"]


def test_without_sink_or_collector_a_span_still_times_itself():
    with host_span("strategy", "split") as sp:
        pass
    assert sp.dur >= 0.0 and sp.t0 > 0.0


def test_a_span_survives_an_exception_and_does_not_swallow_it():
    sink: dict = {}
    with Collected() as c, pytest.raises(ValueError):
        with host_span("engine", "enqueue", sink):
            raise ValueError("boom")
    assert sink["engine/enqueue"].count == 1
    assert len(c.named("enqueue")) == 1


def test_spans_nest_and_the_parent_covers_its_children():
    sink: dict = {}
    with host_span("engine", "dispatch", sink) as parent:
        with host_span("engine", "enqueue", sink) as a:
            time.sleep(0.001)
        with host_span("engine", "wait", sink) as b:
            time.sleep(0.001)
    assert set(sink) == {"engine/dispatch", "engine/enqueue", "engine/wait"}
    assert a.dur + b.dur <= parent.dur
    assert parent.t0 <= a.t0 and b.t0 + b.dur <= parent.t0 + parent.dur


def test_a_profiler_capture_holds_the_span_on_a_host_plane(tmp_path):
    """The profiler's clock, the device plane's file: what
    benchmarks/trace_reduce.py names idle gaps by."""
    import jax
    import jax.numpy as jnp

    from benchmarks import trace_reduce

    jax.profiler.start_trace(str(tmp_path))
    try:
        with host_span("engine", "wait", B=1, S=64):
            (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    planes = trace_reduce.read_planes(trace_reduce.find_xplane(str(tmp_path)))
    found = [(plane, s, e) for plane, lines in planes.items()
             for events in lines.values() for n, s, e in events
             if n == "engine/wait"]
    assert len(found) == 1
    plane, start, end = found[0]
    assert not plane.startswith("/device:TPU")
    assert end - start >= 2_000_000        # nanoseconds


def test_tracer_span_is_a_host_span_under_a_hierarchical_key():
    t = Tracer()
    with Collected() as c:
        with t.span("batch"):
            with t.span("split", layer="strategy", docs=4) as sp:
                pass
    stats = t.stats()
    assert set(stats) == {"batch", "batch/split"}
    assert stats["batch/split"]["total_s"] == sp.dur
    assert sp.full == "strategy/split"     # what the profiler sees
    (timeline_span,) = [s for s in t.timeline() if s.name == "batch/split"]
    assert (timeline_span.t0, timeline_span.dur) == (sp.t0, sp.dur)
    assert [e.name for e in c.bt.events] == ["split", "batch"]


# -- the one-shot engine ------------------------------------------------------

CALL_SPANS = {"engine/tokenize"}
DISPATCH_SPANS = {"engine/dispatch", "engine/pack", "engine/enqueue",
                  "engine/wait", "engine/count", "engine/detokenize"}


def span_counts(backend) -> dict:
    return {k: v.count for k, v in backend.stats.host_spans.items()}


@pytest.mark.parametrize("n_prompts, dispatches", [(1, 1), (4, 1), (5, 2),
                                                   (9, 3)])
def test_generate_opens_a_fixed_count_of_spans(n_prompts, dispatches):
    """a + b x dispatches, as numbers: 1 a call and 6 a dispatch without the
    prefix cache — the guard against a span a row or a token (each row
    decodes 8 tokens here)."""
    backend = make_backend()
    backend.generate([f"văn bản số {i} " * (1 + i % 3)
                      for i in range(n_prompts)])
    counts = span_counts(backend)
    assert set(counts) == CALL_SPANS | DISPATCH_SPANS
    assert all(counts[k] == 1 for k in CALL_SPANS)
    assert all(counts[k] == dispatches for k in DISPATCH_SPANS)
    assert sum(counts.values()) == 1 + 6 * dispatches
    assert backend.stats.batches == dispatches


def test_the_prefix_cache_adds_one_span_a_call_and_two_a_dispatch():
    backend = make_backend(cache_blocks=16, cache_block_tokens=16,
                           model_config=tiny_llama(max_seq_len=512))
    shared = "phần đầu chung của mọi lời nhắc, đủ dài để lấp vài khối. " * 4
    prompts = [shared + f"đuôi {i}" for i in range(4)]
    backend.generate(prompts, cache_hints=[shared] * 4)
    counts = span_counts(backend)
    assert counts["engine/cache_lookup"] == 1
    assert counts["engine/cache_insert"] == 1
    assert "engine/cache_gather" not in counts      # nothing cached yet
    backend.generate(prompts, cache_hints=[shared] * 4)
    counts = span_counts(backend)
    assert counts["engine/cache_gather"] == 1
    assert counts["engine/dispatch"] == 2
    assert sum(counts.values()) == 2 * (2 + 7) + 1


class Lines(logging.Handler):
    def __init__(self):
        super().__init__(logging.INFO)
        self.lines: list[str] = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def test_enqueue_and_wait_lie_inside_their_dispatch(engine):
    before = {k: (v.count, v.total_s)
              for k, v in engine.stats.host_spans.items()}
    # the engine's logger does not propagate: listen on it directly
    log, heard = logging.getLogger("vnsum.engine"), Lines()
    log.addHandler(heard)
    try:
        engine.generate(["một", "hai hai", "ba ba ba"])
    finally:
        log.removeHandler(heard)
    spans = engine.stats.host_spans

    def added(name):
        n0, s0 = before.get(name, (0, 0.0))
        return spans[name].count - n0, spans[name].total_s - s0

    assert added("engine/dispatch")[0] == 1
    assert (added("engine/enqueue")[1] + added("engine/wait")[1]
            <= added("engine/dispatch")[1])
    # one INFO line a dispatch, with both sides of the queue
    lines = [m for m in heard.lines if m.startswith("dispatch B=")]
    assert len(lines) == 1
    assert "enqueue" in lines[0] and "wait" in lines[0] \
        and "detokenize" in lines[0] and "rows=3" in lines[0]
    # and per (program, B, S) the count, total and max of each side
    (key,) = [k for k in engine.stats.executions
              if engine.stats.by_bucket.get(k[1:])]
    ex = engine.stats.executions[key]
    assert key[0] == "generate"
    assert ex.enqueue.count == ex.wait.count == ex.count \
        == engine.stats.by_bucket[key[1:]]
    assert ex.wait.max_s <= ex.wait.total_s
    assert ex.blocked.total_s == pytest.approx(
        ex.enqueue.total_s + ex.wait.total_s)


def test_no_span_name_carries_a_shape_or_a_number(engine):
    engine.generate(["x", "y y y y y y y y y y y y"])
    engine.score_choices(["chọn một:"], ["1", "2"])
    names = set(engine.stats.host_spans)
    assert "engine/choice" in names
    for name in names:
        assert not any(ch.isdigit() or ch in "[]=," for ch in name), name


def test_what_the_primitive_replaced_is_gone(engine):
    import vnsum_tpu.core as core
    import vnsum_tpu.core.profiling as profiling

    assert not hasattr(profiling, "annotate") and not hasattr(core, "annotate")
    assert not hasattr(engine.stats, "phase_seconds")
    assert not hasattr(engine.stats, "add_phase")
    # PR 52: one account of executions in place of the two sides a bucket
    assert not hasattr(engine.stats, "dispatch_by_bucket")
    assert not hasattr(engine.stats, "note_dispatch")


def test_the_collector_still_sees_the_one_shot_names(engine):
    with Collected() as c:
        engine.generate(["một văn bản", "hai"])
    names = [e.name for e in c.bt.events]
    assert names.count("tokenize") == names.count("dispatch") == 1
    assert names.count("detokenize") == 1
    (disp,) = c.named("dispatch")
    assert {"B", "S", "occupancy", "max_new"} <= set(disp.args)
    (enq,), (wait,) = c.named("enqueue"), c.named("wait")
    assert disp.t0 <= enq.t0 and wait.t0 + wait.dur <= disp.t0 + disp.dur
    # a one-shot dispatch has no observable first token: no TTFT anchor
    assert c.bt.first_token_at is None


# -- where the programs are called from (PR 38, step 0) ------------------------


def _callers_of_the_programs(backend, run):
    """The qualified name of the function that CALLS each compiled program
    while ``run()`` runs: ``_timed_first_call`` wraps every program the
    engine builds, so what it returns is what the engine calls."""
    callers: list[tuple[str, str]] = []

    def recording(fn, label):
        def call(*args):
            frame = sys._getframe(1)
            callers.append((label.split("[")[0], frame.f_code.co_qualname))
            return fn(*args)
        return call

    backend._timed_first_call = recording
    run()
    return callers


def test_the_programs_are_called_from_the_frames_step_0_settled_on():
    """PR 37 moved the one-shot program's call out of ``generate()`` into a
    method of its own and under two nested spans, and DeepSeek-V2's warm
    ``setup_s`` went 109 -> 123 s: the three first calls of its one-shot
    programs (trace, lowering, the compile cache's load) took 18.4 / 7.1 /
    10.7 s for the parent's 12.5 / 4.3 / 5.8 — the 14 s that refused it.
    PERF.md section 6 (PR 38) has what was measured. The call sits where the
    parent had it: in ``generate`` itself for the one-shot program, in
    ``_run_group_spec`` for the spec programs, in ``score_choices`` for the
    choice scorer, the spans opened around it IN PLACE. A PR that moves one
    of these calls reads the ``first call of`` lines of a warm DeepSeek-V2
    run before and after (ROADMAP Queue 1 item 0b)."""
    from vnsum_tpu.core.config import GenerationConfig

    backend = make_backend()
    prompts = ["tóm tắt: một hai ba bốn năm", "tóm tắt: sáu bảy"]
    callers = _callers_of_the_programs(backend, lambda: (
        backend.generate(prompts),
        backend.generate(prompts, config=GenerationConfig(spec_k=2),
                         references=["một hai ba bốn", "sáu bảy tám"]),
        backend.score_choices(["chọn:"], ["1", "2"]),
    ))
    by_program: dict[str, set[str]] = {}
    for program, caller in callers:
        by_program.setdefault(program, set()).add(caller)
    assert by_program.pop("generate") == {"TpuBackend.generate"}
    assert by_program.pop("choice") == {"TpuBackend.score_choices"}
    # the spec path's prefill and verify-step programs, both from one frame
    assert by_program and all(
        who == {"TpuBackend._run_group_spec"} for who in by_program.values())


# -- the slot loop ------------------------------------------------------------


def test_the_slot_loop_opens_spans_a_boundary_and_keeps_the_old_names():
    backend = make_backend(batch_size=4, max_new_tokens=8)
    loop = backend.start_slot_loop(4, max_new_tokens=8, prompt_tokens=64)
    with Collected() as c:
        admissions, rejected = loop.admit(
            [(i, p, None) for i, p in enumerate(["một", "hai hai", "ba"])])
        assert len(admissions) == 3 and not rejected
        steps = 0
        while loop.active:
            loop.step()
            steps += 1
    counts = span_counts(backend)
    assert counts["slot/pack"] == counts["slot/prefill"] == 1
    assert counts["slot/adopt"] == 1
    assert counts["slot/segment"] == counts["slot/harvest"] == steps
    (pre,) = c.named("prefill")
    assert pre.args["synced"] is True and pre.args["occupancy"] == 3
    assert c.bt.first_token_at == pytest.approx(pre.t0 + pre.dur)
    assert admissions[0].prefill_end == pytest.approx(pre.t0 + pre.dur)
    segs = c.named("decode_seg")
    assert len(segs) == steps == loop.segments
    loop.close()


def test_host_gap_counters_move_by_no_more_than_the_wall():
    from vnsum_tpu.backend import FakeBackend
    from vnsum_tpu.serve.inflight import InflightScheduler

    backend = FakeBackend(segment_overhead_s=0.02, segment_words=2)
    t0 = time.monotonic()
    sched = InflightScheduler(backend, slots=2, max_wait_s=0.01)
    try:
        futs = [sched.submit(f"câu hỏi số {i} " * 3) for i in range(5)]
        for f in futs:
            f.result(timeout=30)
    finally:
        sched.close()
    wall = time.monotonic() - t0
    stats = sched.metrics.snapshot()
    assert stats.host_gaps >= 2              # joins and segments alternated
    assert 0.0 < stats.host_gap_seconds <= wall
    # the window's wait is part of the gap, never more than it
    assert stats.window_wait_seconds <= stats.host_gap_seconds + 1e-6
    text = sched.metrics.render_prometheus()
    assert "vnsum_serve_inflight_host_gap_seconds_total" in text
    assert "vnsum_serve_inflight_host_gaps_total" in text
    spans = sched.host_spans
    assert {"serve/take", "serve/admit", "serve/complete"} <= set(spans)
    # one serve/complete a segment, one serve/admit a join: never one a row
    assert spans["serve/complete"].count == stats.segments
    assert spans["serve/admit"].count <= spans["serve/take"].count
