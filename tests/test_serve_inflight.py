"""In-flight scheduler over the FakeBackend slot loop: slot feeding,
refill into a running batch, key switching, oversized fallback, deadline
shedding, drain on close, take_upto semantics, and the slot metrics
surface. Hermetic — the real-engine loop is covered by
tests/test_inflight_engine.py."""
from __future__ import annotations

import threading
import time

import pytest

from vnsum_tpu.backend.fake import FakeBackend
from vnsum_tpu.core.config import GenerationConfig
from vnsum_tpu.serve import (
    InflightScheduler,
    RequestQueue,
    RequestShed,
    ServeRequest,
    ShedReason,
)


def make_backend(**kw):
    kw.setdefault("segment_words", 8)
    kw.setdefault("segment_overhead_s", 0.005)
    kw.setdefault("per_slot_segment_s", 0.0005)
    kw.setdefault("batch_overhead_s", 0.01)
    return FakeBackend(**kw)


def make_sched(backend=None, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_wait_s", 0.01)
    return InflightScheduler(backend or make_backend(), **kw)


# -- basic serving -----------------------------------------------------------


def test_requests_complete_with_correct_per_request_outputs():
    sched = make_sched()
    try:
        prompts = [f"tai lieu {i} noi dung rieng " * 6 for i in range(8)]
        futs = [sched.submit(p) for p in prompts]
        for p, f in zip(prompts, futs):
            c = f.result(timeout=30)
            assert c.text == FakeBackend().generate([p])[0]
            assert c.record.status == "ok"
            # TTFT is anchored at the joiner's own prefill, always — the
            # slot loop needs no tracing collector for the anchor
            assert c.record.ttft_anchored
            assert 0 <= c.record.ttft_s <= c.record.total_s
        snap = sched.metrics.snapshot()
        assert snap.completed == 8
        assert snap.segments > 0
    finally:
        sched.close()


def test_inflight_concurrent_submissions():
    """Concurrent submitters stream through shared slots (also rerun under
    VNSUM_SANITIZERS=all in CI — the lock-order/transfer detectors cover
    the queue/metrics/loop interplay)."""
    sched = make_sched()
    try:
        prompts = [f"dong thoi {i} " * (4 + i) for i in range(10)]
        results = [None] * len(prompts)
        barrier = threading.Barrier(len(prompts))

        def worker(i, p):
            barrier.wait()
            results[i] = sched.submit(p).result(timeout=30)

        threads = [
            threading.Thread(target=worker, args=(i, p))
            for i, p in enumerate(prompts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for p, c in zip(prompts, results):
            assert c.text == FakeBackend().generate([p])[0]
    finally:
        sched.close()


def test_refill_joins_running_batch():
    """A long-running resident plus later short arrivals: the later ones
    must be admitted at a segment boundary WHILE the resident decodes
    (refills counter moves), not after it finishes."""
    backend = make_backend(segment_words=4)  # 40-word output = 10 segments
    sched = make_sched(backend)
    try:
        long_fut = sched.submit("dai " * 60)
        time.sleep(0.03)  # a few segments deep
        short_futs = [sched.submit(f"ngan {i} muoi tu " * 3) for i in range(3)]
        long_c = long_fut.result(timeout=30)
        short_cs = [f.result(timeout=30) for f in short_futs]
        snap = sched.metrics.snapshot()
        assert snap.refills >= 3, snap.refills
        # the joiners rode the resident's batch: occupancy above 1
        assert any(c.record.batch_size > 1 for c in short_cs)
        assert long_c.record.status == "ok"
    finally:
        sched.close()


def test_short_joiner_finishes_before_long_resident():
    """The whole point of in-flight batching: a short request admitted
    during a long decode completes without waiting the stranger out."""
    backend = make_backend(segment_words=4)
    sched = make_sched(backend)
    try:
        long_fut = sched.submit("rat dai " * 60)           # 10 segments
        time.sleep(0.02)
        t0 = time.monotonic()
        short_c = sched.submit("ngan gon").result(timeout=30)
        short_wall = time.monotonic() - t0
        long_c = long_fut.result(timeout=30)
        assert long_c.record.total_s > short_wall
        assert short_c.record.status == "ok"
    finally:
        sched.close()


# -- the idle loop's coalescing window ---------------------------------------
# Real threads and the real clock: windows are set far wider than the gaps
# the tests drive through them (ROADMAP D10: six workers share the cores), and
# what is asserted about time is an order of magnitude off either bound.


def _short_prompts(n):
    # FakeBackend answers with the prompt's first words: these finish in one
    # 8-word segment
    return [f"ngan gon {i}" for i in range(n)]


def test_idle_burst_is_admitted_in_one_join():
    """``slots`` requests a few ms apart reach an idle scheduler: one join
    carries them all (the parent admitted the first alone and the rest one
    join and one segment late)."""
    sched = make_sched(max_wait_s=0.3)
    try:
        futs = []
        for p in _short_prompts(4):
            futs.append(sched.submit(p))
            time.sleep(0.005)
        cs = [f.result(timeout=30) for f in futs]
        snap = sched.metrics.snapshot()
        hists = sched.metrics.histograms_snapshot()
    finally:
        sched.close()
    assert snap.batches == 1 and snap.batch_occupancy_sum == 4
    assert all(c.record.batch_size == 4 for c in cs)
    # every segment ran with every slot busy
    occ = hists["slot_occupancy"]
    assert occ["count"] >= 1 and occ["sum"] == 4 * occ["count"]
    # one window, three followers caught; full slots ended it long before
    # its 0.3 s were up
    assert (snap.windows, snap.window_joined) == (1, 3)
    assert 0 < snap.window_wait_seconds < 0.25
    assert snap.refills == 0


def test_lone_request_waits_about_one_window():
    """Light load keeps the batch scheduler's contract: a lone request at
    an idle server waits about ``max_wait_s`` for company, not for ever,
    and the counters say so."""
    sched = make_sched(max_wait_s=0.05)
    try:
        c = sched.submit("mot minh " * 3).result(timeout=30)
        snap = sched.metrics.snapshot()
    finally:
        sched.close()
    assert 0.045 <= c.record.queue_wait_s < 0.5
    assert (snap.windows, snap.window_joined) == (1, 0)
    assert snap.window_wait_seconds == pytest.approx(
        c.record.queue_wait_s, abs=0.02)
    assert snap.batches == 1 and snap.batch_occupancy_sum == 1


def test_window_ends_the_moment_the_free_slots_fill():
    """Two slots, two requests, a 2 s window: nobody waits 2 s."""
    sched = make_sched(slots=2, max_wait_s=2.0)
    try:
        t0 = time.monotonic()
        futs = [sched.submit(p) for p in _short_prompts(2)]
        for f in futs:
            f.result(timeout=30)
        wall = time.monotonic() - t0
        snap = sched.metrics.snapshot()
    finally:
        sched.close()
    assert wall < 1.0
    assert snap.batches == 1 and snap.batch_occupancy_sum == 2
    assert snap.window_wait_seconds < 1.0


def test_boundary_take_does_not_wait_while_rows_decode():
    """Beside test_refill_joins_running_batch: with a window of a whole
    second, a request that arrives while a resident decodes still joins at
    the next segment boundary — there the cadence coalesces, and a wait
    would stall the resident. Only the resident's own idle take held a
    window."""
    backend = make_backend(segment_words=2, segment_overhead_s=0.01)
    sched = make_sched(backend, max_wait_s=1.0)
    try:
        long_fut = sched.submit("dai " * 80)       # 20 segments of ~10 ms
        deadline = time.monotonic() + 10
        while not sched.metrics.snapshot().segments:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        short_c = sched.submit("ngan gon").result(timeout=30)
        long_c = long_fut.result(timeout=30)
        snap = sched.metrics.snapshot()
    finally:
        sched.close()
    assert short_c.record.queue_wait_s < 0.5
    assert snap.refills >= 1
    assert long_c.record.status == "ok"
    assert (snap.windows, snap.window_joined) == (1, 0)


def test_close_inside_the_window_drains_at_once():
    sched = make_sched(max_wait_s=5.0)
    fut = sched.submit("dong cua " * 3)
    time.sleep(0.05)                    # the idle take holds its window
    t0 = time.monotonic()
    sched.close(drain=True, timeout=10)
    assert fut.result(timeout=1).record.status == "ok"
    assert time.monotonic() - t0 < 2.5
    assert not sched._thread.is_alive()


def test_incompatible_arrival_inside_the_window_waits_its_turn():
    """A request of another batch key is no company: the head's window
    runs out, the head is served alone, then the loop is rebuilt for the
    other key — past ``switch_grace_s`` or not, as before."""
    sched = make_sched(max_wait_s=0.1, switch_grace_s=0.02)
    try:
        a = sched.submit("khoa mot " * 3, max_new_tokens=16)
        time.sleep(0.01)
        b = sched.submit("khoa hai " * 3, max_new_tokens=32)
        ca, cb = a.result(timeout=30), b.result(timeout=30)
        snap = sched.metrics.snapshot()
    finally:
        sched.close()
    assert ca.record.status == cb.record.status == "ok"
    assert ca.record.batch_size == cb.record.batch_size == 1
    assert snap.batches == 2 and snap.window_joined == 0


def test_cancelled_and_expiring_requests_inside_the_window():
    """A request cancelled inside the window leaves it typed, as it leaves
    the queue at any other time; one whose deadline falls inside the
    window closes it — taken before the deadline, or shed typed if the
    deadline won the race — and the head is not made to wait the window
    out either way."""
    from vnsum_tpu.serve import RequestCancelled

    sched = make_sched(max_wait_s=3.0)
    try:
        head = sched.submit("dau hang " * 3)
        victim = sched.submit("bi huy " * 3, trace_id="victim")
        time.sleep(0.02)
        assert sched.cancel("victim")["cancelled_queued"] == 1
        with pytest.raises(RequestCancelled):
            victim.result(timeout=5)
        urgent = sched.submit(
            "sap het han " * 3, deadline=time.monotonic() + 0.25)
        c = head.result(timeout=30)
        try:
            assert urgent.result(timeout=30).record.status == "ok"
        except RequestShed as e:
            assert e.reason is ShedReason.DEADLINE
    finally:
        sched.close()
    assert c.record.status == "ok"
    assert c.record.queue_wait_s < 2.0


def test_heartbeat_keeps_beating_through_the_window():
    """The idle take stamps the watchdog heartbeat at every wake-up, and a
    window's longest sleep is its cap: a loop deadline well above it never
    reads the window as a stall."""
    from vnsum_tpu.serve import Watchdog

    escalations = []
    wd = Watchdog(interval_s=0.02, loop_deadline_s=1.0,
                  on_escalate=escalations.append)
    wd.start()
    sched = make_sched(max_wait_s=0.2, watchdog=wd)
    beats = []
    beat = sched.queue.heartbeat
    sched.queue.heartbeat = lambda: (beats.append(time.monotonic()), beat())
    try:
        t0 = time.monotonic()
        futs = []
        for p in _short_prompts(3):     # three of four slots: it stays open
            futs.append(sched.submit(p))
            time.sleep(0.03)
        cs = [f.result(timeout=30) for f in futs]
        t1 = time.monotonic()
    finally:
        sched.close(timeout=5)
        wd.close()
    assert all(c.record.batch_size == 3 for c in cs)
    # entry, each arrival and the flush woke it: beats inside the window
    assert sum(t0 <= b <= t1 for b in beats) >= 3
    assert not escalations and wd.recoveries_total == 0


# -- compatibility / key switching -------------------------------------------


def test_incompatible_keys_drain_and_switch():
    sched = make_sched()
    try:
        a = sched.submit("khoa mot " * 5, max_new_tokens=16)
        b = sched.submit("khoa hai " * 5, max_new_tokens=32)
        c = sched.submit(
            "khoa ba " * 5, config=GenerationConfig(temperature=0.5)
        )
        for f in (a, b, c):
            assert f.result(timeout=30).record.status == "ok"
    finally:
        sched.close()


def test_incompatible_head_is_not_starved():
    """Compatible traffic keeps arriving while an incompatible request
    waits: after switch_grace_s the loop must drain and serve it."""
    backend = make_backend()
    sched = make_sched(backend, switch_grace_s=0.05)
    try:
        sched.submit("nen " * 30).result(timeout=30)  # warm the loop's key
        stop = threading.Event()
        done_odd = []

        def odd_key():
            done_odd.append(
                sched.submit("khac khoa " * 5, max_new_tokens=16)
                .result(timeout=30)
            )

        t = threading.Thread(target=odd_key)
        t.start()

        def feeder():
            while not stop.is_set():
                sched.submit("cung khoa " * 10).result(timeout=30)

        feeders = [threading.Thread(target=feeder) for _ in range(2)]
        for f in feeders:
            f.start()
        t.join(timeout=20)
        stop.set()
        for f in feeders:
            f.join(timeout=20)
        assert done_odd and done_odd[0].record.status == "ok"
    finally:
        sched.close()


# -- oversized fallback ------------------------------------------------------


def test_oversized_prompt_falls_back_to_batch_dispatch():
    backend = make_backend()
    sched = make_sched(backend, slot_prompt_tokens=8)
    try:
        small = sched.submit("vua khit day")           # 3 words, fits
        big_prompt = "qua kho " * 20                   # 40 words > 8
        big = sched.submit(big_prompt)
        assert small.result(timeout=30).record.status == "ok"
        c = big.result(timeout=30)
        assert c.record.status == "ok"
        assert c.text == FakeBackend().generate([big_prompt])[0]
    finally:
        sched.close()


# -- shedding / shutdown -----------------------------------------------------


def test_deadline_expiring_in_queue_is_shed():
    backend = make_backend(segment_words=2, segment_overhead_s=0.03)
    sched = make_sched(backend, slots=1)
    try:
        slow = sched.submit("giu may " * 40)  # 20 segments x 30ms
        shed = sched.submit(
            "het han " * 5, deadline=time.monotonic() + 0.05
        )
        assert slow.result(timeout=30).record.status == "ok"
        with pytest.raises(RequestShed) as exc:
            shed.result(timeout=30)
        assert exc.value.reason is ShedReason.DEADLINE
    finally:
        sched.close()


def test_close_drains_resident_and_queued():
    backend = make_backend()
    sched = make_sched(backend)
    futs = [sched.submit(f"thoat {i} " * 6) for i in range(6)]
    sched.close(drain=True)
    for f in futs:
        assert f.result(timeout=1).record.status == "ok"
    assert not sched._thread.is_alive()
    with pytest.raises(RequestShed):
        sched.submit("den muon ")


def test_backend_without_slot_loop_is_rejected():
    class NoLoop(FakeBackend):
        start_slot_loop = None

    with pytest.raises(ValueError, match="start_slot_loop"):
        InflightScheduler(NoLoop())


# -- strategy fan-out rides the slots ----------------------------------------


def test_queued_backend_fanout_rides_slot_loop():
    sched = make_sched()
    try:
        qb = sched.backend_view()
        outs = qb.generate([f"chunk {i} cua tai lieu " * 4 for i in range(6)])
        ref = FakeBackend()
        assert outs == [
            ref.generate([f"chunk {i} cua tai lieu " * 4])[0]
            for i in range(6)
        ]
        assert sched.metrics.snapshot().segments > 0
    finally:
        sched.close()


# -- take_upto unit behavior -------------------------------------------------


def test_take_upto_filters_by_key_and_bills_per_slot():
    q = RequestQueue(max_depth=8, max_queued_tokens=1000)
    a = ServeRequest(prompt="a mot hai", max_new_tokens=32, est_tokens=3)
    b = ServeRequest(prompt="b ba", max_new_tokens=64, est_tokens=2)
    c = ServeRequest(prompt="c bon nam", max_new_tokens=32, est_tokens=3)
    for r in (a, b, c):
        q.submit(r)
    assert q.queued_tokens == 8
    got = q.take_upto(4, key=(32, None))
    assert [r.prompt for r in got] == ["a mot hai", "c bon nam"]
    assert q.depth == 1 and q.queued_tokens == 2
    # head-key default
    assert [r.prompt for r in q.take_upto(1)] == ["b ba"]
    # empty + open: [] after the wait; closed + drained: None
    assert q.take_upto(1, wait_s=0.0) == []
    q.close()
    assert q.take_upto(1) is None


class _Clock:
    """A synthetic clock for the queue alone: ``monotonic`` reads it, and a
    condition wait advances it to the next scripted event (run with the
    queue's lock released, as a real wait releases it) or by the whole
    timeout. No thread, no sleep: the window's arithmetic is exact."""

    def __init__(self, q, monkeypatch):
        import types

        from vnsum_tpu.serve import queue as queue_mod

        self.now = 1000.0
        self.events: list[tuple[float, object]] = []
        self.waits = 0
        self.q = q
        lock = q._lock
        clock = self

        class Cond:
            def __enter__(self):
                lock.acquire()

            def __exit__(self, *exc):
                lock.release()

            def notify_all(self):
                pass

            def wait(self, timeout=None):
                clock.waits += 1
                assert clock.waits < 1000, "the take never returned"
                due = [e for e in clock.events if e[0] <= clock.now + timeout]
                if not due:
                    clock.now += timeout
                    return
                ev = min(due, key=lambda e: e[0])
                clock.events.remove(ev)
                clock.now = max(clock.now, ev[0])
                lock.release()
                try:
                    ev[1]()
                finally:
                    lock.acquire()

        q._cond = Cond()
        monkeypatch.setattr(
            queue_mod, "time",
            types.SimpleNamespace(monotonic=lambda: clock.now))

    def req(self, name, **kw):
        return ServeRequest(prompt=name, max_new_tokens=32,
                            enqueued_at=self.now, **kw)

    def at(self, dt, fn):
        self.events.append((self.now + dt, fn))

    def arrive(self, dt, name, **kw):
        self.at(dt, lambda: self.q.submit(self.req(name, **kw)))


# every case: one request "a" waits at entry, window_s is 0.010; arrivals
# are (seconds after entry, name); expect is what the scheduler would pass
@pytest.mark.parametrize(
    "case, slots, arrivals, expect, taken, held_s",
    [
        # nobody comes: a lone request waits one window_s, no longer
        ("alone", 4, [], 0, "a", 0.010),
        # the free slots fill: the window ends that instant
        ("full_early_exit", 4, [(0.002, "b"), (0.003, "c"), (0.004, "d"),
                                (0.006, "e")], 0, "abcd", 0.004),
        # each arrival inside the window keeps it open one window_s more,
        # and a quiet gap ends it: "d" at +0.030 is 0.012 after "c"
        ("quiet_gap_extension", 4,
         [(0.008, "b"), (0.018, "c"), (0.030, "d")], 0, "abc", 0.028),
        # an arrival every 8 ms would keep it open for ever: the cap ends
        # it with "h" (+0.056) still on its way
        ("hard_cap", 16, [(0.008 * i, n) for i, n in enumerate("bcdefgh", 1)],
         0, "abcdefg", 0.050),
        # rows that finished at the boundary just passed: the window holds
        # to the cap for that many followers, past any quiet gap
        ("expected_followers", 4,
         [(0.025, "b"), (0.045, "c"), (0.049, "d")], 3, "abcd", 0.049),
        ("expected_followers_capped", 4,
         [(0.025, "b"), (0.060, "c")], 3, "ab", 0.050),
    ],
)
def test_take_upto_window(monkeypatch, case, slots, arrivals, expect, taken,
                          held_s):
    q = RequestQueue(max_depth=16)
    clock = _Clock(q, monkeypatch)
    seen = []
    q.on_window = lambda held, joined: seen.append((held, joined))
    t0 = clock.now
    q.submit(clock.req("a"))
    for dt, name in arrivals:
        clock.arrive(dt, name)
    got = q.take_upto(slots, wait_s=0.05, window_s=0.010, expect=expect)
    assert "".join(r.prompt for r in got) == taken
    assert clock.now - t0 == pytest.approx(held_s, abs=1e-9)
    # the hook: one window, the seconds it was held, the followers it caught
    assert len(seen) == 1
    assert seen[0][0] == pytest.approx(clock.now - t0, abs=1e-9)
    assert seen[0][1] == len(taken) - 1


def test_take_upto_window_stays_open_for_an_announced_request(monkeypatch):
    """A request that is still being tokenized on its handler thread
    (``arriving``) is company on its way: no quiet gap closes the window
    on it, and the cap still does."""
    q = RequestQueue(max_depth=16)
    clock = _Clock(q, monkeypatch)
    q.submit(clock.req("a"))
    slow = q.arriving()
    clock.at(0.003, slow.__enter__)              # POST received at +3 ms
    clock.at(0.034, lambda: q.submit(clock.req("b")))
    clock.at(0.034, lambda: slow.__exit__(None, None, None))
    t0 = clock.now
    got = q.take_upto(4, wait_s=0.05, window_s=0.010)
    # "b" took 31 ms to tokenize, three quiet gaps; then one more gap
    assert [r.prompt for r in got] == ["a", "b"]
    assert clock.now - t0 == pytest.approx(0.044, abs=1e-9)
    # one that never finishes is cut by the cap
    q.submit(clock.req("c"))
    stuck = q.arriving()
    stuck.__enter__()
    t0 = clock.now
    assert [r.prompt for r in q.take_upto(4, window_s=0.010)] == ["c"]
    assert clock.now - t0 == pytest.approx(0.050, abs=1e-9)
    stuck.__exit__(None, None, None)


def test_take_upto_window_anchors_on_entry_for_an_old_head(monkeypatch):
    """take_batch's anchor: a backlog older than any window still leaves
    one open from this call's entry, for the requests that the answers
    just sent unblock; a head that arrives later anchors it on arrival."""
    q = RequestQueue(max_depth=16)
    clock = _Clock(q, monkeypatch)
    q.submit(clock.req("old"))
    clock.now += 5.0                              # a long join and segment
    clock.arrive(0.006, "follower")
    t0 = clock.now
    got = q.take_upto(4, wait_s=0.05, window_s=0.010)
    assert [r.prompt for r in got] == ["old", "follower"]
    assert clock.now - t0 == pytest.approx(0.016, abs=1e-9)
    # an empty queue: the idle wait is not the window — the head arrives
    # 30 ms in, and only then do its 10 ms start
    clock.arrive(0.030, "late")
    t0 = clock.now
    assert [r.prompt for r in q.take_upto(4, wait_s=0.05, window_s=0.010)] \
        == ["late"]
    assert clock.now - t0 == pytest.approx(0.040, abs=1e-9)


def test_take_upto_window_deadline_close_cancel_expiry(monkeypatch):
    """What the queue did before the window it does inside it."""
    q = RequestQueue(max_depth=16)
    clock = _Clock(q, monkeypatch)
    sheds = []
    q.on_shed = lambda r, reason: sheds.append((r.prompt, reason))
    # a deadline inside the window: the request is taken now, not made to
    # wait past it (and not shed: it has not expired)
    q.submit(clock.req("urgent", deadline=clock.now + 0.004))
    t0 = clock.now
    got = q.take_upto(4, wait_s=0.05, window_s=0.010)
    assert [r.prompt for r in got] == ["urgent"] and clock.now == t0
    assert not sheds
    # a deadline beyond the window does not shorten it
    q.submit(clock.req("patient", deadline=clock.now + 5.0))
    t0 = clock.now
    assert len(q.take_upto(4, window_s=0.010)) == 1
    assert clock.now - t0 == pytest.approx(0.010, abs=1e-9)
    # a follower that arrives already doomed closes the window for the head
    q.submit(clock.req("head"))
    clock.arrive(0.002, "doomed", deadline=clock.now + 0.005)
    t0 = clock.now
    got = q.take_upto(4, window_s=0.010)
    assert [r.prompt for r in got] == ["head", "doomed"]
    assert clock.now - t0 == pytest.approx(0.002, abs=1e-9)
    # cancelled inside the window: gone from the take, which then waits
    # out the idle wait like any empty take
    victim = clock.req("victim")
    q.submit(victim)
    clock.at(0.003, lambda: q.cancel_where(lambda r: r is victim))
    assert q.take_upto(4, wait_s=0.02, window_s=0.010) == []
    # closed inside the window: drain at once; closed and drained: None
    q.submit(clock.req("last"))
    clock.at(0.002, q.close)
    t0 = clock.now
    assert [r.prompt for r in q.take_upto(4, window_s=0.010)] == ["last"]
    assert clock.now - t0 == pytest.approx(0.002, abs=1e-9)
    assert q.take_upto(4, wait_s=0.05, window_s=0.010) is None


def test_take_upto_without_window_returns_at_once(monkeypatch):
    """The decoding loop's take (no ``window_s``) is today's: whatever is
    compatible, now, and no window is counted."""
    q = RequestQueue(max_depth=16)
    clock = _Clock(q, monkeypatch)
    seen = []
    q.on_window = lambda held, joined: seen.append((held, joined))
    q.submit(clock.req("a"))
    clock.arrive(0.001, "b")
    t0 = clock.now
    assert [r.prompt for r in q.take_upto(3, key=(32, None))] == ["a"]
    assert clock.now == t0 and clock.waits == 0 and not seen


def test_window_and_announcements_under_concurrent_submitters():
    """More submitter threads than cores, a short switch interval: every
    announcement is withdrawn, every request is taken exactly once, and no
    take holds a request past the cap by more than scheduling noise."""
    import sys

    q = RequestQueue(max_depth=100000)
    n_threads, per_thread = 32, 100
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def submitter():
            for _ in range(per_thread):
                with q.arriving():
                    q.submit(ServeRequest(prompt="x", max_new_tokens=32))

        threads = [threading.Thread(target=submitter)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        taken = []
        deadline = time.monotonic() + 60
        while len(taken) < n_threads * per_thread:
            assert time.monotonic() < deadline
            taken += q.take_upto(4, wait_s=0.01, window_s=0.002, expect=2)
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert len({r.request_id for r in taken}) == n_threads * per_thread
    assert q._arriving == 0 and q.depth == 0 and q.queued_tokens == 0


def test_take_upto_head_snapshot():
    q = RequestQueue(max_depth=4)
    assert q.head_snapshot() is None
    r = ServeRequest(prompt="x", max_new_tokens=16)
    q.submit(r)
    key, enq = q.head_snapshot()
    assert key == (16, None) and enq == r.enqueued_at


# -- metrics surface ---------------------------------------------------------


def test_slot_metrics_render():
    sched = make_sched()
    try:
        sched.submit("do luong " * 6).result(timeout=30)
        text = sched.metrics.render_prometheus(
            queue_depth=0, queued_tokens=0, slot_state=sched.slot_state()
        )
    finally:
        sched.close()
    assert "vnsum_serve_inflight_segments_total" in text
    assert "vnsum_serve_inflight_refills_total" in text
    # the lone request's idle take held one window and caught nobody
    assert "vnsum_serve_inflight_windows_total 1" in text
    assert "vnsum_serve_inflight_window_joined_total 0" in text
    assert "vnsum_serve_inflight_window_wait_seconds_total 0.0" in text
    assert "vnsum_serve_slots_total 4" in text
    assert "vnsum_serve_slots_busy" in text
    assert "vnsum_serve_slot_occupancy_bucket" in text
    assert "vnsum_serve_ttft_seconds_bucket" in text
