"""Multi-tenant QoS (serve/qos.py): WFQ math in isolation, typed quota
sheds with Retry-After, the single-tenant FIFO fall-through, priority-tier
preemption with journal lifecycle + prefix-pin hygiene, and the
GET /v1/requests/<id> state aggregation."""
from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from vnsum_tpu.backend.fake import FakeBackend
from vnsum_tpu.serve import (
    InflightScheduler,
    RequestQueue,
    RequestShed,
    ServeRequest,
    ShedReason,
    TenantSpec,
    TenantTable,
    TokenBucket,
    parse_tenant_specs,
)
from vnsum_tpu.serve.qos import _NAME_RE
from vnsum_tpu.serve.server import ServeState, make_server


def make_table(spec="interactive:4:0,batch:1:0:batch", **kw):
    return TenantTable(parse_tenant_specs(spec), **kw)


def req(prompt, tenant="", tier="interactive", tokens=10, **kw):
    return ServeRequest(prompt=prompt, tenant=tenant, tier=tier,
                        est_tokens=tokens, **kw)


# -- spec parsing ------------------------------------------------------------


def test_parse_tenant_specs_full_form():
    specs = parse_tenant_specs("fast:8:1000,slow:1:50:batch")
    assert specs["fast"].weight == 8 and specs["fast"].tier == "interactive"
    assert specs["slow"].token_rate == 50 and specs["slow"].tier == "batch"


def test_zero_weight_is_rejected():
    with pytest.raises(ValueError, match="weight"):
        parse_tenant_specs("muted:0:100")
    with pytest.raises(ValueError, match="weight"):
        TenantSpec("neg", weight=-1)


def test_parse_rejects_duplicates_bad_tier_and_empty():
    with pytest.raises(ValueError, match="duplicate"):
        parse_tenant_specs("a:1:0,a:2:0")
    with pytest.raises(ValueError, match="tier"):
        parse_tenant_specs("a:1:0:turbo")
    with pytest.raises(ValueError):
        parse_tenant_specs("   ")


# -- token bucket ------------------------------------------------------------


def test_token_bucket_burst_then_rate():
    b = TokenBucket(rate=100.0, burst=50.0)
    t0 = 1000.0
    assert b.take(50, t0) is None           # full burst spends at once
    retry = b.take(10, t0)                  # bucket dry: typed refusal
    assert retry == pytest.approx(0.1)      # 10 tokens / 100 per s
    assert b.take(10, t0 + 0.1) is None     # refilled exactly that much
    # refill never exceeds burst
    assert b.take(50, t0 + 1000.0) is None
    assert b.take(1, t0 + 1000.0) == pytest.approx(0.01)


def test_token_bucket_oversized_request_is_billed_the_burst():
    # a request larger than the whole burst must not be refused forever
    b = TokenBucket(rate=10.0, burst=20.0)
    assert b.take(10_000, 0.0) is None      # drains the bucket, admitted
    assert b.take(1, 0.0) == pytest.approx(0.1)


def test_unlimited_tenant_never_sheds():
    b = TokenBucket(rate=0.0, burst=1.0)
    for _ in range(100):
        assert b.take(10_000) is None


# -- deficit round robin -----------------------------------------------------


def test_drr_proportionality_over_long_run():
    """Weights 3:1 with both tenants permanently backlogged -> the token
    share of what select() hands out converges to 3:1."""
    table = TenantTable(parse_tenant_specs("heavy:3:0,light:1:0"),
                        quantum_tokens=64)
    took = {"heavy": 0, "light": 0}
    for _round in range(200):
        backlog = (
            [req(f"h{_round}-{i}", tenant="heavy", tokens=50)
             for i in range(8)]
            + [req(f"l{_round}-{i}", tenant="light", tokens=50)
               for i in range(8)]
        )
        for r in table.select(backlog, 4):
            took[r.tenant] += r.est_tokens
    ratio = took["heavy"] / took["light"]
    assert 2.5 <= ratio <= 3.5, (took, ratio)


def test_drr_preserves_fifo_within_tenant_and_never_returns_empty():
    table = make_table("a:1:0,b:1:0")
    backlog = [req(f"a{i}", tenant="a") for i in range(4)] + [
        req(f"b{i}", tenant="b") for i in range(4)
    ]
    picked = table.select(list(backlog), 8)
    assert len(picked) == 8
    for tenant in ("a", "b"):
        order = [r.prompt for r in picked if r.tenant == tenant]
        assert order == sorted(order)  # a0..a3 / b0..b3 in FIFO order
    assert table.select([req("x", tenant="a")], 4)  # non-empty in -> out


def test_select_serves_undeclared_tenants_instead_of_spinning():
    """A candidate whose tenant the table never declared (journal replay
    after a --tenants change) must be scheduled as a weight-1 tenant, not
    spin the pick forever with the queue lock held."""
    table = make_table("known:2:0")
    backlog = [req(f"g{i}", tenant="ghost") for i in range(3)] + [
        req(f"k{i}", tenant="known") for i in range(3)
    ]
    picked = table.select(backlog, 6)
    assert sorted(r.prompt for r in picked) == sorted(
        r.prompt for r in backlog
    )
    # and a backlog that is ONLY ghosts still drains
    only_ghosts = [req(f"o{i}", tenant="phantom") for i in range(2)]
    assert len(table.select(only_ghosts, 2)) == 2
    # a label-unsafe request-carried name is sanitized, never raised on —
    # the take path must serve (the HTTP layer 400s these before the queue,
    # but library callers reach select() directly)
    unsafe = [req("u0", tenant='team "a"\n'), req("u1", tenant="known")]
    assert len(table.select(unsafe, 2)) == 2
    assert all(_NAME_RE.fullmatch(name) for name in table.stats())


def test_interactive_tier_always_picked_before_batch():
    table = make_table()
    backlog = [req(f"b{i}", tenant="batch", tier="batch") for i in range(6)]
    backlog += [req(f"i{i}", tenant="interactive") for i in range(2)]
    picked = table.select(backlog, 4)
    assert [r.tenant for r in picked[:2]] == ["interactive", "interactive"]


# -- queue integration -------------------------------------------------------


def test_single_tenant_fall_through_identical_to_fifo():
    """With one tenant (or no table) the queue's take order — including the
    cache-hint clustering — must be byte-identical to the pre-QoS FIFO."""
    def fill(q):
        for i in range(6):
            hint = "chung" if i % 2 else "khac"
            q.submit(ServeRequest(prompt=f"p{i}", cache_hint=hint,
                                  tenant="solo"))
        return [r.prompt for r in q.take_upto(4)]

    plain = RequestQueue(max_depth=16)
    tabled = RequestQueue(max_depth=16,
                          tenants=make_table("solo:2:0"))
    assert fill(plain) == fill(tabled)


def test_wfq_pick_in_take_batch_and_take_upto():
    """Both take paths route through the DRR pick: with two tenants
    backlogged, a take returns interactive-tier work first regardless of
    arrival order."""
    q = RequestQueue(max_depth=32, tenants=make_table())
    for i in range(4):
        q.submit(req(f"batch{i}", tenant="batch", tier="batch"))
    for i in range(2):
        q.submit(req(f"inter{i}", tenant="interactive"))
    got = q.take_batch(3, max_wait_s=0.0)
    assert [r.prompt for r in got[:2]] == ["inter0", "inter1"]
    got2 = q.take_upto(4)
    assert all(r.tenant == "batch" for r in got2)
    # FIFO preserved within the batch tenant
    assert [r.prompt for r in got2] == sorted(r.prompt for r in got2)


def test_quota_shed_is_typed_with_refill_retry_after():
    table = TenantTable(parse_tenant_specs("metered:1:100"))
    q = RequestQueue(max_depth=32, tenants=table)
    q.submit(req("dau tien", tenant="metered", tokens=200))  # burst spends
    with pytest.raises(RequestShed) as exc:
        q.submit(req("qua han muc", tenant="metered", tokens=100))
    assert exc.value.reason is ShedReason.QUOTA
    assert exc.value.retry_after_s == pytest.approx(1.0, rel=0.2)


def test_backlog_sheds_carry_depth_derived_retry_after():
    q = RequestQueue(max_depth=2)
    q.submit(req("a"))
    q.submit(req("b"))
    with pytest.raises(RequestShed) as exc:
        q.submit(req("c"))
    assert exc.value.reason is ShedReason.QUEUE_FULL
    assert exc.value.retry_after_s >= 1.0
    qt = RequestQueue(max_depth=8, max_queued_tokens=15)
    qt.submit(req("a", tokens=10))
    with pytest.raises(RequestShed) as exc:
        qt.submit(req("b", tokens=10))
    assert exc.value.reason is ShedReason.TOKEN_BUDGET
    assert exc.value.retry_after_s >= 1.0


def test_deadline_shed_carries_retry_after():
    q = RequestQueue(max_depth=8)
    with pytest.raises(RequestShed) as exc:
        q.submit(req("het han", deadline=time.monotonic() - 1))
    assert exc.value.reason is ShedReason.DEADLINE
    assert exc.value.retry_after_s == 1.0


# -- preemption --------------------------------------------------------------


def make_inflight(**kw):
    backend = FakeBackend(
        segment_words=4, segment_overhead_s=0.005, batch_overhead_s=0.01,
        **kw.pop("backend_kw", {}),
    )
    kw.setdefault("slots", 2)
    kw.setdefault("max_wait_s", 0.01)
    kw.setdefault("tenants", make_table())
    return backend, InflightScheduler(backend, **kw)


def test_preemption_interactive_reclaims_slots():
    """Two batch-tier jobs saturate both slots; an interactive arrival must
    preempt one within a segment and complete FIRST, while the preempted
    job still completes byte-identically to an unpreempted run (also rerun
    under VNSUM_SANITIZERS=all in CI — the tenant-table lock joins the
    lock-order graph here)."""
    backend, sched = make_inflight()
    try:
        long_prompt = "phan tich chuyen sau noi dung " * 12
        b_futs = [
            sched.submit(long_prompt + f" so {i}", tenant="batch",
                         tier="batch")
            for i in range(2)
        ]
        time.sleep(0.03)  # both resident, a few segments deep
        t0 = time.monotonic()
        i_c = sched.submit("ngan gon", tenant="interactive").result(timeout=30)
        interactive_wall = time.monotonic() - t0
        b_cs = [f.result(timeout=30) for f in b_futs]
        snap = sched.metrics.snapshot()
        assert snap.preemptions >= 1 and snap.requeues >= 1
        assert i_c.record.status == "ok"
        # lossless round trip: the preempted batch runs restart and finish
        # byte-identical to an uninterrupted run
        for i, c in enumerate(b_cs):
            ref = FakeBackend().generate([long_prompt + f" so {i}"])[0]
            assert c.text == ref
        # the interactive request did not wait out a batch job's decode
        assert interactive_wall < max(c.record.total_s for c in b_cs)
    finally:
        sched.close()


def test_preemption_pins_prefix_blocks_and_releases_them():
    """Eviction pins the victim's cached prefix (it survives LRU while
    requeued) and every pin is released by terminal resolution."""
    backend, sched = make_inflight(
        backend_kw=dict(prefix_cache_blocks=64, cache_block_tokens=4),
    )
    try:
        long_prompt = "tai lieu can tom tat rat dai " * 10
        b_fut = sched.submit(long_prompt, tenant="batch", tier="batch")
        sched.submit(long_prompt + " hai", tenant="batch", tier="batch")
        time.sleep(0.03)
        deadline = time.monotonic() + 30
        sched.submit("uu tien", tenant="interactive").result(timeout=30)
        while sched.metrics.snapshot().preemptions < 1:
            assert time.monotonic() < deadline, "no preemption happened"
            time.sleep(0.005)
        b_fut.result(timeout=30)
    finally:
        sched.close()
    # all pins (admission + preemption) returned: nothing left uneviciable
    assert backend.prefix_index.pinned_blocks == 0
    assert sched.metrics.snapshot().preemptions >= 1


def test_sampled_batch_requests_are_never_preempted():
    """A SAMPLED row's stream keys on its slot-admission uid, so a restart
    would draw different text — sampled batch requests keep their slots
    and only greedy ones are evicted."""
    from vnsum_tpu.core.config import GenerationConfig

    backend, sched = make_inflight(slots=1)
    try:
        cfg = GenerationConfig(temperature=0.7, seed=3)
        b_fut = sched.submit("nen lay mau ngau nhien " * 10, tenant="batch",
                             tier="batch", config=cfg)
        time.sleep(0.03)
        # same batch key required to target the resident loop: the
        # interactive prompt rides the same config
        i_fut = sched.submit("khan", tenant="interactive", config=cfg)
        assert b_fut.result(timeout=30).record.status == "ok"
        assert i_fut.result(timeout=30).record.status == "ok"
        assert sched.metrics.snapshot().preemptions == 0
    finally:
        sched.close()


def test_preempt_budget_bounds_starvation():
    """A batch request evicted preempt_budget times becomes non-evictable
    and completes even under constant interactive pressure."""
    backend, sched = make_inflight(slots=1, preempt_budget=2)
    try:
        b_fut = sched.submit("cong viec nen dai " * 10, tenant="batch",
                             tier="batch")
        stop = threading.Event()

        def pressure():
            while not stop.is_set():
                try:
                    sched.submit("gap", tenant="interactive").result(timeout=30)
                except RequestShed:
                    return
        t = threading.Thread(target=pressure, daemon=True)
        t.start()
        c = b_fut.result(timeout=30)
        stop.set()
        t.join(timeout=10)
        assert c.text == FakeBackend().generate(["cong viec nen dai " * 10])[0]
        assert sched.metrics.snapshot().preemptions <= 2
    finally:
        sched.close()


def test_preemption_journal_lifecycle(tmp_path):
    """PREEMPTED + REQUEUED ride the journal, the entry ends in exactly one
    terminal state, and the raw segments carry the typed events."""
    from vnsum_tpu.serve.journal import RequestJournal

    journal = RequestJournal(tmp_path)
    backend, sched = make_inflight(journal=journal)
    try:
        b_fut = sched.submit("nen dai phai cho " * 10, tenant="batch",
                             tier="batch", trace_id="job-batch")
        sched.submit("nen hai cho lau " * 10, tenant="batch", tier="batch")
        time.sleep(0.03)
        sched.submit("khan", tenant="interactive").result(timeout=30)
        b_fut.result(timeout=30)
        deadline = time.monotonic() + 30
        while journal.pending() and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        sched.close()
        journal.close()
    entries, _sealed, torn = RequestJournal.read_state(tmp_path)
    assert torn == 0
    entry = entries["job-batch"]
    assert entry.status == "complete"  # exactly one terminal state
    raw = b"".join(p.read_bytes() for p in sorted(tmp_path.glob("*.jsonl")))
    assert b'"e":"preempted"' in raw and b'"e":"requeued"' in raw


# -- HTTP surface ------------------------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, json.loads(resp.read())


def _post(url, payload, headers=None):
    req_ = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req_, timeout=30) as resp:
        return resp.status, json.loads(resp.read())


@pytest.fixture()
def qos_server(tmp_path):
    state = ServeState(
        FakeBackend(segment_words=4, segment_overhead_s=0.002),
        max_batch=4, max_wait_s=0.005, inflight=True, slots=4,
        journal_dir=str(tmp_path / "journal"),
        tenants=TenantTable(
            parse_tenant_specs(
                "interactive:8:0,batch:1:0:batch,metered:1:40"
            )
        ),
    )
    server = make_server(state, "127.0.0.1", 0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{server.server_address[1]}", state
    server.shutdown()
    server.server_close()
    state.close()


def test_unknown_tenant_is_typed_400(qos_server):
    base, _ = qos_server
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(base + "/v1/generate", {"prompt": "ai do"},
              headers={"X-Tenant": "nobody"})
    assert exc.value.code == 400
    assert "unknown tenant" in json.loads(exc.value.read())["error"]


def test_missing_header_lands_on_default_tenant(qos_server):
    base, state = qos_server
    status, _ = _post(base + "/v1/generate", {"prompt": "vo danh " * 4})
    assert status == 200
    snap = state.scheduler.metrics.snapshot()
    assert snap.tenant_requests.get("default", 0) >= 1


def test_quota_shed_has_retry_after_header(qos_server):
    base, _ = qos_server
    # burst = 2x rate = 80 word-tokens; two 60-word prompts overflow it
    _post(base + "/v1/generate", {"prompt": "dinh muc " * 30},
          headers={"X-Tenant": "metered"})
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(base + "/v1/generate", {"prompt": "vuot muc " * 30},
              headers={"X-Tenant": "metered"})
    assert exc.value.code == 429
    body = json.loads(exc.value.read())
    assert body["reason"] == "quota"
    assert int(exc.value.headers["Retry-After"]) >= 1


def test_deadline_shed_has_retry_after_header(qos_server):
    base, _ = qos_server
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(base + "/v1/generate", {"prompt": "tre", "deadline_ms": 0})
    assert exc.value.code == 429
    assert int(exc.value.headers["Retry-After"]) >= 1


def test_queue_full_shed_has_retry_after_header():
    state = ServeState(
        FakeBackend(batch_overhead_s=0.2), max_batch=1, max_wait_s=0.005,
        max_queue_depth=1,
    )
    server = make_server(state, "127.0.0.1", 0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{server.server_address[1]}"
    stop = threading.Event()
    try:
        def fire():
            # keeps posting until told to stop: a one-shot burst loses the
            # race when the foreground post below gets queued first, and
            # the queue is then never full again (a flake, 2 runs in 5)
            while not stop.is_set():
                # lint-allow[swallowed-exception]: background load may itself shed or race shutdown — only the foreground 429 below is asserted
                try:
                    _post(base + "/v1/generate", {"prompt": "giu cho " * 4})
                except Exception:
                    time.sleep(0.005)
        threads = [threading.Thread(target=fire) for _ in range(6)]
        for t in threads:
            t.start()
        saw_429 = None
        for _ in range(40):
            try:
                _post(base + "/v1/generate", {"prompt": "day hang " * 4})
            except urllib.error.HTTPError as e:
                if e.code == 429:
                    saw_429 = e
                    break
            time.sleep(0.01)
        stop.set()
        assert saw_429 is not None, "queue never filled"
        assert int(saw_429.headers["Retry-After"]) >= 1
        assert json.loads(saw_429.read())["reason"] in (
            "queue_full", "token_budget"
        )
        for t in threads:
            t.join(timeout=30)
    finally:
        stop.set()
        server.shutdown()
        server.server_close()
        state.close()


def test_healthz_echoes_tenants_and_metrics_render_qos_rows(qos_server):
    base, _ = qos_server
    _post(base + "/v1/generate", {"prompt": "do dac " * 4},
          headers={"X-Tenant": "interactive"})
    _, health = _get(base + "/healthz")
    assert health["tenants"]["batch"]["tier"] == "batch"
    with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
        text = resp.read().decode()
    assert "vnsum_serve_qos_tenants 4" in text  # 3 declared + default
    assert 'vnsum_serve_qos_requests_total{tenant="interactive"}' in text
    assert 'vnsum_serve_qos_quota_sheds_total{tenant="metered"}' in text
    assert 'vnsum_serve_qos_bucket_tokens{tenant="metered"}' in text
    assert "vnsum_serve_qos_preemptions_total" in text
    assert "vnsum_serve_qos_requeues_total" in text
    assert 'vnsum_serve_requests_shed_total{reason="quota"}' in text


# -- GET /v1/requests/<id> lifecycle states ----------------------------------


def _seed(journal, rid, prompt="van ban"):
    r = ServeRequest(prompt=prompt, trace_id=rid)
    journal.accept(r)
    return r


def test_request_status_reports_each_lifecycle_state(qos_server):
    base, state = qos_server
    j = state.journal
    cases = {
        "st-accepted": [],
        "st-started": ["start"],
        "st-streaming": ["start", "streaming"],
        "st-preempted": ["start", "preempt"],
        "st-requeued": ["start", "preempt", "requeue"],
    }
    for rid, steps in cases.items():
        _seed(j, rid)
        for step in steps:
            getattr(j, step)(rid)
    for rid, expected in (
        ("st-accepted", "accepted"), ("st-started", "started"),
        ("st-streaming", "streaming"), ("st-preempted", "preempted"),
        ("st-requeued", "requeued"),
    ):
        _, body = _get(base + f"/v1/requests/{rid}")
        assert body["status"] == expected, (rid, body)
        assert body["entries"][0]["status"] in (
            "accept", "start", "streaming", "preempted", "requeued"
        )


def test_request_status_aggregates_fanout_states(qos_server):
    base, state = qos_server
    j = state.journal
    # fan-out: one sibling preempted, one actively streaming -> the
    # aggregate says streaming (something is moving)
    _seed(j, "fan-a", "mot")
    _seed(j, "fan-a", "hai")  # becomes fan-a#1
    j.preempt("fan-a")
    j.start("fan-a#1")
    j.streaming("fan-a#1")
    _, body = _get(base + "/v1/requests/fan-a")
    assert body["status"] == "streaming" and len(body["entries"]) == 2
    # both siblings parked by preemption, one already requeued -> requeued
    _seed(j, "fan-b", "ba")
    _seed(j, "fan-b", "bon")
    for rid in ("fan-b", "fan-b#1"):
        j.start(rid)
        j.preempt(rid)
    j.requeue("fan-b#1")
    _, body = _get(base + "/v1/requests/fan-b")
    assert body["status"] == "requeued"
    # a failed sibling still fails the fan-out whatever the others do
    _seed(j, "fan-c", "nam")
    _seed(j, "fan-c", "sau")
    j.preempt("fan-c")
    j.fail("fan-c#1", "poison")
    _, body = _get(base + "/v1/requests/fan-c")
    assert body["status"] == "failed"


def test_preempted_state_survives_compacting_reopen(tmp_path):
    from vnsum_tpu.serve.journal import RequestJournal

    j = RequestJournal(tmp_path)
    _seed(j, "dur-1")
    j.start("dur-1")
    j.preempt("dur-1")
    j.close()
    j2 = RequestJournal(tmp_path)  # reopen compacts
    try:
        entries = j2.lookup("dur-1")
        assert entries and entries[0].status == "preempted"
        # still replayable: take_unfinished hands it out exactly once
        assert [e.rid for e in j2.take_unfinished()] == ["dur-1"]
        assert j2.take_unfinished() == []
    finally:
        j2.close()
