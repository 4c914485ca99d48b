"""Replica-fleet router tests: in-process engine workers (ServeState +
FakeBackend on ephemeral ports) behind an in-process RouterState — routing
spread, cache-affinity stickiness, end-to-end request-id propagation,
inline journal-handoff failover, startup replay, the typed /readyz
contract, front-door sheds, and the router /metrics surface. Process-level
chaos (SIGKILL mid-load, rolling restarts) lives in
scripts/chaos_soak.py --fleet; these tests pin the mechanism."""
from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request
import zlib

import pytest

from vnsum_tpu.backend.fake import FakeBackend
from vnsum_tpu.serve.journal import RequestJournal, aggregate_status
from vnsum_tpu.serve.router import (
    RouterState,
    Worker,
    _RouterRequest,
    make_router_server,
    request_body_from_payload,
)
from vnsum_tpu.serve.server import ServeState, make_server
from vnsum_tpu.testing.chaos import free_port, http_delete, http_json


def _spawn_inproc_worker(name: str):
    """One in-process engine worker: full ServeState over FakeBackend on
    an ephemeral port — the /v1/* surface the router proxies to, without
    subprocess startup cost."""
    state = ServeState(FakeBackend(), max_batch=8, max_wait_s=0.005)
    server = make_server(state, "127.0.0.1", 0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    port = server.server_address[1]
    return Worker(name, "127.0.0.1", port), (server, state, thread)


def _mark_up(state: RouterState) -> None:
    with state._lock:
        for w in state.workers:
            w.up = True


@pytest.fixture()
def fleet(tmp_path):
    """Two in-process workers behind a journaled router (probe loop ON,
    fast cadence). Yields (base_url, router_state, workers)."""
    w0, h0 = _spawn_inproc_worker("w0")
    w1, h1 = _spawn_inproc_worker("w1")
    state = RouterState(
        [w0, w1],
        journal_dir=tmp_path / "router",
        probe_interval_s=0.05,
        probe_timeout_s=2.0,
        down_after=2,
        up_after=1,
        tenants={"alpha": "interactive", "beta": "batch"},
    )
    state.start()
    server = make_router_server(state, "127.0.0.1", 0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    state.wait_ready(timeout_s=10.0)
    yield f"http://127.0.0.1:{server.server_address[1]}", state, [w0, w1]
    server.shutdown()
    server.server_close()
    state.close(drain_timeout_s=5.0)
    for server_, sstate, _t in (h0, h1):
        server_.shutdown()
        server_.server_close()
        sstate.close()


def _post(url, payload, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, json.loads(resp.read()), dict(resp.headers)


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read()


def test_router_proxies_generate_and_summarize(fleet):
    base, state, _workers = fleet
    status, body, _ = _post(base + "/v1/generate",
                            {"prompt": "xin chào fleet",
                             "max_new_tokens": 8, "request_id": "f-gen"})
    assert status == 200
    assert body["request_id"] == "f-gen"
    assert body["completions"][0]["text"]
    status, body, _ = _post(base + "/v1/summarize",
                            {"text": "nội dung tiếng Việt có dấu. " * 30,
                             "request_id": "f-sum"})
    assert status == 200
    assert body["summary"] and body["approach"]
    # both landed in the GLOBAL ledger as completed
    for rid in ("f-gen", "f-sum"):
        assert aggregate_status(state.journal.lookup(rid)) == "completed"


def test_least_loaded_spreads_across_workers(fleet):
    base, _state, workers = fleet
    for i in range(8):
        status, _, _ = _post(base + "/v1/generate",
                             {"prompt": f"tin số {i}",
                              "request_id": f"spread-{i}"})
        assert status == 200
    counts = [w.requests for w in workers]
    assert sum(counts) == 8
    # no-affinity traffic must not pile onto one worker
    assert all(c > 0 for c in counts)


def test_cache_affinity_is_sticky(fleet):
    base, _state, workers = fleet
    before = [w.requests for w in workers]
    for i in range(6):
        status, _, _ = _post(
            base + "/v1/generate",
            {"prompt": f"cùng tiền tố, đuôi {i}",
             "cache_hint": "shared-prefix-A", "request_id": f"aff-{i}"},
        )
        assert status == 200
    deltas = [w.requests - b for w, b in zip(workers, before)]
    # rendezvous hashing: one worker took all six, the other none
    assert sorted(deltas) == [0, 6]


def test_request_id_and_tenant_propagate_end_to_end(fleet):
    """Satellite: ONE id crosses the router->worker hop — the client's
    X-Request-Id is the router's journal rid, the response echo, AND the
    worker-side trace id visible in that worker's /debug/trace ring."""
    base, state, workers = fleet
    rid = "trace-me-e2e"
    status, body, headers = _post(
        base + "/v1/generate",
        {"prompt": "định danh xuyên suốt"},
        headers={"X-Request-Id": rid, "X-Tenant": "alpha"},
    )
    assert status == 200
    assert body["request_id"] == rid
    assert headers["X-Request-Id"] == rid
    # the worker journaled/traced the SAME id (no router-side rewrite)
    assert body["completions"][0]["record"]["trace_id"] == rid
    found = False
    for w in workers:
        s, raw = _get(f"http://{w.host}:{w.port}/debug/trace")
        if s == 200 and rid in raw.decode():
            found = True
    assert found, "request id never appeared in any worker's trace ring"
    # the router ledger holds the same rid, completed
    assert aggregate_status(state.journal.lookup(rid)) == "completed"
    # tenant accounting happened at the front door
    s, raw = _get(base + "/healthz")
    assert json.loads(raw)["tenant_requests"].get("alpha", 0) >= 1


def test_unknown_tenant_is_typed_400(fleet):
    base, _state, _workers = fleet
    req = urllib.request.Request(
        base + "/v1/generate",
        data=json.dumps({"prompt": "x"}).encode(),
        headers={"Content-Type": "application/json", "X-Tenant": "ghost"},
        method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=10)
    assert exc.value.code == 400
    body = json.loads(exc.value.read())
    assert "ghost" in body["error"] and "alpha" in body["tenants"]


def test_stream_is_typed_501(fleet):
    base, _state, _workers = fleet
    req = urllib.request.Request(
        base + "/v1/generate",
        data=json.dumps({"prompt": "x", "stream": True}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=10)
    assert exc.value.code == 501
    assert json.loads(exc.value.read())["error"] == "stream_unsupported"


def _hint_for(workers, target_name: str) -> str:
    """A cache_hint whose rendezvous hash lands on ``target_name``."""
    for i in range(1000):
        hint = f"hint-{i}"
        best = max(workers, key=lambda w: zlib.crc32(
            f"{hint}|{w.name}".encode()
        ))
        if best.name == target_name:
            return hint
    raise AssertionError("no hint found")  # pragma: no cover


def test_inline_failover_replays_onto_survivor(tmp_path):
    """A worker that dies with the client still on the line: the proxy
    thread claims the journaled rids and re-dispatches onto the survivor —
    the client sees a 200, never the death."""
    live, handles = _spawn_inproc_worker("live")
    dead = Worker("dead", "127.0.0.1", free_port())  # nothing listening
    state = RouterState([dead, live], journal_dir=tmp_path / "router")
    # no probe loop: both marked up by hand so the dead endpoint is
    # deterministically picked first via affinity
    _mark_up(state)
    with state._lock:
        state._replay_started = state._replay_done = True
    server = make_router_server(state, "127.0.0.1", 0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        hint = _hint_for([dead, live], "dead")
        status, body, _ = _post(
            base + "/v1/generate",
            {"prompt": "sống sót qua failover", "cache_hint": hint,
             "request_id": "failover-1"},
        )
        assert status == 200
        text = body["completions"][0]["text"]
        assert aggregate_status(state.journal.lookup("failover-1")) \
            == "completed"
        assert dead.failovers >= 1 and live.requests >= 1
        # byte-identical to a direct hit on the survivor (deterministic
        # greedy engine + same payload)
        s2, direct, _ = _post(
            f"http://{live.host}:{live.port}/v1/generate",
            {"prompt": "sống sót qua failover", "cache_hint": hint},
        )
        assert s2 == 200 and direct["completions"][0]["text"] == text
    finally:
        server.shutdown()
        server.server_close()
        state.close(drain_timeout_s=2.0)
        handles[0].shutdown()
        handles[0].server_close()
        handles[1].close()


def test_failover_preserves_trace_identity_on_survivor(tmp_path):
    """Regression: the journal-handoff replay after a worker death must
    carry the ORIGINAL trace id onto the survivor — the client-facing
    request id, the X-Request-Id response header, and the survivor's own
    span ring all name the same trace, so the merged fleet trace can join
    the pre- and post-failover halves."""
    live, handles = _spawn_inproc_worker("live")
    dead = Worker("dead", "127.0.0.1", free_port())
    state = RouterState([dead, live], journal_dir=tmp_path / "router")
    _mark_up(state)
    with state._lock:
        state._replay_started = state._replay_done = True
    server = make_router_server(state, "127.0.0.1", 0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        hint = _hint_for([dead, live], "dead")
        status, body, resp_headers = _post(
            base + "/v1/generate",
            {"prompt": "giữ nguyên dấu vết", "cache_hint": hint,
             "request_id": "trace-keep-1"},
        )
        assert status == 200
        assert body["request_id"] == "trace-keep-1"
        assert resp_headers.get("X-Request-Id") == "trace-keep-1"
        # the survivor's span ring traced the replayed hop under the
        # ORIGINAL id (not a router-minted replacement); the worker's
        # trace finishes in its handler's finally — after the response
        # bytes — so poll briefly
        _srv, live_state, _t = handles
        deadline = time.monotonic() + 5.0
        survivor_ids: set = set()
        while time.monotonic() < deadline:
            survivor_ids = {t.trace_id
                            for t in live_state.obs.snapshot()[0]}
            if "trace-keep-1" in survivor_ids:
                break
            time.sleep(0.02)
        assert "trace-keep-1" in survivor_ids
        # and the router's own ring joined the same id, so the two halves
        # stitch into one merged trace (its trace, too, finishes after the
        # response bytes: the same brief poll)
        router_ids: set = set()
        while time.monotonic() < deadline:
            router_ids = {t.trace_id for t in state.obs.snapshot()[0]}
            if "trace-keep-1" in router_ids:
                break
            time.sleep(0.02)
        assert "trace-keep-1" in router_ids
    finally:
        server.shutdown()
        server.server_close()
        state.close(drain_timeout_s=2.0)
        handles[0].shutdown()
        handles[0].server_close()
        handles[1].close()


def test_startup_replay_hands_unfinished_accepts_to_workers(tmp_path):
    """Router-restart recovery: unfinished ACCEPTs in the router's own
    journal re-dispatch once a worker is routable, and the replayed
    completion is byte-identical to a direct engine answer."""
    jdir = tmp_path / "router"
    journal = RequestJournal(jdir, fsync_interval_s=0.0)
    req = _RouterRequest(trace_id="replay-me",
                         prompt="bản tin chưa hoàn thành",
                         max_new_tokens=12)
    rid = journal.accept(req)
    journal.start(rid)
    journal.close()
    assert rid == "replay-me"

    live, handles = _spawn_inproc_worker("live")
    state = RouterState([live], journal_dir=jdir, probe_interval_s=0.05)
    state.start()
    try:
        state.wait_ready(timeout_s=10.0)
        t_end = time.monotonic() + 10.0
        while time.monotonic() < t_end:
            if aggregate_status(state.journal.lookup(rid)) == "completed":
                break
            time.sleep(0.02)
        entries = {e.rid: e for e in state.journal.lookup(rid)}
        assert entries[rid].terminal and entries[rid].status == "complete"
        s, direct, _ = _post(
            f"http://{live.host}:{live.port}/v1/generate",
            {"prompt": "bản tin chưa hoàn thành", "max_new_tokens": 12},
        )
        assert s == 200
        assert entries[rid].to_dict()["text"] \
            == direct["completions"][0]["text"]
    finally:
        state.close(drain_timeout_s=2.0)
        handles[0].shutdown()
        handles[0].server_close()
        handles[1].close()


def test_router_readyz_typed_states(tmp_path):
    """/readyz on the router: pre_replay before the journal replays,
    no_worker with nothing routable, ready, then draining — each a typed
    reason a load balancer can branch on."""
    live, handles = _spawn_inproc_worker("live")
    state = RouterState([live], journal_dir=tmp_path / "router",
                        probe_interval_s=0.05)
    try:
        ready, reason = state.readiness()
        assert (ready, reason) == (False, "pre_replay")
        with state._lock:
            state._replay_started = state._replay_done = True
        ready, reason = state.readiness()
        assert (ready, reason) == (False, "no_worker")
        _mark_up(state)
        ready, reason = state.readiness()
        assert (ready, reason) == (True, "ready")
        with state._lock:
            state._draining = True
        ready, reason = state.readiness()
        assert (ready, reason) == (False, "draining")
        with state._lock:
            state._draining = False
    finally:
        state.close(drain_timeout_s=1.0)
        handles[0].shutdown()
        handles[0].server_close()
        handles[1].close()


def test_front_door_saturation_is_typed_429(fleet):
    base, state, _workers = fleet
    state.max_inflight = 0  # saturate the front door
    try:
        req = urllib.request.Request(
            base + "/v1/generate",
            data=json.dumps({"prompt": "x"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 429
        body = json.loads(exc.value.read())
        assert body["reason"] == "queue_full"
        assert exc.value.headers["Retry-After"]
    finally:
        state.max_inflight = 256


def test_router_metrics_surface(fleet):
    """The router /metrics renders only registered names (doc-lint parity
    with the worker surface) and carries per-worker + journal series."""
    base, _state, _workers = fleet
    _post(base + "/v1/generate", {"prompt": "đo lường"})
    status, raw = _get(base + "/metrics")
    assert status == 200
    text = raw.decode()
    from vnsum_tpu.serve.metrics import metric_names

    registered = set(metric_names())
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name = line.split("{")[0].split(" ")[0]
        for suffix in ("_bucket", "_sum", "_count"):
            # histogram sample names derive from a registered base
            if name not in registered and name.endswith(suffix):
                name = name[: -len(suffix)]
        assert name in registered, line
    assert 'vnsum_serve_router_requests_total{worker="w0"}' in text
    assert 'vnsum_serve_router_sheds_total{reason="queue_full"}' in text
    assert "vnsum_serve_journal_pending" in text
    assert "vnsum_serve_router_workers_up 2" in text
    # fleet federation re-exports ride the same surface
    assert "vnsum_serve_federation_scrapes_total" in text
    assert 'vnsum_serve_fleet_incidents_total{reason="failover"} 0' in text


def test_cancel_routes_to_ledger(fleet):
    """DELETE on a completed rid answers from the global ledger (terminal
    entries stay terminal — cancel is idempotent, not destructive)."""
    base, state, _workers = fleet
    _post(base + "/v1/generate", {"prompt": "hủy tôi đi",
                                  "request_id": "cancel-me"})
    port = int(base.rsplit(":", 1)[1])
    status, body = http_json("GET", "127.0.0.1", port,
                             "/v1/requests/cancel-me")
    assert status == 200 and body["status"] == "completed"
    status, body = http_delete("127.0.0.1", port,
                               "/v1/requests/cancel-me")
    assert status == 200
    assert aggregate_status(state.journal.lookup("cancel-me")) \
        == "completed"


def test_rolling_restart_endpoint_answers_202(fleet):
    """Unspawned (externally managed) workers: the rolling restart
    accepts, then skips every worker it does not own. The full
    drain-one-restart-one path over real subprocesses runs in
    scripts/chaos_soak.py --fleet."""
    base, state, _workers = fleet
    status, body, _ = _post(base + "/admin/rolling-restart", {})
    assert status == 202 and body["status"] == "rolling"
    t_end = time.monotonic() + 5.0
    while time.monotonic() < t_end:
        with state._lock:
            rolling = state._rolling
        if not rolling:
            break
        time.sleep(0.02)
    result = state.rolling_restart()
    assert result["status"] == "done"
    assert result["skipped"] == ["w0", "w1"] and not result["restarted"]


def test_request_body_from_payload_round_trip():
    """The handoff inverse: journal payload -> re-POST body keeps the
    fields the /v1/* surface accepts and nothing it rejects (summarize
    must not regrow sampling knobs — unknown fields are a typed 400)."""
    payload = {
        "prompt": "văn bản", "max_new_tokens": 32,
        "config": {"temperature": 0.7, "top_k": 40, "top_p": None,
                   "seed": 7, "spec_k": 2, "eos_ids": [0]},
        "reference": None, "cache_hint": "h1", "trace_id": "t",
        "deadline_unix": time.time() + 30.0, "tenant": "alpha",
    }
    path, body, headers = request_body_from_payload("rid-1", payload)
    assert path == "/v1/generate"
    assert body["prompt"] == "văn bản" and body["cache_hint"] == "h1"
    assert body["temperature"] == 0.7 and body["seed"] == 7
    assert "top_p" not in body and "eos_ids" not in body
    assert 0 < body["deadline_ms"] <= 30_000
    assert headers == {"X-Request-Id": "rid-1", "X-Tenant": "alpha"}

    spayload = {"prompt": "tóm tắt dài", "approach": "refine",
                "max_new_tokens": 64, "trace_id": "t2",
                "deadline_unix": None}
    path, body, headers = request_body_from_payload("rid-2", spayload)
    assert path == "/v1/summarize"
    assert body == {"request_id": "rid-2", "max_new_tokens": 64,
                    "text": "tóm tắt dài", "approach": "refine"}
