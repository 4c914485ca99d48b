"""Chaos soak: SIGKILL a live serving process at seeded points and prove
the durable-serving ledger invariant.

The write-ahead journal (vnsum_tpu/serve/journal.py) claims at-least-once
acceptance semantics across process death. This harness is the acceptance
test for that claim, end to end and out of process:

1. start ``python -m vnsum_tpu.serve.server --backend fake --journal-dir D``
   as a subprocess (the fake backend carries a device-shaped latency model
   so kills land mid-prefill/mid-decode, not between instantaneous calls);
2. drive mixed closed-loop load (unique deterministic prompts, explicit
   ``request_id``\\ s, a mix of default and seeded-sampling configs);
3. at seeded points (``--seed``), SIGKILL it — ``mid_load`` kills catch
   requests mid-prefill or mid-decode; ``mid_drain`` kills send SIGTERM
   first and SIGKILL a beat into the drain, so the journal dies UNSEALED
   with work in every state;
4. restart on the same journal dir — startup replay re-enqueues every
   unfinished ACCEPT through the supervised path;
5. after the schedule: wait for the ledger to quiesce
   (``GET /metrics`` -> ``vnsum_serve_journal_pending 0``), spot-check the
   reconnect surface (``GET /v1/requests/<id>``), SIGTERM for a graceful
   drain+seal, and assert exit code 0;
6. audit the journal OFFLINE (read-only) and assert:

   - **ledger invariant**: every journaled ACCEPT ended COMPLETE or typed
     FAILED — never lost;
   - **byte-identity**: every COMPLETE's text equals the deterministic
     reference output computed from the same payload in-process (greedy
     replays are byte-identical by the engine's determinism guarantees).

Exit 0 only when every assertion holds. ``--out`` records the run as a
JSON artifact (written atomically, of course).

    python scripts/chaos_soak.py --seed 7 --kills 3 --out CHAOS_soak_r01.json
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import sys
import tempfile
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from vnsum_tpu.backend.fake import FakeBackend  # noqa: E402
from vnsum_tpu.core.artifacts import atomic_write_json  # noqa: E402
from vnsum_tpu.serve.journal import RequestJournal, aggregate_status  # noqa: E402
from vnsum_tpu.testing.chaos import (  # noqa: E402
    KillSchedule,
    RouterProcess,
    ServerProcess,
    free_port,
    http_delete,
    http_json,
    sse_stream,
)

# the load: unique deterministic Vietnamese-shaped prompts; half the
# requests carry a seeded sampling config so replay determinism is proven
# for the journaled-seed path too, not just default greedy
_WORDS = ("văn bản tiếng Việt cần tóm tắt nội dung chính sách kinh tế "
          "xã hội giáo dục y tế môi trường").split()


def make_prompt(cid: int, i: int) -> str:
    body = " ".join(_WORDS[(cid + i + k) % len(_WORDS)] for k in range(60))
    return f"Tài liệu {cid}-{i}: {body}"


def make_payload(cid: int, i: int) -> dict:
    payload = {
        "prompt": make_prompt(cid, i),
        "request_id": f"soak-{cid}-{i}",
    }
    if (cid + i) % 2:
        # journaled-seed arm: temperature 0 keeps the fake backend
        # deterministic while exercising config round-trip through the WAL
        payload.update({"temperature": 0.0, "seed": cid * 1000 + i})
    return payload


def reference_output(payload: dict) -> str:
    """What an uninterrupted run returns for this journaled payload — the
    fake backend is deterministic per payload, so one in-process call is
    the oracle the replayed COMPLETEs must byte-match. The journaled
    GenerationConfig rides along: a WAL round-trip that dropped or mangled
    the config/seed must FAIL this check, not coincide with it."""
    from vnsum_tpu.core.config import GenerationConfig

    cfg = None
    if payload.get("config") is not None:
        c = dict(payload["config"])
        c["eos_ids"] = tuple(c.get("eos_ids") or ())
        cfg = GenerationConfig(**c)
    return FakeBackend().generate(
        [payload.get("prompt", "")],
        max_new_tokens=payload.get("max_new_tokens"),
        config=cfg,
    )[0]


class LoadDriver:
    """Closed-loop clients firing the deterministic payload stream; robust
    to the server dying mid-request (that is the point). With ``qos=True``
    odd clients ride the preemptible batch tenant and even ones the
    interactive tenant (X-Tenant header) — the mix that makes the server
    actually preempt."""

    def __init__(self, port: int, clients: int, per_client: int,
                 qos: bool = False) -> None:
        self.port = port
        self.clients = clients
        self.per_client = per_client
        self.qos = qos
        self.attempted: dict[str, str] = {}  # rid -> prompt
        self.completed: dict[str, str] = {}  # rid -> text (HTTP 200 seen)
        self._lock = threading.Lock()
        self._cursor = [0] * clients
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()

    def _client(self, cid: int) -> None:
        while not self._stop.is_set():
            i = self._cursor[cid]
            if i >= self.per_client:
                return
            payload = make_payload(cid, i)
            rid = payload["request_id"]
            with self._lock:
                self.attempted[rid] = payload["prompt"]
            headers = None
            if self.qos:
                headers = {
                    "X-Tenant": "batch" if cid % 2 else "interactive"
                }
            try:
                status, body = http_json(
                    "POST", "127.0.0.1", self.port, "/v1/generate",
                    payload, timeout=20.0, headers=headers,
                )
                if status == 200 and body and body.get("completions"):
                    with self._lock:
                        self.completed[rid] = body["completions"][0]["text"]
                    self._cursor[cid] = i + 1
                elif status in (400, 404):
                    self._cursor[cid] = i + 1  # don't spin on a client bug
                else:
                    time.sleep(0.05)  # shed/error: back off, retry same i
            except OSError:
                time.sleep(0.1)  # server is down/being killed: wait it out

    def start(self) -> None:
        self._threads = [
            threading.Thread(target=self._client, args=(cid,), daemon=True)
            for cid in range(self.clients)
        ]
        for t in self._threads:
            t.start()

    @property
    def done(self) -> bool:
        return all(c >= self.per_client for c in self._cursor)

    def stop(self, timeout_s: float = 30.0) -> None:
        self._stop.set()
        t_end = time.monotonic() + timeout_s
        for t in self._threads:
            t.join(timeout=max(t_end - time.monotonic(), 0.1))


def scrape_metric(port: int, name: str) -> int | None:
    """One /metrics scrape -> the integer value of ``name`` (labels
    allowed verbatim, e.g. ``..._total{stage="queued"}``), or None."""
    import http.client

    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
    except OSError:
        return None
    m = re.search(rf"^{re.escape(name)} (\d+)", text, re.M)
    return int(m.group(1)) if m else None


# -- client-churn soak (--churn): cancels/disconnects, no process kills ------


class ChurnDriver:
    """Seeded client churn against a live in-flight server: every request
    draws one behavior — complete normally (plain or streamed), DELETE
    itself mid-flight (instantly = mid-queue-biased, or after a delay =
    mid-slot-biased), or open a stream and drop the socket mid-decode.
    Odd clients ride the preemptible batch tenant, even ones interactive,
    so tier preemption runs underneath the churn the whole time."""

    MODES = ("plain", "stream_full", "cancel_fast", "cancel_slow",
             "stream_abandon")
    WEIGHTS = (0.30, 0.20, 0.15, 0.20, 0.15)

    def __init__(self, port: int, clients: int, per_client: int,
                 seed: int) -> None:
        self.port = port
        self.clients = clients
        self.per_client = per_client
        self.seed = seed
        self._lock = threading.Lock()
        self.attempted: dict[str, str] = {}     # rid -> prompt
        self.completed: dict[str, str] = {}     # rid -> text (client saw it)
        self.churned: set[str] = set()          # rid -> cancelled/abandoned
        self.mode_counts: dict[str, int] = {}
        self.identity_failures: list[str] = []  # streamed deltas != done
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()

    def _headers(self, cid: int) -> dict:
        return {"X-Tenant": "batch" if cid % 2 else "interactive"}

    def _rid(self, cid: int, i: int) -> str:
        return f"churn-{cid}-{i}"

    def _client(self, cid: int) -> None:
        import random

        rng = random.Random(self.seed * 1000 + cid)
        for i in range(self.per_client):
            if self._stop.is_set():
                return
            mode = rng.choices(self.MODES, weights=self.WEIGHTS)[0]
            rid = self._rid(cid, i)
            payload = {"prompt": make_prompt(cid, i), "request_id": rid}
            if (cid + i) % 2:
                payload.update({"temperature": 0.0,
                                "seed": cid * 1000 + i})
            with self._lock:
                self.attempted[rid] = payload["prompt"]
                self.mode_counts[mode] = self.mode_counts.get(mode, 0) + 1
            try:
                self._one(cid, i, rng, mode, rid, payload)
            except OSError:
                time.sleep(0.1)  # server hiccup: this request is forfeit

    def _one(self, cid, i, rng, mode, rid, payload) -> None:
        headers = self._headers(cid)
        if mode == "plain":
            status, body = http_json(
                "POST", "127.0.0.1", self.port, "/v1/generate",
                payload, timeout=30.0, headers=headers,
            )
            if status == 200 and body and body.get("completions"):
                with self._lock:
                    self.completed[rid] = body["completions"][0]["text"]
        elif mode == "stream_full":
            status, events = sse_stream(
                "127.0.0.1", self.port, "/v1/generate",
                {**payload, "stream": True}, headers=headers,
            )
            if status != 200 or not events or events[-1][0] != "done":
                return
            done = events[-1][1]
            text = done["completions"][0]["text"]
            deltas = "".join(p["text"] for n, p in events if n == "delta")
            if deltas != text:
                with self._lock:
                    self.identity_failures.append(rid)
            with self._lock:
                self.completed[rid] = text
        elif mode in ("cancel_fast", "cancel_slow"):
            # DELETE from a side thread while the POST blocks: fast draws
            # bias mid-queue/mid-prefill, slow draws mid-slot/mid-decode
            delay = (rng.uniform(0.0, 0.02) if mode == "cancel_fast"
                     else rng.uniform(0.06, 0.25))
            with self._lock:
                self.churned.add(rid)

            def cancel_later():
                time.sleep(delay)
                try:
                    http_delete("127.0.0.1", self.port,
                                f"/v1/requests/{rid}")
                except OSError:
                    pass  # lint-allow[swallowed-exception]: the POST side still resolves the request; a lost DELETE just means this draw degraded to a plain request

            t = threading.Thread(target=cancel_later, daemon=True)
            t.start()
            status, body = http_json(
                "POST", "127.0.0.1", self.port, "/v1/generate",
                payload, timeout=30.0, headers=headers,
            )
            t.join(timeout=10)
            if status == 200 and body and body.get("completions"):
                # the cancel lost the completion race — legal; the ledger
                # must then say COMPLETE and byte-match like any survivor
                with self._lock:
                    self.completed[rid] = body["completions"][0]["text"]
        else:  # stream_abandon
            with self._lock:
                self.churned.add(rid)
            sse_stream(
                "127.0.0.1", self.port, "/v1/generate",
                {**payload, "stream": True},
                abandon_after=rng.randint(1, 3), headers=headers,
            )

    def start(self) -> None:
        self._threads = [
            threading.Thread(target=self._client, args=(cid,), daemon=True)
            for cid in range(self.clients)
        ]
        for t in self._threads:
            t.start()

    def join(self, timeout_s: float) -> bool:
        t_end = time.monotonic() + timeout_s
        for t in self._threads:
            t.join(timeout=max(t_end - time.monotonic(), 0.1))
        return not any(t.is_alive() for t in self._threads)

    def stop(self) -> None:
        self._stop.set()


def _churn_stage_probes(port: int) -> dict:
    """Deterministic stage coverage on top of the random churn: pin each
    lifecycle stage with a dedicated scenario so the acceptance assertions
    never depend on a lucky draw. Returns the probe bookkeeping (rids per
    scenario) for the offline audit."""
    long_prompt = " ".join(f"tai lieu dai {k}" for k in range(120))
    probes = {"resident": [], "queued": [], "preempt_cancel": []}

    def submit_bg(rid: str, tenant: str):
        def run():
            try:
                http_json("POST", "127.0.0.1", port, "/v1/generate",
                          {"prompt": long_prompt, "request_id": rid},
                          timeout=30.0, headers={"X-Tenant": tenant})
            except OSError:
                pass  # lint-allow[swallowed-exception]: the server resolves the request either way; the probe audits the LEDGER, not this socket

        t = threading.Thread(target=run, daemon=True)
        t.start()
        return t

    # (a) saturate all 4 slots with batch-tier work, cancel one RESIDENT
    fillers = [submit_bg(f"probe-res-{k}", "batch") for k in range(4)]
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if scrape_metric(port, "vnsum_serve_slots_busy") == 4:
            break
        time.sleep(0.02)
    probes["resident"].append("probe-res-0")
    http_delete("127.0.0.1", port, "/v1/requests/probe-res-0")
    # (b) with slots still saturated, a 5th request must QUEUE — cancel it
    queued_t = submit_bg("probe-q-0", "interactive")
    time.sleep(0.03)
    probes["queued"].append("probe-q-0")
    http_delete("127.0.0.1", port, "/v1/requests/probe-q-0")
    # (c) mid-preemption: an interactive burst evicts the remaining batch
    # residents (the widened eviction->journal gap keeps the window open).
    # Wait for the preemption counter to actually move — a DELETE fired
    # before the eviction would cancel the victim as a plain resident and
    # prove nothing about the preempt->cancel window — then cancel the
    # victims while they sit preempted/requeued
    preempts_before = scrape_metric(
        port, "vnsum_serve_qos_preemptions_total") or 0
    burst = [submit_bg(f"probe-burst-{k}", "interactive") for k in range(6)]
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        n = scrape_metric(port, "vnsum_serve_qos_preemptions_total")
        if n is not None and n > preempts_before:
            break
        time.sleep(0.01)
    for k in range(1, 4):
        rid = f"probe-res-{k}"
        probes["preempt_cancel"].append(rid)
        http_delete("127.0.0.1", port, f"/v1/requests/{rid}")
    for t in fillers + [queued_t] + burst:
        t.join(timeout=30)
    return probes


def churn_soak(args) -> int:
    """Client-churn soak: no process ever dies — the CLIENTS do. Seeded
    cancels and disconnects land mid-queue, mid-stream, mid-slot, and
    mid-preemption against an in-flight, two-tier, journaled server; the
    audit then proves the server reclaimed everything: zero busy slots,
    prefix-cache pins back to baseline, every journaled ACCEPT terminal
    (CANCELLED included), and every COMPLETE byte-identical to the
    deterministic reference."""
    journal_dir = args.journal_dir or tempfile.mkdtemp(prefix="vnsum-churn-")
    own_dir = args.journal_dir is None
    server_args = [
        "--max-batch", "4",
        "--max-wait-ms", "20",
        "--drain-timeout-s", "20",
        "--trace-sample", "0",
        "--inflight", "--slots", "4",
        "--tenants", "interactive:4:0,batch:1:0:batch",
        "--fake-batch-overhead-ms", str(args.fake_batch_overhead_ms),
        "--fake-per-prompt-ms", str(args.fake_per_prompt_ms),
        "--fake-segment-overhead-ms", "30",
        # 2 words/segment -> a 40-word summary spans ~20 segments (~600ms):
        # abandoned streams are still decoding when the idle window fires
        "--fake-segment-words", "2",
        "--stream-heartbeat-s", "0.1",
        "--stream-idle-timeout-s", str(args.stream_idle_timeout_s),
    ]
    server_env = {
        # widen the eviction->PREEMPTED-journal gap so mid-preemption
        # cancels have a real window to land in
        "VNSUM_CHAOS_PREEMPT_GAP_MS": str(args.preempt_gap_ms),
    }
    port = free_port()
    srv = ServerProcess(port, journal_dir=journal_dir,
                        extra_args=server_args, env=server_env)
    srv.start()
    srv.wait_healthy()
    driver = ChurnDriver(port, args.clients, args.per_client, args.seed)
    print(f"churn soak: {args.clients} clients x {args.per_client} "
          f"requests, seed={args.seed}", flush=True)
    counters: dict = {}
    try:
        driver.start()
        if not driver.join(timeout_s=120):
            driver.stop()
            print("FAIL: churn driver never finished")
            return 1
        probes = _churn_stage_probes(port)

        # quiesce: every accepted request terminal, nothing resident
        t_end = time.monotonic() + args.quiesce_timeout_s
        while time.monotonic() < t_end:
            pending = scrape_metric(port, "vnsum_serve_journal_pending")
            busy = scrape_metric(port, "vnsum_serve_slots_busy")
            depth = scrape_metric(port, "vnsum_serve_queue_depth")
            if pending == 0 and busy == 0 and depth == 0:
                break
            time.sleep(0.2)
        for name in (
            "vnsum_serve_journal_pending",
            "vnsum_serve_slots_busy",
            "vnsum_serve_queue_depth",
            "vnsum_serve_cache_pinned_blocks",
            'vnsum_serve_cancel_requests_total{stage="queued"}',
            'vnsum_serve_cancel_requests_total{stage="dispatched"}',
            'vnsum_serve_cancel_requests_total{stage="resident"}',
            "vnsum_serve_cancel_disconnects_total",
            "vnsum_serve_qos_preemptions_total",
            "vnsum_serve_stream_backpressure_coalesced_total",
            "vnsum_serve_stream_heartbeats_total",
            "vnsum_serve_inflight_segments_total",
        ):
            counters[name] = scrape_metric(port, name)

        srv.sigterm()
        rc = srv.wait_exit(timeout_s=30)
        if rc != 0:
            print(f"FAIL: graceful SIGTERM shutdown exited {rc}, not 0")
            return 1
        srv = None
    finally:
        driver.stop()
        if srv is not None and srv.alive:
            srv.sigkill()

    # -- offline ledger audit (read-only) ---------------------------------
    entries, sealed, torn = RequestJournal.read_state(journal_dir)
    lost = [e.rid for e in entries.values() if not e.terminal]
    completed = [e for e in entries.values() if e.status == "complete"]
    cancelled = [e for e in entries.values() if e.status == "cancelled"]
    mismatches = [
        e.rid for e in completed if e.text != reference_output(e.payload)
    ]
    by_rid = {e.rid: e for e in entries.values()}
    client_vs_ledger = [
        rid for rid, text in driver.completed.items()
        if (e := by_rid.get(rid)) is not None
        and e.status == "complete" and e.text != text
    ]
    # every churned rid must be terminal as cancelled OR complete (losing
    # the completion race is legal; limbo is not)
    churn_unresolved = [
        rid for rid in driver.churned
        if (e := by_rid.get(rid)) is not None
        and e.status not in ("cancelled", "complete")
    ]
    # mid-preemption coverage: at least one cancelled rid whose raw event
    # stream also carries a PREEMPTED record
    raw = b"".join(
        p.read_bytes() for p in sorted(Path(journal_dir).glob("*.jsonl"))
    )
    preempted_rids = {
        m.group(1).decode()
        for m in re.finditer(
            rb'"e":"preempted","rid":"([^"]+)"', raw
        )
    }
    preempt_cancel_overlap = sorted(
        preempted_rids & {e.rid for e in cancelled}
    )

    record = {
        "bench": "chaos_soak_client_churn",
        "seed": args.seed,
        "clients": args.clients,
        "per_client": args.per_client,
        "mode_counts": driver.mode_counts,
        "stage_probes": probes,
        "counters": counters,
        "sealed": sealed,
        "torn_records_dropped": torn,
        "journaled_accepts": len(entries),
        "completed": len(completed),
        "cancelled": len(cancelled),
        "typed_failed": sum(
            1 for e in entries.values() if e.status == "failed"
        ),
        "lost": lost,
        "replay_byte_mismatches": mismatches,
        "client_vs_ledger_mismatches": client_vs_ledger,
        "stream_identity_failures": driver.identity_failures,
        "churned_unresolved": churn_unresolved,
        "preempt_cancel_overlap": preempt_cancel_overlap,
        "client_attempted": len(driver.attempted),
        "client_saw_200": len(driver.completed),
        "client_churned": len(driver.churned),
    }
    print(json.dumps(record, indent=2, ensure_ascii=False))
    if args.out:
        atomic_write_json(args.out, record)
        print(f"wrote {args.out}")
    if own_dir:
        shutil.rmtree(journal_dir, ignore_errors=True)

    ok = (
        not lost
        and not mismatches
        and not client_vs_ledger
        and not driver.identity_failures
        and not churn_unresolved
        and sealed
        and len(entries) > 0
        and len(cancelled) > 0
        # reclamation: nothing resident, no pin leaks at quiesce
        and counters.get("vnsum_serve_slots_busy") == 0
        and counters.get("vnsum_serve_queue_depth") == 0
        and counters.get("vnsum_serve_cache_pinned_blocks") == 0
        # all four lifecycle stages actually exercised
        and (counters.get(
            'vnsum_serve_cancel_requests_total{stage="queued"}') or 0) > 0
        and (counters.get(
            'vnsum_serve_cancel_requests_total{stage="resident"}') or 0) > 0
        and (counters.get("vnsum_serve_cancel_disconnects_total") or 0) > 0
        and (counters.get("vnsum_serve_qos_preemptions_total") or 0) > 0
        and len(preempt_cancel_overlap) > 0
        # the whole soak ran on the slot loop
        and (counters.get("vnsum_serve_inflight_segments_total") or 0) > 0
    )
    print("churn ledger invariant:", "OK" if ok else "VIOLATED")
    return 0 if ok else 1


# -- hang-injection soak (--hang): wedged threads, no exceptions -------------


def hang_soak(args) -> int:
    """Hang-injection soak (ISSUE 15): the process never crashes and no
    exception ever fires — threads simply STOP RETURNING, at seeded points,
    and the watchdog must keep the service live end to end:

    - epoch 1 (``mid_dispatch``): a forever-hang inside a one-shot engine
      dispatch. The watchdog declares it HUNG past its budget, resolves the
      riders typed (clients retry), replaces the scheduler thread, and the
      server keeps serving — graceful SIGTERM must still exit 0.
    - epoch 2 (``mid_slot_loop``): a forever-hang inside an in-flight decode
      segment. Recovery tears the loop down and REQUEUES every resident
      through the journal's replayable ACCEPT — clients see nothing but
      latency; byte-identity holds on the rebuilt loop.
    - epoch 3 (``mid_fsync``): a forever-hang inside the journal's
      group-commit fsync — the scheduler wedges INSIDE the journal lock,
      where a replacement thread would deadlock too. The watchdog
      classifies it as a lock stall and escalates: supervised
      seal-and-exit with WATCHDOG_EXIT_CODE, the harness restarts (the
      process-manager role), and journal replay restores state.
    - final epoch: no faults; the ledger quiesces and seals.

    Offline audit: every journaled ACCEPT terminal (0 lost), COMPLETEs
    byte-identical to the deterministic reference, watchdog stack dumps on
    disk for BOTH the dispatch and the lock stalls (with the wedged frame —
    the fault plan's hang site — visible in a stack), a flight-recorder
    dump carrying the typed ``stall`` event, and every stall detected
    within its configured bound + ``--detect-slack-s``."""
    from vnsum_tpu.serve.watchdog import WATCHDOG_EXIT_CODE

    journal_dir = args.journal_dir or tempfile.mkdtemp(prefix="vnsum-hang-")
    own_dir = args.journal_dir is None
    flight_dir = str(Path(journal_dir) / "flight")
    common = [
        "--max-batch", "4",
        "--max-wait-ms", "20",
        "--drain-timeout-s", "20",
        "--trace-sample", "0",
        "--fake-batch-overhead-ms", "40",
        "--fake-per-prompt-ms", "2",
        "--flight-dir", flight_dir,
        # tight liveness bounds so the soak runs in seconds: dispatches get
        # a 1s budget (per-token term off for determinism), loop heartbeats
        # a 1s deadline, the monitor ticks at 10Hz
        "--watchdog-interval-s", "0.1",
        "--watchdog-stall-s", "1.0",
        "--watchdog-dispatch-budget-s", "1.0",
        "--watchdog-dispatch-per-token-ms", "0",
    ]
    inflight = [
        "--inflight", "--slots", "4",
        "--fake-segment-overhead-ms", "20",
        "--fake-segment-words", "2",
    ]
    s = args.seed
    epochs = [
        # (name, extra server args, VNSUM_FAULTS, expected stall kind, end)
        ("mid_dispatch", [],
         f"seed={s};fake.dispatch:hang@on_call=4,delay_s=0",
         "dispatch", "sigterm"),
        ("mid_slot_loop", inflight,
         f"seed={s};fake.slot_step:hang@on_call=6,delay_s=0",
         "dispatch", "sigterm"),
        ("mid_fsync", ["--journal-fsync-ms", "0"],
         f"seed={s};journal.fsync:hang@on_call=3,delay_s=0",
         "lock", "escalate"),
    ]
    port = free_port()
    driver = LoadDriver(port, args.clients, args.per_client * 10)
    epoch_counters: list[dict] = []
    escalate_rc: int | None = None
    srv = None

    def scrape_stalls(kind: str):
        return scrape_metric(
            port, f'vnsum_serve_watchdog_stalls_total{{kind="{kind}"}}'
        )

    try:
        driver_started = False
        for name, extra, faults, expect_kind, end in epochs:
            print(f"[epoch {name}] faults={faults}", flush=True)
            srv = ServerProcess(
                port, journal_dir=journal_dir, extra_args=common + extra,
                env={"VNSUM_FAULTS": faults},
            )
            srv.start()
            srv.wait_healthy()
            if not driver_started:
                driver.start()
                driver_started = True
            if end == "sigterm":
                # in-process recovery epoch: wait for the stall verdict AND
                # a completed recovery, settle, then prove the server is
                # still a working server (graceful drain, exit 0)
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    stalls = scrape_stalls(expect_kind)
                    recoveries = scrape_metric(
                        port, "vnsum_serve_watchdog_recoveries_total"
                    )
                    if (stalls or 0) > 0 and (recoveries or 0) > 0:
                        break
                    time.sleep(0.1)
                else:
                    print(f"FAIL: epoch {name}: no {expect_kind} stall/"
                          "recovery observed")
                    return 1
                time.sleep(1.0)  # let retried/requeued work flow
                epoch_counters.append({
                    "epoch": name,
                    "stalls_dispatch": scrape_stalls("dispatch"),
                    "stalls_lock": scrape_stalls("lock"),
                    "recoveries": scrape_metric(
                        port, "vnsum_serve_watchdog_recoveries_total"),
                    "hung_dispatches": scrape_metric(
                        port, "vnsum_serve_watchdog_hung_dispatches_total"),
                    "segments": scrape_metric(
                        port, "vnsum_serve_inflight_segments_total"),
                })
                srv.sigterm()
                rc = srv.wait_exit(timeout_s=30)
                if rc != 0:
                    print(f"FAIL: epoch {name}: graceful SIGTERM exited "
                          f"{rc}, not 0")
                    return 1
                srv = None
            else:
                # escalation epoch: the wedge is inside the journal lock —
                # the only liveness-preserving exit is seal-and-exit with
                # the watchdog code; the harness is the process manager
                rc = srv.wait_exit(timeout_s=60)
                escalate_rc = rc
                if rc != WATCHDOG_EXIT_CODE:
                    print(f"FAIL: epoch {name}: expected watchdog exit "
                          f"{WATCHDOG_EXIT_CODE}, got {rc}")
                    return 1
                epoch_counters.append({"epoch": name, "exit_code": rc})
                srv = None

        # final epoch: no faults — replay the escalation epoch's unfinished
        # work, quiesce, and seal
        print("[epoch final] no faults: replay + quiesce + seal", flush=True)
        srv = ServerProcess(port, journal_dir=journal_dir,
                            extra_args=common, env={"VNSUM_FAULTS": ""})
        srv.start()
        srv.wait_healthy()
        # the manual twin: SIGUSR1 must write an on-demand stack dump to
        # --flight-dir (audited below alongside the automatic ones)
        import os as _os
        import signal as _signal

        _os.kill(srv.proc.pid, _signal.SIGUSR1)
        driver.stop(timeout_s=30)
        t_end = time.monotonic() + args.quiesce_timeout_s
        while time.monotonic() < t_end:
            if scrape_metric(port, "vnsum_serve_journal_pending") == 0:
                break
            time.sleep(0.2)
        pending = scrape_metric(port, "vnsum_serve_journal_pending")
        if pending != 0:
            print(f"FAIL: journal never quiesced (pending={pending})")
            return 1
        srv.sigterm()
        rc = srv.wait_exit(timeout_s=30)
        if rc != 0:
            print(f"FAIL: final graceful SIGTERM exited {rc}, not 0")
            return 1
        srv = None
    finally:
        driver.stop(timeout_s=5)
        if srv is not None and srv.alive:
            srv.sigkill()

    # -- offline audit (read-only) ----------------------------------------
    entries, sealed, torn = RequestJournal.read_state(journal_dir)
    lost = [e.rid for e in entries.values() if not e.terminal]
    completed = [e for e in entries.values() if e.status == "complete"]
    hung_failed = [e for e in entries.values()
                   if e.status == "failed" and e.reason == "hung"]
    mismatches = [
        e.rid for e in completed if e.text != reference_output(e.payload)
    ]

    # watchdog stack dumps: both classifications on disk, the wedged frame
    # (the fault plan's hang site) visible in a stack, detection latency
    # inside the configured bound
    wd_dumps = sorted(
        p for p in Path(flight_dir).glob("watchdog_*.json")
        if not p.name.startswith("watchdog_sigusr1_")  # audited separately
    )
    dump_kinds: dict[str, int] = {}
    detect_latencies: list[float] = []
    stacks_show_wedge = False
    dumps_well_formed = bool(wd_dumps)
    for p in wd_dumps:
        try:
            d = json.loads(p.read_text())
            stall = d["stall"]
            dump_kinds[stall["kind"]] = dump_kinds.get(stall["kind"], 0) + 1
            detect_latencies.append(
                round(stall["stalled_for_s"] - stall["limit_s"], 3)
            )
            if not d["stacks"]:
                raise ValueError("dump carries no thread stacks")
            if any("faults.py" in ln or "_hang_release" in ln
                   for t in d["stacks"] for ln in t["stack"]):
                stacks_show_wedge = True
        except (KeyError, ValueError):
            dumps_well_formed = False
    # SIGUSR1's manual stack dump (written by the final, healthy epoch)
    sigusr1_dumps = sorted(Path(flight_dir).glob("watchdog_sigusr1_*.json"))
    sigusr1_ok = False
    for p in sigusr1_dumps:
        try:
            d = json.loads(p.read_text())
            sigusr1_ok = bool(d["stacks"])
        except (KeyError, ValueError):
            pass
    # flight-recorder ring dumps carrying the typed stall event
    stall_events = 0
    for p in sorted(Path(flight_dir).glob("flight_*.json")):
        try:
            d = json.loads(p.read_text())
            stall_events += sum(
                1 for e in d.get("events", []) if e.get("kind") == "stall"
            )
        except ValueError:
            dumps_well_formed = False

    slot_epoch = next(
        (c for c in epoch_counters if c.get("epoch") == "mid_slot_loop"),
        None,
    )

    record = {
        "bench": "chaos_soak_hang_injection",
        "seed": args.seed,
        "epochs": epoch_counters,
        "slot_loop_false_hung": (
            (slot_epoch["stalls_dispatch"] or 0) - 1
            if slot_epoch else None
        ),
        "escalation_exit_code": escalate_rc,
        "sealed": sealed,
        "torn_records_dropped": torn,
        "journaled_accepts": len(entries),
        "completed": len(completed),
        "typed_failed_hung": len(hung_failed),
        "typed_failed": sum(
            1 for e in entries.values() if e.status == "failed"
        ),
        "lost": lost,
        "replay_byte_mismatches": mismatches,
        "watchdog_dumps": {
            "files": len(wd_dumps),
            "by_kind": dump_kinds,
            "detect_latencies_s": detect_latencies,
            "stacks_show_wedged_frame": stacks_show_wedge,
            "well_formed": dumps_well_formed,
        },
        "flight_stall_events": stall_events,
        "sigusr1_dump_ok": sigusr1_ok,
        "detect_slack_s": args.detect_slack_s,
        "client_attempted": len(driver.attempted),
        "client_saw_200": len(driver.completed),
    }
    print(json.dumps(record, indent=2, ensure_ascii=False))
    if args.out:
        atomic_write_json(args.out, record)
        print(f"wrote {args.out}")
    if own_dir:
        shutil.rmtree(journal_dir, ignore_errors=True)

    ok = (
        not lost
        and not mismatches
        and sealed
        and len(entries) > 0
        and dumps_well_formed
        # both stall classes actually exercised, stacks on the tape, and
        # the typed stall event in a flight dump
        and dump_kinds.get("dispatch", 0) >= 2  # one per in-process epoch
        and dump_kinds.get("lock", 0) >= 1
        and stacks_show_wedge
        and stall_events > 0
        and sigusr1_ok
        # the escalation epoch exited with the supervised watchdog code
        and escalate_rc == WATCHDOG_EXIT_CODE
        # detection bound: each stall declared within (limit + slack) —
        # the monitor interval is 0.1s, so the slack is host-scheduling
        # headroom, not a loophole
        and all(lat <= args.detect_slack_s for lat in detect_latencies)
        # slot-loop epoch: segments ran, and the ONLY dispatch stall was
        # the injected hang — a live segment must never read as HUNG
        and slot_epoch is not None
        and (slot_epoch["segments"] or 0) > 0
        and slot_epoch["stalls_dispatch"] == 1
        and (slot_epoch["recoveries"] or 0) >= 1
    )
    print("hang-soak liveness invariant:", "OK" if ok else "VIOLATED")
    return 0 if ok else 1


# -- replica-fleet soak (--fleet): worker kills behind the router ------------


def fleet_soak(args) -> int:
    """Kill engine workers behind a live router and prove the FLEET ledger
    invariant: the router journals every admitted request before dispatch,
    so a SIGKILLed worker's unfinished ACCEPTs replay onto survivors —
    0 requests lost, replays byte-identical, and the client never has to
    know. The seeded schedule reuses the single-process kill shapes:
    ``mid_load`` points SIGKILL the busiest worker; the first ``mid_drain``
    point becomes a rolling drain-one-restart-one wave (the deploy path,
    under the same load). Ends with a graceful SIGTERM of the ROUTER
    (exit 0: drain, worker drains, journal seal) and an offline audit of
    the router's journal against the deterministic reference outputs."""
    fleet_dir = args.journal_dir or tempfile.mkdtemp(prefix="vnsum-fleet-")
    own_dir = args.journal_dir is None
    schedule = KillSchedule(args.seed, kills=args.kills,
                            load_window_s=args.load_window_s)
    print(f"fleet kill schedule (seed={args.seed}): "
          f"{json.dumps(schedule.describe())}", flush=True)
    worker_args = (
        "--max-batch 4 --max-wait-ms 20 --drain-timeout-s 20 "
        "--trace-sample 0 "
        f"--fake-batch-overhead-ms {args.fake_batch_overhead_ms} "
        f"--fake-per-prompt-ms {args.fake_per_prompt_ms}"
    )
    port = free_port()
    router = RouterProcess(
        port, fleet_dir=fleet_dir, spawn_workers=args.fleet_workers,
        extra_args=["--probe-interval-ms", "100",
                    "--worker-args", worker_args],
    )
    driver = LoadDriver(port, args.clients, args.per_client)
    kills: list[str] = []
    rolling_waves = 0
    polled = 0
    health: dict = {}

    def fleet_health() -> dict:
        _, payload = http_json("GET", "127.0.0.1", port, "/healthz",
                               timeout=10)
        return payload or {}

    try:
        router.start()
        router.wait_ready(timeout_s=90)
        driver.start()

        for n, point in enumerate(schedule.points, start=1):
            t_point = time.monotonic() + point.delay_s
            while time.monotonic() < t_point:
                time.sleep(0.05)
            if point.kind == "mid_drain":
                # the deploy path under load: drain-one-restart-one
                print(f"[wave {n}] rolling restart under load", flush=True)
                http_json("POST", "127.0.0.1", port,
                          "/admin/rolling-restart", {}, timeout=10)
                rolling_waves += 1
                continue
            live = [w for w in fleet_health().get("workers", [])
                    if w.get("pid") and w.get("up")]
            if not live:
                time.sleep(0.2)
                live = [w for w in fleet_health().get("workers", [])
                        if w.get("pid") and w.get("up")]
            if not live:
                print(f"[kill {n}] skipped: no live worker", flush=True)
                continue
            victim = max(live, key=lambda w: w["inflight"])
            print(f"[kill {n}] SIGKILL {victim['name']} "
                  f"(pid {victim['pid']}, inflight {victim['inflight']}) "
                  "mid-load", flush=True)
            router.kill_worker(victim["name"])
            kills.append(victim["name"])

        # quiesce: load done, rolling wave finished, router ledger drained
        t_end = time.monotonic() + args.quiesce_timeout_s
        while time.monotonic() < t_end:
            pending = scrape_metric(port, "vnsum_serve_journal_pending")
            health = fleet_health()
            if driver.done and pending == 0 and not health.get("rolling"):
                break
            time.sleep(0.2)
        driver.stop()
        health = fleet_health()
        pending = scrape_metric(port, "vnsum_serve_journal_pending")
        if pending != 0:
            print(f"FAIL: router ledger never quiesced (pending={pending})")
            return 1

        # the reconnect surface survives worker deaths: ids a client saw
        # complete poll back terminal off the ROUTER's global ledger
        for rid in list(driver.completed)[:10]:
            status, body = http_json(
                "GET", "127.0.0.1", port, f"/v1/requests/{rid}", timeout=10,
            )
            if status != 200 or body["status"] != "completed":
                print(f"FAIL: poll {rid}: {status} {body}")
                return 1
            polled += 1

        # operator incident: SIGUSR1 to the quiesced router fans out
        # POST /debug/dump to every (respawned) worker — the deterministic
        # bundle the offline validator audits below, on top of whatever
        # failover/markdown incidents the kills themselves minted
        if hasattr(signal, "SIGUSR1"):
            os.kill(router.proc.pid, signal.SIGUSR1)
            t_inc = time.monotonic() + 15.0
            incidents_root = Path(fleet_dir) / "incidents"
            while time.monotonic() < t_inc:
                manifests = list(incidents_root.glob("inc_*/manifest.json"))
                if any(json.loads(m.read_text()).get("reason") == "operator"
                       for m in manifests):
                    break
                time.sleep(0.2)

        # graceful exit: SIGTERM drains the front door, drains every
        # worker (exit 0 each), seals the router journal, exits 0
        router.sigterm()
        rc = router.wait_exit(timeout_s=60)
        if rc != 0:
            print(f"FAIL: graceful router SIGTERM exited {rc}, not 0")
            return 1
    finally:
        if router.alive:
            router.sigkill()
        driver.stop(timeout_s=5)

    # -- offline audit of the ROUTER journal (read-only) -------------------
    entries, sealed, torn = RequestJournal.read_state(
        Path(fleet_dir) / "router"
    )
    lost = [e.rid for e in entries.values() if not e.terminal]
    completed = [e for e in entries.values() if e.status == "complete"]
    failed = [e for e in entries.values() if e.status == "failed"]
    mismatches = [e.rid for e in completed
                  if e.text != reference_output(e.payload)]
    # retry-aware grouping (a shed-then-retried id journals rid, rid#1...):
    # every id a client saw 200 for must aggregate completed AND carry the
    # exact text the client received
    groups: dict[str, list] = {}
    for e in entries.values():
        groups.setdefault(e.rid.split("#")[0], []).append(e)
    client_vs_ledger = []
    for rid, text in driver.completed.items():
        group = groups.get(rid)
        if group is None:
            client_vs_ledger.append(rid)
            continue
        if aggregate_status(group) != "completed" or not any(
            e.status == "complete" and e.text == text for e in group
        ):
            client_vs_ledger.append(rid)

    # -- offline audit of the INCIDENT bundles (read-only) -----------------
    # the correlated-capture invariant: at least one bundle is well-formed
    # (manifest + router ring + >= 2 worker contributions under ONE
    # incident id) and folds into a monotone timeline — the exact artifact
    # an operator would open first after this soak's kills
    from vnsum_tpu.serve.federation import fold_incident_bundle
    from incident_report import render_text

    incident_best: dict | None = None
    incident_bundles = 0
    for manifest_path in sorted(
        (Path(fleet_dir) / "incidents").glob("inc_*/manifest.json")
    ):
        incident_bundles += 1
        bundle = manifest_path.parent
        try:
            report = fold_incident_bundle(bundle)
        except (OSError, ValueError, KeyError) as e:
            print(f"incident bundle {bundle.name}: unreadable ({e})")
            continue
        walls = [e["wall"] for e in report["events"]]
        worker_sources = [s for s in report["sources"] if s != "router"]
        well_formed = (
            report["incident"] == bundle.name
            and report["reason"] in ("slo_fast_burn", "markdown",
                                     "failover", "operator")
            and "router" in report["sources"]
            and len(worker_sources) >= 2
            and report["sources"]["router"]["events"] > 0
            and walls == sorted(walls)
            and bool(walls)
        )
        if well_formed and (
            incident_best is None
            or len(report["events"]) > incident_best["events"]
        ):
            incident_best = {
                "id": report["incident"],
                "reason": report["reason"],
                "sources": {s: i["events"]
                            for s, i in report["sources"].items()},
                "events": len(report["events"]),
                "timeline_monotone": True,
            }
            # the report CLI consumes the same fold — smoke its rendering
            render_text(report, limit=5)

    workers_tbl = health.get("workers", [])
    failovers = sum(w.get("failovers", 0) for w in workers_tbl)
    restarts = sum(w.get("restarts", 0) for w in workers_tbl)
    record = {
        "bench": "chaos_soak_fleet_worker_kill",
        "seed": args.seed,
        "workers": args.fleet_workers,
        "schedule": schedule.describe(),
        "worker_kills": kills,
        "rolling_waves": rolling_waves,
        "worker_failovers": failovers,
        "worker_restarts": restarts,
        "sealed": sealed,
        "torn_records_dropped": torn,
        "journaled_accepts": len(entries),
        "completed": len(completed),
        "typed_failed": len(failed),
        "lost": lost,
        "replay_byte_mismatches": mismatches,
        "client_vs_ledger_mismatches": client_vs_ledger,
        "client_attempted": len(driver.attempted),
        "client_saw_200": len(driver.completed),
        "polled_after_kills": polled,
        "router_sheds": health.get("sheds", {}),
        "incident_bundles": incident_bundles,
        "incident_validated": incident_best,
        "router_incident_counts": health.get("incidents", {}),
    }
    print(json.dumps(record, indent=2, ensure_ascii=False))
    if args.out:
        atomic_write_json(args.out, record)
        print(f"wrote {args.out}")
    if own_dir:
        shutil.rmtree(fleet_dir, ignore_errors=True)

    ok = (
        not lost
        and not mismatches
        and not client_vs_ledger
        and sealed
        and len(entries) > 0
        # the soak must actually exercise the failover machinery: at least
        # one kill landed and at least one journaled request replayed (or
        # retried inline) onto a survivor
        and bool(kills)
        and failovers + restarts > 0
        # correlated incident capture: at least one well-formed bundle —
        # router ring + >= 2 worker contributions under one incident id,
        # folded into a monotone timeline
        and incident_best is not None
    )
    print("fleet ledger invariant:", "OK" if ok else "VIOLATED")
    print(f"kills={len(kills)} rolling_waves={rolling_waves} "
          f"failovers={failovers} restarts={restarts} "
          f"incident_bundles={incident_bundles} "
          f"incident_validated={incident_best['id'] if incident_best else None}")
    return 0 if ok else 1


# -- structured-jobs soak (--gang): SIGKILL mid-map-fan-out ------------------


def make_doc(cid: int, i: int) -> str:
    """A deterministic multi-chunk document: long enough that the mapreduce
    splitter (chunk_size 12000 whitespace tokens) fans it out into several
    map children plus a reduce — the gang shape the kills must land inside
    of. Sizes vary per (cid, i) so fan-out widths differ across the run."""
    nwords = 12600 + 700 * ((cid + i) % 3)
    body = " ".join(_WORDS[(cid + i + k) % len(_WORDS)] for k in range(nwords))
    return f"Tài liệu dài {cid}-{i}.\n\n{body}"


def reference_summary(doc: str) -> str:
    """The offline-barrier oracle for a whole structured job: the BLOCKING
    MapReduceStrategy over a latency-free fake backend, with the server's
    exact approach defaults. The serving path streams the same rounds
    through the gang machinery — across kills and replays the final
    summary a client sees must byte-match this."""
    from vnsum_tpu.core.config import PipelineConfig, approach_defaults
    from vnsum_tpu.strategies import get_strategy

    cfg = PipelineConfig(approach="mapreduce",
                         **approach_defaults("mapreduce"))
    strat = get_strategy("mapreduce", FakeBackend(), cfg)
    return strat.summarize_batch([doc])[0].summary


class GangLoadDriver:
    """Closed-loop summarize clients: each POST fans out server-side into a
    gang of map children plus a reduce, all journaled under one trace id.
    Robust to the server dying mid-fan-out — a client that never saw the
    200 re-POSTs the same document under the same request_id, which rejoins
    the (replay-restored) gang rather than forking a new one."""

    def __init__(self, port: int, clients: int, per_client: int) -> None:
        self.port = port
        self.clients = clients
        self.per_client = per_client
        # docs are big (~13k words); build the deterministic stream once
        self.docs = {
            f"gang-{cid}-{i}": make_doc(cid, i)
            for cid in range(clients) for i in range(per_client)
        }
        self.attempted: dict[str, str] = {}  # rid -> doc
        self.completed: dict[str, str] = {}  # rid -> summary (HTTP 200 seen)
        self.partials: set[str] = set()
        self._lock = threading.Lock()
        self._cursor = [0] * clients
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()

    def _client(self, cid: int) -> None:
        while not self._stop.is_set():
            i = self._cursor[cid]
            if i >= self.per_client:
                return
            rid = f"gang-{cid}-{i}"
            doc = self.docs[rid]
            with self._lock:
                self.attempted[rid] = doc
            try:
                status, body = http_json(
                    "POST", "127.0.0.1", self.port, "/v1/summarize",
                    {"text": doc, "approach": "mapreduce",
                     "request_id": rid},
                    timeout=60.0,
                )
                if status == 200 and body and body.get("summary"):
                    with self._lock:
                        self.completed[rid] = body["summary"]
                        if body.get("partial"):
                            self.partials.add(rid)
                    self._cursor[cid] = i + 1
                elif status in (400, 404):
                    self._cursor[cid] = i + 1  # don't spin on a client bug
                else:
                    time.sleep(0.05)  # shed/error: back off, retry same i
            except OSError:
                time.sleep(0.1)  # server is down/being killed: wait it out

    def start(self) -> None:
        self._threads = [
            threading.Thread(target=self._client, args=(cid,), daemon=True)
            for cid in range(self.clients)
        ]
        for t in self._threads:
            t.start()

    @property
    def done(self) -> bool:
        return all(c >= self.per_client for c in self._cursor)

    def stop(self, timeout_s: float = 30.0) -> None:
        self._stop.set()
        t_end = time.monotonic() + timeout_s
        for t in self._threads:
            t.join(timeout=max(t_end - time.monotonic(), 0.1))


def gang_soak(args) -> int:
    """Structured-jobs chaos epoch: SIGKILL the server while gangs of
    fanned-out map/reduce children are mid-flight, restart on the same
    journal, and audit that every admitted gang folds to a TERMINAL parent
    aggregate with byte-identical replays and no stranded cache pins.

    Beyond the base ledger invariant this asserts, per gang:

    - a typed GANG record exists and every recorded member is journaled
      and terminal (membership never outlives the ledger);
    - the parent aggregate (``rid`` plus its ``#N`` children folded by
      ``aggregate_status``) is terminal for EVERY admitted gang — completed,
      partial, failed, or cancelled, never stuck mid-lifecycle;
    - every summary a client saw (HTTP 200) byte-matches the OFFLINE
      blocking MapReduceStrategy over the same document — the streaming
      reduce plus kills plus replay changed nothing observable;
    - after quiesce ``vnsum_serve_cache_pinned_blocks`` reads 0: dead
      gangs released every prefix-cache pin their fan-out took."""
    journal_dir = args.journal_dir or tempfile.mkdtemp(prefix="vnsum-gangs-")
    own_dir = args.journal_dir is None
    schedule = KillSchedule(args.seed, kills=args.kills,
                            load_window_s=args.load_window_s, qos=False)
    print(f"gang kill schedule (seed={args.seed}): "
          f"{json.dumps(schedule.describe())}", flush=True)

    server_args = [
        "--max-batch", "4",
        "--max-wait-ms", "20",
        "--drain-timeout-s", "20",
        "--trace-sample", "0",
        "--fake-batch-overhead-ms", str(args.fake_batch_overhead_ms),
        "--fake-per-prompt-ms", str(args.fake_per_prompt_ms),
    ]
    port = free_port()
    driver = GangLoadDriver(port, args.clients, args.per_client)
    restarts = 0
    srv = None
    pinned = None
    gang_admitted_final = None
    try:
        srv = ServerProcess(port, journal_dir=journal_dir,
                            extra_args=server_args)
        srv.start()
        srv.wait_healthy()
        driver.start()

        for n, point in enumerate(schedule.points, start=1):
            t_kill = time.monotonic() + point.delay_s
            while time.monotonic() < t_kill:
                time.sleep(0.05)
            if point.kind == "mid_drain":
                print(f"[kill {n}] SIGTERM, then SIGKILL "
                      f"{point.drain_gap_s}s into the drain", flush=True)
                srv.sigterm()
                time.sleep(point.drain_gap_s)
                srv.sigkill()
            else:
                print(f"[kill {n}] {point.kind}: SIGKILL after "
                      f"{point.delay_s}s of load", flush=True)
                srv.sigkill()
            restarts += 1
            srv = ServerProcess(port, journal_dir=journal_dir,
                                extra_args=server_args)
            srv.start()
            srv.wait_healthy()

        # let the surviving load finish, then wait for the ledger to
        # quiesce — replayed gang children resolve through the same path
        t_end = time.monotonic() + args.quiesce_timeout_s
        while time.monotonic() < t_end:
            pending = scrape_metric(port, "vnsum_serve_journal_pending")
            if driver.done and pending == 0:
                break
            time.sleep(0.2)
        driver.stop()
        pending = scrape_metric(port, "vnsum_serve_journal_pending")
        if pending != 0:
            print(f"FAIL: journal never quiesced (pending={pending})")
            return 1
        # stranded-pin probe: with everything terminal, the prefix cache
        # must hold zero pinned blocks — a gang that died mid-fan-out and
        # left its template-header pins behind shows up RIGHT HERE
        pinned = scrape_metric(port, "vnsum_serve_cache_pinned_blocks")
        gang_admitted_final = scrape_metric(
            port, "vnsum_serve_gang_admitted_total"
        )

        # reconnect surface: completed parents must poll back terminal
        # WITH their per-phase gang progress attached
        polled = 0
        for rid in list(driver.completed)[:6]:
            status, body = http_json(
                "GET", "127.0.0.1", port, f"/v1/requests/{rid}", timeout=10,
            )
            assert status == 200 and body["status"] in (
                "completed", "partial"
            ), f"poll {rid}: {status} {body}"
            gang = body.get("gang")
            assert gang and "map" in gang.get("phases", {}), \
                f"poll {rid}: no gang phase progress in {body}"
            polled += 1

        srv.sigterm()
        rc = srv.wait_exit(timeout_s=30)
        if rc != 0:
            print(f"FAIL: graceful SIGTERM shutdown exited {rc}, not 0")
            return 1
        srv = None
    finally:
        if srv is not None and srv.alive:
            srv.sigkill()
        driver.stop(timeout_s=5)

    # -- offline ledger + gang audit (read-only) ---------------------------
    entries, sealed, torn = RequestJournal.read_state(journal_dir)
    lost = [e.rid for e in entries.values() if not e.terminal]
    completed = [e for e in entries.values() if e.status == "complete"]
    failed = [e for e in entries.values() if e.status == "failed"]
    mismatches = [e.rid for e in completed
                  if e.text != reference_output(e.payload)]

    # parent aggregates: fold each trace's children; every admitted gang
    # must land on a terminal fold, whatever the kills did to it
    groups: dict[str, list] = {}
    for e in entries.values():
        groups.setdefault(e.rid.split("#")[0], []).append(e)
    terminal = {"completed", "partial", "failed", "cancelled"}
    parent_status = {base: aggregate_status(g) for base, g in groups.items()}
    stuck_parents = sorted(
        b for b, s in parent_status.items() if s not in terminal
    )

    # gang membership: every member a GANG record names must be journaled
    # and terminal, and every parent trace must carry a GANG record
    gangs = RequestJournal.read_gangs(journal_dir)
    member_gaps = sorted(
        rid
        for g in gangs.values()
        for rid in g["members"]
        if rid not in entries or not entries[rid].terminal
    )
    unrecorded_parents = sorted(b for b in groups if b not in gangs)

    # end-to-end byte identity: streaming + kills + replay vs the offline
    # blocking strategy, per document a client actually saw complete
    summary_mismatches = [
        rid for rid, text in driver.completed.items()
        if text != reference_summary(driver.docs[rid])
    ]

    record = {
        "bench": "chaos_soak_gang_kill",
        "seed": args.seed,
        "schedule": schedule.describe(),
        "restarts": restarts,
        "sealed": sealed,
        "torn_records_dropped": torn,
        "journaled_accepts": len(entries),
        "completed": len(completed),
        "typed_failed": len(failed),
        "lost": lost,
        "replay_byte_mismatches": mismatches,
        "gangs_recorded": len(gangs),
        "gang_members_recorded": sum(len(g["members"])
                                     for g in gangs.values()),
        "gang_admitted_final_epoch": gang_admitted_final,
        "parent_aggregates": {
            s: sum(1 for v in parent_status.values() if v == s)
            for s in sorted(set(parent_status.values()))
        },
        "stuck_parents": stuck_parents,
        "gang_member_gaps": member_gaps,
        "unrecorded_parents": unrecorded_parents,
        "summary_byte_mismatches": summary_mismatches,
        "client_partials": sorted(driver.partials),
        "cache_pinned_blocks_after_quiesce": pinned,
        "client_attempted": len(driver.attempted),
        "client_saw_200": len(driver.completed),
        "polled_after_restart": polled,
    }
    print(json.dumps(record, indent=2, ensure_ascii=False))
    if args.out:
        atomic_write_json(args.out, record)
        print(f"wrote {args.out}")
    if own_dir:
        shutil.rmtree(journal_dir, ignore_errors=True)

    ok = (
        not lost
        and not mismatches
        and not summary_mismatches
        and not stuck_parents
        and not member_gaps
        and not unrecorded_parents
        and sealed
        and len(entries) > 0
        and len(gangs) > 0
        and pinned == 0
    )
    print("gang ledger invariant:", "OK" if ok else "VIOLATED")
    print(f"gangs={len(gangs)} parents={len(groups)} "
          f"children={len(entries)} pinned_after={pinned}")
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--kills", type=int, default=3)
    p.add_argument("--clients", type=int, default=6)
    p.add_argument("--per-client", type=int, default=8)
    p.add_argument("--journal-dir", default=None,
                   help="journal directory (default: fresh temp dir)")
    p.add_argument("--load-window-s", type=float, default=1.5,
                   help="how long load runs before each seeded kill")
    p.add_argument("--quiesce-timeout-s", type=float, default=60.0)
    p.add_argument("--fake-batch-overhead-ms", type=float, default=80.0)
    p.add_argument("--fake-per-prompt-ms", type=float, default=4.0)
    p.add_argument("--qos", action="store_true",
                   help="multi-tenant QoS soak: in-flight serving with an "
                        "interactive + preemptible-batch tenant mix, a "
                        "widened eviction->PREEMPTED-journal gap "
                        "(VNSUM_CHAOS_PREEMPT_GAP_MS), and a mid_preempt "
                        "kill point — the ledger audit then also proves "
                        "preempted requests reach exactly one terminal "
                        "state after restart replay")
    p.add_argument("--preempt-gap-ms", type=float, default=120.0,
                   help="qos mode: how long the server sleeps between slot "
                        "eviction and the PREEMPTED journal append (the "
                        "window kills must be able to land in)")
    p.add_argument("--churn", action="store_true",
                   help="client-churn soak: no process kills — seeded "
                        "client cancels (DELETE) and stream disconnects "
                        "land mid-queue, mid-stream, mid-slot, and "
                        "mid-preemption against an in-flight two-tier "
                        "server; the audit asserts zero leaked slots, pin "
                        "counts back to baseline, every ACCEPT terminal "
                        "(CANCELLED included), and survivor outputs "
                        "byte-identical")
    p.add_argument("--stream-idle-timeout-s", type=float, default=0.4,
                   help="churn mode: the server's bounded resume window "
                        "(abandoned streams cancel after this)")
    p.add_argument("--hang", action="store_true",
                   help="hang-injection soak (serve/watchdog.py): seeded "
                        "forever-hangs mid-dispatch (one-shot), "
                        "mid-slot-loop (in-flight), and mid-fsync (inside "
                        "the journal lock). Proves liveness end to end: "
                        "hung riders fail typed / residents requeue, the "
                        "lock wedge escalates to a supervised "
                        "seal-and-exit + restart replay, every ACCEPT "
                        "reaches a terminal state, each stall is detected "
                        "within its bound, and stack dumps land on disk")
    p.add_argument("--detect-slack-s", type=float, default=3.0,
                   help="hang mode: allowed detection latency beyond the "
                        "configured budget/deadline (monitor runs at 10Hz; "
                        "this is host-scheduling headroom)")
    p.add_argument("--fleet", action="store_true",
                   help="replica-fleet mode: run a front-door router over "
                        "N spawned engine workers, SIGKILL workers at the "
                        "seeded points (plus one rolling-restart wave), "
                        "and audit the ROUTER's global journal")
    p.add_argument("--fleet-workers", type=int, default=3,
                   help="engine workers behind the router in --fleet mode")
    p.add_argument("--gang", action="store_true",
                   help="structured-jobs mode: drive /v1/summarize fan-outs "
                        "(gangs of map children plus a streaming reduce), "
                        "SIGKILL mid-fan-out, and audit that every admitted "
                        "gang folds to a terminal parent aggregate with "
                        "byte-identical replays and zero stranded cache pins")
    p.add_argument("--out", default=None,
                   help="optional JSON artifact for the run record")
    args = p.parse_args(argv)

    if args.churn:
        return churn_soak(args)
    if args.hang:
        return hang_soak(args)
    if args.fleet:
        return fleet_soak(args)
    if args.gang:
        return gang_soak(args)

    journal_dir = args.journal_dir or tempfile.mkdtemp(prefix="vnsum-chaos-")
    own_dir = args.journal_dir is None
    schedule = KillSchedule(args.seed, kills=args.kills,
                            load_window_s=args.load_window_s, qos=args.qos)
    print(f"kill schedule (seed={args.seed}): "
          f"{json.dumps(schedule.describe())}", flush=True)

    server_args = [
        "--max-batch", "4",
        "--max-wait-ms", "20",
        "--drain-timeout-s", "20",
        "--trace-sample", "0",
        "--fake-batch-overhead-ms", str(args.fake_batch_overhead_ms),
        "--fake-per-prompt-ms", str(args.fake_per_prompt_ms),
    ]
    server_env = None
    flight_dir = None
    if args.qos:
        # in-flight + two tiers + real per-segment latency, so kills and
        # preemptions land mid-decode rather than between instant segments
        server_args += [
            "--inflight", "--slots", "4",
            "--tenants", "interactive:4:0,batch:1:0:batch",
            "--fake-segment-overhead-ms", "30",
        ]
        # flight recorder: every process epoch dumps its typed-event ring
        # on graceful drain (SIGKILLed epochs leave nothing — that is the
        # point of the ring being in-memory); the final SIGTERM's drain
        # dump is the one the audit below holds to account
        flight_dir = str(Path(journal_dir) / "flight")
        server_args += ["--flight-dir", flight_dir]
        server_env = {
            "VNSUM_CHAOS_PREEMPT_GAP_MS": str(args.preempt_gap_ms),
        }
    port = free_port()
    driver = LoadDriver(port, args.clients, args.per_client, qos=args.qos)
    restarts = 0
    # preemption evidence: the counter resets per process, so sample its
    # high-water mark within each process epoch and sum across restarts
    preempts_observed = 0
    epoch_high = 0
    final_epoch_preempts = 0

    def sample_preempts() -> None:
        nonlocal epoch_high
        n = scrape_metric(port, "vnsum_serve_qos_preemptions_total")
        if n is not None:
            epoch_high = max(epoch_high, n)

    srv = None
    try:
        srv = ServerProcess(port, journal_dir=journal_dir,
                            extra_args=server_args, env=server_env)
        srv.start()
        srv.wait_healthy()
        driver.start()

        for n, point in enumerate(schedule.points, start=1):
            t_kill = time.monotonic() + point.delay_s
            while time.monotonic() < t_kill:
                time.sleep(0.05)
                if args.qos:
                    sample_preempts()
            if point.kind == "mid_drain":
                print(f"[kill {n}] SIGTERM, then SIGKILL "
                      f"{point.drain_gap_s}s into the drain", flush=True)
                srv.sigterm()
                time.sleep(point.drain_gap_s)
                srv.sigkill()
            else:
                # mid_load and mid_preempt are both SIGKILL-under-load; in
                # qos mode every preemption holds the widened gap open, so
                # a mid_preempt draw has a real window to land in
                print(f"[kill {n}] {point.kind}: SIGKILL after "
                      f"{point.delay_s}s of load", flush=True)
                srv.sigkill()
            restarts += 1
            preempts_observed += epoch_high
            epoch_high = 0
            srv = ServerProcess(port, journal_dir=journal_dir,
                                extra_args=server_args, env=server_env)
            srv.start()
            srv.wait_healthy()

        # let the remaining load finish, then wait for the ledger to
        # quiesce: pending == 0 means every replayed ACCEPT resolved
        t_end = time.monotonic() + args.quiesce_timeout_s
        while time.monotonic() < t_end:
            pending = scrape_metric(port, "vnsum_serve_journal_pending")
            if args.qos:
                sample_preempts()
            if driver.done and pending == 0:
                break
            time.sleep(0.2)
        driver.stop()
        final_epoch_preempts = epoch_high
        preempts_observed += epoch_high
        pending = scrape_metric(port, "vnsum_serve_journal_pending")
        if pending != 0:
            print(f"FAIL: journal never quiesced (pending={pending})")
            return 1
        # how much crash recovery this run actually exercised (final
        # process only — each restart's replays are its own counter)
        last_replayed = scrape_metric(
            port, "vnsum_serve_journal_replayed_total"
        )

        # reconnect surface: every id a client SAW complete must poll back
        # terminal (spot-check a handful to keep the smoke fast)
        polled = 0
        for rid in list(driver.completed)[:10]:
            status, body = http_json(
                "GET", "127.0.0.1", port, f"/v1/requests/{rid}", timeout=10,
            )
            # the client SAW a 200 for this id, so the poll surface must
            # say completed — even when a replayed duplicate of the same
            # payload failed typed (the retry-aware aggregation)
            assert status == 200 and body["status"] == "completed", \
                f"poll {rid}: {status} {body}"
            polled += 1

        # graceful exit: SIGTERM drains, seals, exits 0 (the satellite)
        srv.sigterm()
        rc = srv.wait_exit(timeout_s=30)
        if rc != 0:
            print(f"FAIL: graceful SIGTERM shutdown exited {rc}, not 0")
            return 1
        srv = None
    finally:
        if srv is not None and srv.alive:
            srv.sigkill()
        driver.stop(timeout_s=5)

    # -- offline ledger audit (read-only: no compaction, no appends) -------
    entries, sealed, torn = RequestJournal.read_state(journal_dir)
    lost = [e.rid for e in entries.values() if not e.terminal]
    completed = [e for e in entries.values() if e.status == "complete"]
    failed = [e for e in entries.values() if e.status == "failed"]
    mismatches = []
    for e in completed:
        if e.text != reference_output(e.payload):
            mismatches.append(e.rid)
    # every text a CLIENT saw (HTTP 200) must match the ledger's COMPLETE
    client_vs_ledger = []
    by_rid = {e.rid: e for e in entries.values()}
    for rid, text in driver.completed.items():
        e = by_rid.get(rid)
        if e is not None and e.status == "complete" and e.text != text:
            client_vs_ledger.append(rid)

    # flight-recorder audit (qos mode): the final graceful SIGTERM dumped
    # the drain ring — assert a WELL-FORMED dump exists (reason + typed
    # events with monotone seqs and the serving lifecycle in them), and
    # that the preemption lifecycle is on the tape whenever the final
    # process epoch actually preempted (earlier epochs die by SIGKILL —
    # their in-memory rings are exactly what a black box cannot keep)
    flight_ok = True
    flight_summary: dict = {}
    if args.qos:
        dump_paths = sorted(Path(flight_dir).glob("flight_*.json"))
        events: list[dict] = []
        well_formed = bool(dump_paths)
        for p in dump_paths:
            try:
                d = json.loads(p.read_text())
                # explicit raises, not asserts: the audit must survive -O
                if not (d["reason"] and isinstance(d["events"], list)):
                    raise ValueError("missing reason / events list")
                seqs = [e["seq"] for e in d["events"]]
                if seqs != sorted(seqs):
                    raise ValueError("event seqs not monotone")
                if not all("kind" in e and "t_rel" in e
                           for e in d["events"]):
                    raise ValueError("untyped event on the tape")
                events.extend(d["events"])
            # lint-allow[swallowed-exception]: a malformed dump fails the audit via flight_ok below — recording the verdict IS the handling
            except (KeyError, ValueError):
                well_formed = False
        kinds = {e["kind"] for e in events}
        preempt_events = sum(1 for e in events if e["kind"] == "preempt")
        flight_ok = (
            well_formed
            and {"admit", "dispatch"} <= kinds
            and (final_epoch_preempts == 0 or preempt_events > 0)
        )
        flight_summary = {
            "dumps": len(dump_paths),
            "events": len(events),
            "event_kinds": sorted(kinds),
            "preempt_events": preempt_events,
            "final_epoch_preemptions": final_epoch_preempts,
            "well_formed": well_formed,
        }

    record = {
        "bench": "chaos_soak_process_kill",
        "seed": args.seed,
        "qos": args.qos,
        "flight_recorder": flight_summary,
        "preemptions_observed": preempts_observed,
        "schedule": schedule.describe(),
        "restarts": restarts,
        "last_restart_replayed": last_replayed,
        "sealed": sealed,
        "torn_records_dropped": torn,
        "journaled_accepts": len(entries),
        "completed": len(completed),
        "typed_failed": len(failed),
        "lost": lost,
        "replay_byte_mismatches": mismatches,
        "client_vs_ledger_mismatches": client_vs_ledger,
        "client_attempted": len(driver.attempted),
        "client_saw_200": len(driver.completed),
        "polled_after_restart": polled,
    }
    print(json.dumps(record, indent=2, ensure_ascii=False))
    if args.out:
        atomic_write_json(args.out, record)
        print(f"wrote {args.out}")
    if own_dir:
        shutil.rmtree(journal_dir, ignore_errors=True)

    ok = (
        not lost
        and not mismatches
        and not client_vs_ledger
        and sealed
        and len(entries) > 0
        # qos mode must actually exercise the preemption path: a soak
        # that never preempted proved nothing about the mid-preempt
        # kill window
        and (not args.qos or preempts_observed > 0)
        # ...and must leave a well-formed flight-recorder dump behind
        and flight_ok
    )
    print("ledger invariant:", "OK" if ok else "VIOLATED")
    if args.qos:
        print(f"preemptions observed across processes: {preempts_observed}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
