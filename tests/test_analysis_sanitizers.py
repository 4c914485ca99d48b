"""Runtime-sanitizer acceptance over the REAL serving stack:

- lock-order detector green (and actually watching) under concurrent
  scheduler traffic and under cache-eviction churn — the two paths ISSUE 7
  names as deadlock suspects;
- transfer-guard mode green over a hermetic TpuBackend one-shot
  prefill/decode run, with byte-identical outputs;
- the disabled-mode no-op guarantee: with sanitizers off the serve/cache
  locks are plain ``threading.Lock`` objects — no wrapper, zero extra
  acquisitions on the scheduler hot path — so serving goodput is
  untouched by this machinery existing.

CPU caveat (documented in analysis/sanitizers.py): device<->host on CPU JAX
is zero-copy, so the transfer guard cannot fire there — these tests verify
the guarded path stays green and the real jax context is installed; the
blocking behavior itself is asserted only on accelerator backends.
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np
import pytest

pytest.importorskip("jax")

from vnsum_tpu.analysis import sanitizers
from vnsum_tpu.backend.fake import FakeBackend
from vnsum_tpu.serve.metrics import ServeMetrics
from vnsum_tpu.serve.queue import RequestQueue
from vnsum_tpu.serve.scheduler import MicroBatchScheduler


@pytest.fixture
def lock_sanitizer(monkeypatch):
    monkeypatch.setenv("VNSUM_SANITIZERS", "lock")
    sanitizers.lock_graph().reset()
    yield
    sanitizers.lock_graph().reset()


# -- lock order under the real concurrent paths ------------------------------


def test_lock_order_green_under_concurrent_scheduler(lock_sanitizer):
    """The PR 1 coalescing path with every lock tracked: queue cond,
    metrics, obs hub/trace — concurrent submits must complete with zero
    wait-for cycles, and the graph must prove it was actually watching."""
    from vnsum_tpu.obs import ObsHub

    sched = MicroBatchScheduler(
        FakeBackend(), max_batch=8, max_wait_s=0.05, obs=ObsHub(sample=1.0),
    )
    try:
        assert isinstance(sched.queue._lock, sanitizers.TrackedLock)
        barrier = threading.Barrier(6)
        errors = []

        def worker(i):
            barrier.wait()
            try:
                sched.submit(f"tai lieu {i} " * 10).result(timeout=30)
            except Exception as e:  # noqa: BLE001 - assertion target
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sched.close()
    assert not errors
    assert sanitizers.lock_order_violations() == []
    # the detector saw the queue-lock -> metrics-lock nesting (on_admit
    # runs under the queue cond) — the graph is populated, not idle
    edges = sanitizers.lock_graph().edges()
    assert "serve.metrics" in edges.get("serve.queue", set())


def test_lock_order_green_under_cache_eviction_traffic(lock_sanitizer):
    """PR 4's eviction-under-traffic path — ISSUE 7's prime deadlock
    suspect: a tight radix pool churning evictions on the scheduler thread
    while submit-side threads probe it for admission billing. Must stay
    cycle-free with the radix lock in the tracked graph."""
    fb = FakeBackend(prefix_cache_blocks=6, cache_block_tokens=2)
    oracle = FakeBackend()
    sched = MicroBatchScheduler(
        fb, max_batch=4, max_wait_s=0.002,
        # a token budget forces cached_prefix_tokens probes (radix lock)
        # from the submitting threads, concurrent with engine-side inserts
        max_queued_tokens=100_000,
    )
    headers = [f"tieu de so {h} lap lai nhieu lan " for h in range(3)]
    errors = []

    def client(tid):
        try:
            for i in range(10):
                h = headers[(tid + i) % len(headers)]
                prompt = h * 2 + f"phan rieng {tid} {i} con lai"
                got = sched.submit(prompt, cache_hint=h * 2).result(timeout=15)
                want = oracle.generate([prompt])[0]
                if got.text != want:
                    errors.append((prompt, got.text, want))
        except Exception as e:  # pragma: no cover - assertion target
            errors.append(e)

    threads = [threading.Thread(target=client, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sched.close()
    assert not errors
    assert sanitizers.lock_order_violations() == []
    assert fb.prefix_cache_stats()["evictions"] > 0  # churn really happened
    assert isinstance(
        fb.prefix_index._lock, sanitizers.TrackedLock
    )  # the radix lock was in the tracked graph, not a bystander


# -- transfer guard over a hermetic engine run -------------------------------


@pytest.fixture(scope="module")
def tiny():
    from vnsum_tpu.models import jitted_init
    from vnsum_tpu.models.llama import init_params, tiny_llama

    cfg = tiny_llama(max_seq_len=256)
    return cfg, jitted_init(init_params, cfg, 0)


def test_transfer_guard_green_over_engine_decode_prefill(tiny, monkeypatch):
    """Acceptance: sanitizer transfer mode passes over a hermetic one-shot
    prefill/decode run, byte-identical to unsanitized — every hot-loop
    sync is an explicit (lint-acknowledged) device_get."""
    from vnsum_tpu.backend.engine import TpuBackend

    cfg, params = tiny
    prompts = [f"van ban nguon so {i} can tom tat ngay" for i in range(3)]

    monkeypatch.delenv("VNSUM_SANITIZERS", raising=False)
    base = TpuBackend(model_config=cfg, params=params, batch_size=4,
                      max_new_tokens=8, flash=False)
    want = base.generate(prompts)

    monkeypatch.setenv("VNSUM_SANITIZERS", "transfer")
    one_shot = TpuBackend(model_config=cfg, params=params, batch_size=4,
                          max_new_tokens=8, flash=False)
    assert one_shot.generate(prompts) == want


def test_transfer_guard_context_selection(monkeypatch):
    monkeypatch.delenv("VNSUM_SANITIZERS", raising=False)
    assert isinstance(
        sanitizers.hot_path_transfer_guard(), contextlib.nullcontext
    )
    monkeypatch.setenv("VNSUM_SANITIZERS", "transfer")
    assert not isinstance(
        sanitizers.hot_path_transfer_guard(), contextlib.nullcontext
    )


def test_transfer_guard_explicit_fetch_always_passes(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("VNSUM_SANITIZERS", "transfer")
    x = jnp.arange(4)
    with sanitizers.hot_path_transfer_guard():
        assert jax.device_get(x).tolist() == [0, 1, 2, 3]
        try:
            np.asarray(x)
            implicit_blocked = False
        except Exception:  # noqa: BLE001 - jax raises a backend error type
            implicit_blocked = True
    if jax.default_backend() != "cpu":
        # on accelerators the implicit d2h must error; CPU is zero-copy and
        # unguardable — the context installation is still exercised above
        assert implicit_blocked


# -- disabled mode is a true no-op (the bench guard, ISSUE 7 satellite) ------


def test_sanitizers_disabled_are_noops(monkeypatch):
    """With VNSUM_SANITIZERS unset, serve/cache locks are PLAIN
    threading.Lock objects (no wrapper exists at all — zero extra
    acquisitions on the scheduler hot path) and the wait-for graph stays
    empty across real traffic, so serving goodput is untouched."""
    from vnsum_tpu.cache.radix import RadixIndex

    monkeypatch.delenv("VNSUM_SANITIZERS", raising=False)
    sanitizers.lock_graph().reset()
    plain = type(threading.Lock())
    assert type(RequestQueue()._lock) is plain
    assert type(ServeMetrics()._lock) is plain
    assert type(RadixIndex(4, 2)._lock) is plain

    sched = MicroBatchScheduler(FakeBackend(), max_batch=4, max_wait_s=0.01)
    try:
        assert type(sched.queue._lock) is plain
        futs = [sched.submit(f"tai lieu {i} " * 8) for i in range(5)]
        for f in futs:
            f.result(timeout=30)
    finally:
        sched.close()
    assert sanitizers.lock_graph().edges() == {}
    assert sanitizers.lock_order_violations() == []
