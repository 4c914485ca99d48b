"""In-flight batching: a persistent slot-based decode loop with refill.

The serving path used to be Orca-before-Orca: the scheduler coalesced a
micro-batch, called a blocking ``backend.generate``, and every request that
arrived during that batch's decode waited out the full prefill+decode of
strangers. The engine already owned every ingredient of iteration-level
scheduling — segmented decode with a host-visible boundary, tail compaction,
chunked prefill, prefix-cache resume — and this module assembles them into
the missing loop (Orca OSDI'22; vLLM/PagedAttention arXiv:2309.06180's
continuous batching is the same lever over paged memory):

- a long-lived fixed-shape batch of B *slots* (one compiled program set per
  loop — no per-batch bucketing churn);
- per-slot state (budget ``t``, done flag, RNG uid, output cursor) is
  slot-indexed, so rows at different generation depths coexist
  (``engine._make_slot_segment_fn``'s per-row budgets);
- at every segment boundary, finished rows are harvested and freed slots
  are REFILLED from waiting prompts: joiners get chunked prefill (optionally
  resumed from the radix prefix cache) into a small join batch, then an
  adopt program scatters their cache rows into the resident stacked cache
  (``engine._make_adopt_fn``) and they decode together with residents.

Greedy per-request outputs stay byte-identical to the one-shot path (same
caveat class as compaction: identical per-row math, batch-shape tiling can
flip near-tie last bits on real hardware; CPU/interpret runs are exact).
Sampled streams key on (loop seed, per-request uid, row-local step), so a
request's randomness is independent of its slot, its join segment, and its
companions.

The loop is driven from ONE thread (the serving scheduler's contract —
engine access is single-threaded); nothing here locks.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from ..analysis.sanitizers import hot_path_transfer_guard
from ..core.logging import get_logger
from ..core.profiling import execution_span, host_span
from ..testing.faults import fault
from .base import left_pad_batch, trim_to_eos

# jax is imported lazily (TpuSlotLoop.__init__): the shared record types
# below also serve FakeBackend's hermetic slot loop, which must not pay a
# cold jax import on its first admission

logger = get_logger("vnsum.inflight")


@dataclass
class SlotAdmission:
    """One request's admission into the loop (the TTFT anchor rides here:
    ``prefill_end`` is the sync-bounded host time the joiner's own prefill
    finished — anchored at the JOINER's prefill, not a shared batch's)."""

    key: object
    slot: int
    admitted_at: float          # time.monotonic() at admit entry
    prefill_end: float          # time.monotonic() after the prefill sync
    prompt_tokens: int = 0
    cached_tokens: int = 0      # prompt tokens resumed from the prefix cache
    occupancy: int = 0          # busy slots right after this admit


@dataclass
class SlotCompletion:
    """One finished request harvested at a segment boundary."""

    key: object
    text: str
    slot: int
    gen_tokens: int = 0


@dataclass
class SegmentResult:
    """One decode segment's outcome."""

    completions: list = field(default_factory=list)
    live: int = 0               # rows live at dispatch start
    new_tokens: int = 0         # tokens retired across all rows this dispatch
    seconds: float = 0.0
    steps: int = 0              # decode steps the dispatch ran (its deepest row's)


@dataclass
class SlotEviction:
    """One request preempted out of its decode slot (serve/qos.py priority
    tiers). ``pin`` is a ``(cache, match)`` pair the loop took on the
    request's prompt prefix at eviction — the blocks stay pinned against
    LRU until the SCHEDULER releases them at the request's terminal
    resolution, so the restarted prefill resumes warm (None when no prefix
    cache is configured)."""

    key: object
    slot: int
    pin: object = None


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


class TpuSlotLoop:
    """Slot bookkeeping + program driving for TpuBackend's in-flight loop.

    Built by ``TpuBackend.start_slot_loop``; the compiled programs live in
    the backend's ``_seg_fns`` cache (``slot_prefill`` / ``slot_seg`` /
    ``adopt``), so loops over the same geometry reuse executables.
    """

    def __init__(self, backend, slots: int, S: int, max_new: int, gen,
                 seed: int) -> None:
        import jax.numpy as jnp

        self.backend = backend
        self.slots = int(slots)
        self.S = int(S)
        self.max_new = int(max_new)
        self.gen = gen
        self.seed = seed
        b = backend
        B = self.slots
        # resident device state: every slot starts FREE (all-pad, done)
        self._cache = b._init_prefill_cache(B, S + max_new)
        self._cur = jnp.zeros((B,), jnp.int32)
        self._done = jnp.ones((B,), bool)
        self._t = jnp.zeros((B,), jnp.int32)
        self._out = jnp.full((B, max_new), b.tok.pad_id, jnp.int32)
        self._pads = jnp.full((B,), S, jnp.int32)
        if b.mesh is not None:
            # pin the per-slot vectors to the cache's batch layout (rows
            # over `data`) instead of leaving them on the default device for
            # GSPMD to re-layout on every segment dispatch
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            row = NamedSharding(b.mesh, P("data"))
            self._cur = jax.device_put(self._cur, row)
            self._done = jax.device_put(self._done, row)
            self._t = jax.device_put(self._t, row)
            self._out = jax.device_put(
                self._out, NamedSharding(b.mesh, P("data", None))
            )
            self._pads = jax.device_put(self._pads, row)
        # host-side slot table: caller key per busy slot (None = free),
        # per-request RNG uid, last fetched per-row t; prompts are kept so
        # the fault-injection poison matcher sees residents at every
        # segment, symmetric with FakeSlotLoop
        self._keys: list = [None] * B
        self._prompts: list[str | None] = [None] * B
        self._uids: list[int] = [0] * B
        self._admissions: dict[int, SlotAdmission] = {}
        self._t_host = np.zeros((B,), np.int64)
        # each slot's left pad as the device holds it, for the count of the
        # key blocks a segment's steps skip; the engine keeps that count by
        # program (every loop of this shape), so the loop notes where it
        # stood and its closing line says what this loop added
        self._pads_host = np.full((B,), S, np.int64)
        self._kv_blocks_before = self._segment_kv_blocks()
        self._uid_next = 0
        self.segments = 0           # decode segments dispatched
        self.refills = 0
        # boundary out-buffer snapshot: when step(fetch_outputs=True) rode
        # the control fetch, partial_outputs serves from it instead of
        # paying a second d2h per boundary (None = no snapshot resident)
        self._out_snap = None
        self._closed = False

    # -- introspection ---------------------------------------------------

    @property
    def active(self) -> int:
        return sum(1 for k in self._keys if k is not None)

    @property
    def free(self) -> int:
        return self.slots - self.active

    # -- admission (prefill + adopt) -------------------------------------

    # hot path
    def admit(self, items) -> tuple[list[SlotAdmission], list]:
        """Admit up to the free-slot budget from ``items`` (an iterable of
        ``(key, prompt, cache_hint)``). Returns (admissions, rejected_keys):
        rejected keys had prompts longer than the loop's S budget and must
        be routed through the one-shot path by the caller; items beyond the
        admitted count are simply not consumed (the caller retries at the
        next segment boundary). Join groups are bucketed to data_size * 2^k
        (power of two single-chip; multiples of the mesh data axis sharded,
        so join rows always divide over `data`) and capped at the free-slot
        count so every scatter target — including all-pad filler rows —
        lands on a distinct free slot; with fewer free slots than data_size
        the admit defers to the next boundary."""
        if self._closed:
            raise RuntimeError("slot loop is closed")
        import jax
        import jax.numpy as jnp

        b = self.backend
        sink = b.stats.host_spans
        t_admit = time.monotonic()
        items = list(items)
        if not items or not self.free:
            return [], []
        # seeded fault injection (vnsum_tpu.testing.faults); no-op unless a
        # plan is armed. Any raise propagates with the matched chains still
        # unpinned (matching happens below) or released by the finally
        fault("engine.slot_admit", prompts=[it[1] for it in items])
        keys = [it[0] for it in items]
        prompts = [it[1] for it in items]
        hints = [it[2] for it in items]
        pc = b.prefix_cache
        matches = None
        # host spans, one set a JOIN (core.profiling.host_span): slot/pack
        # (tokenize, match, pack), slot/prefill (dispatch to the done0
        # fetch), slot/adopt — never one a row
        try:
            with host_span("slot", "pack", sink, prompts=len(items)):
                encoded = b.tok.encode_batch(prompts, add_bos=True)
                rejected = [
                    keys[i] for i in range(len(items))
                    if len(encoded[i]) > self.S
                ]
                ok = [i for i in range(len(items)) if len(encoded[i]) <= self.S]
                if not ok:
                    return [], rejected
                free_slots = [s for s, k in enumerate(self._keys) if k is None]
                n = min(len(ok), len(free_slots))
                # the join bucket starts at the mesh data-axis size (join
                # batches shard their rows over `data` exactly like the
                # resident batch, so Bj must stay divisible by it; 1
                # single-chip) and grows by doubling
                data_size = (
                    b.mesh.shape.get("data", 1) if b.mesh is not None else 1
                )
                Bj = data_size
                while Bj < n:
                    Bj *= 2
                if Bj > len(free_slots):
                    # the bucket's filler rows need free slots too — shrink
                    # the admit to the largest data_size * 2^k that fits
                    # outright; with fewer free slots than DP rows need,
                    # wait for the next boundary
                    if len(free_slots) < data_size:
                        return [], rejected
                    n = Bj = (
                        _pow2_floor(len(free_slots) // data_size) * data_size
                    )
                take = ok[:n]

                if pc is not None:
                    matches = {
                        i: pc.match(encoded[i], max_tokens=len(encoded[i]) - 1)
                        for i in take
                    }
                    # order the join group by UNCOVERED suffix so its shared
                    # resume boundary K is as deep as the coldest row allows
                    # (same policy as generate()'s cache ordering)
                    take.sort(key=lambda i: (
                        len(encoded[i]) - matches[i].tokens, len(encoded[i])))
                group_ids = [encoded[i] for i in take]
                group_hints = [hints[i] for i in take]
                tokens, pad_lens = left_pad_batch(
                    group_ids, Bj, self.S, b.tok.pad_id
                )
                resume = None
                if matches is not None:
                    group_matches = [matches[i] for i in take]
                    resume = b._prepare_resume(
                        list(range(len(take))), group_ids, group_matches,
                        pad_lens, Bj, self.S, self.max_new,
                    )
                K = resume[0] if resume else 0
                uids = [self._uid_next + j for j in range(len(take))]
                self._uid_next += len(take)
                uids_np = np.zeros((Bj,), np.int32)
                uids_np[: len(take)] = uids
                dead, pieces = b._count_row_chunks(pad_lens, self.S, K)
            prefill = b._get_seg_fn(
                "slot_prefill", Bj, self.S, self.max_new, self.gen, K
            )
            with hot_path_transfer_guard():
                # the collector's "prefill": its end is the joiners' TTFT
                # anchor, bounded by the fetch below (synced)
                warm = prefill in b._warm
                with execution_span("slot", "prefill", sink, probe=warm,
                                    B=Bj, S=self.S, occupancy=len(take),
                                    dead_row_chunks=dead, synced=True) as pre:
                    if resume:
                        first, join_cache, done0 = prefill(
                            b.params, tokens, pad_lens, self.seed, uids_np,
                            resume[1],
                        )
                    else:
                        first, join_cache, done0 = prefill(
                            b.params, tokens, pad_lens, self.seed, uids_np
                        )
                    if pc is not None:
                        # prefix-cache insertion reads the join cache BEFORE
                        # the adopt dispatch donates it (the copies enter
                        # the stream first)
                        b._cache_insert(
                            join_cache, list(range(len(take))), group_ids,
                            group_matches, group_hints, pad_lens,
                        )
                    # the joiners' first token IS their TTFT: bound the
                    # prefill dispatch with the cheapest output so the
                    # anchor is honest
                    # lint-allow[host-sync-in-hot-path]: sync makes the per-joiner TTFT anchor real, one [Bj] bool fetch per admit
                    jax.device_get(done0)
                prefill_end = pre.t0 + pre.dur
                # a join's pieces are what tells its executions apart: the
                # pace is a live piece's
                b.stats.note_execution(
                    "slot_prefill", Bj, self.S, pre, first=not warm,
                    units=pieces - dead, rows=len(take),
                    pieces=(dead, pieces))
                adopt = b._get_seg_fn(
                    "adopt", Bj, self.S, self.max_new, self.gen
                )
                warm = adopt in b._warm
                with execution_span("slot", "adopt", sink, probe=warm,
                                    B=Bj) as adopted:
                    # lint-allow[host-sync-in-hot-path]: host list -> host array for the scatter indices, no device sync
                    slot_idx = np.asarray(free_slots[:Bj], np.int32)
                    (self._cache, self._cur, self._done, self._t, self._out,
                     self._pads) = adopt(
                        self._cache, self._cur, self._done, self._t,
                        self._out, self._pads, join_cache, first, done0,
                        jnp.asarray(pad_lens), slot_idx,
                    )
                    self._t_host[slot_idx] = 0
                    self._pads_host[slot_idx] = pad_lens
                b.stats.note_execution("adopt", Bj, self.S, adopted,
                                       first=not warm, rows=Bj)
        finally:
            if matches is not None:
                for m in matches.values():
                    pc.release(m)

        # the adopt scatter rewrote out rows: any boundary snapshot is stale
        self._out_snap = None
        skipped = resume[2] if resume else [0] * len(take)
        admissions: list[SlotAdmission] = []
        occupancy = self.active + len(take)
        for j, i in enumerate(take):
            slot = free_slots[j]
            self._keys[slot] = keys[i]
            self._prompts[slot] = prompts[i]
            self._uids[slot] = uids[j]
            self._t_host[slot] = 0
            adm = SlotAdmission(
                key=keys[i], slot=slot, admitted_at=t_admit,
                prefill_end=prefill_end,
                prompt_tokens=len(encoded[i]),
                cached_tokens=int(skipped[j]),
                occupancy=occupancy,
            )
            self._admissions[slot] = adm
            admissions.append(adm)
        self.refills += len(take)
        st = b.stats
        st.batches += 1
        st.prompts += len(take)
        st.prompt_tokens += sum(len(group_ids[j]) for j in range(len(take)))
        st.by_bucket[(Bj, self.S)] = st.by_bucket.get((Bj, self.S), 0) + 1
        if pc is not None:
            hit = sum(skipped)
            st.cache_hit_tokens += hit
            st.cache_miss_tokens += sum(len(g) for g in group_ids) - hit
        return admissions, rejected

    # -- one decode segment ----------------------------------------------

    @staticmethod
    def _await_retirement(arrays) -> None:
        """Async host polling: request the d2h copies up front (non-
        blocking), then poll ``jax.Array`` readiness with a backing-off
        sleep until the segment retires. The host never blocks
        inside the runtime while the device is still looping — the poll
        is pure host time, and the later explicit ``device_get`` finds the
        copies already landed. On a TPU the transfer guard counts
        ``copy_to_host_async`` as a device-to-host transfer it was not told
        about (on the CPU it never fires, which is how this went unseen), so
        the copies are started under an explicit allow: they ARE the
        boundary fetch, begun early."""
        import jax

        with jax.transfer_guard_device_to_host("allow"):
            for a in arrays:
                a.copy_to_host_async()
        spin = 0.0001
        while not all(a.is_ready() for a in arrays):
            time.sleep(spin)
            spin = min(spin * 2, 0.005)

    # hot path
    def step(self) -> SegmentResult:
        """Advance every live slot by up to ``segment_tokens`` tokens in
        one dispatch (the on-device while_loop owns the early all-rows-done
        stop), then harvest finished rows at the boundary. The host does
        not block inside the runtime: it dispatches the segment, polls
        array readiness asynchronously, and pays ONE coalesced done/t/out
        fetch when the dispatch retires. The out
        snapshot it leaves behind serves ``partial_outputs`` — a streaming
        boundary costs one d2h, not two."""
        if self._closed:
            raise RuntimeError("slot loop is closed")
        res = SegmentResult(live=self.active)
        if not res.live:
            return res
        fault("engine.slot_step",
              prompts=[p for p in self._prompts if p is not None])
        import jax

        b = self.backend
        sink = b.stats.host_spans
        seg_fn = b._get_seg_fn(
            "slot_seg", self.slots, self.S, self.max_new, self.gen,
        )
        self._out_snap = None
        # one span a segment, call to boundary fetch (the collector's
        # "decode_seg"); nothing inside _await_retirement's poll
        warm = seg_fn in b._warm
        seg = execution_span("slot", "segment", sink, event="decode_seg",
                             probe=warm, B=self.slots, S=self.S,
                             live=res.live, refill=True)
        skipped = blocks = 0
        with seg:
            with hot_path_transfer_guard():
                # lint-allow[host-sync-in-hot-path]: host list -> host array for the uids argument, no device sync
                uids_np = np.asarray(self._uids, np.int32)
                (self._t, self._cur, self._cache, self._done,
                 self._out) = seg_fn(
                    b.params, self._t, self._cur, self._cache, self._done,
                    uids_np, self._out, self._pads, self.seed,
                )
                # whether a row finished is unknowable before the done poll, so
                # the out buffer ALWAYS rides the boundary fetch — one coalesced
                # d2h covers harvest AND streaming instead of the former
                # fetch-done-then-maybe-fetch-out / fetch-out-again-per-stream
                # pattern (a [B, max_new] int32 block, small next to a segment's
                # compute)
                ctrl = (self._done, self._t, self._out)
                self._await_retirement(ctrl)
                # ONE explicit fetch for the whole boundary: control values and
                # the output buffer together (the copies already landed — this
                # resolves them without a fresh device sync)
                # lint-allow[host-sync-in-hot-path]: segment-boundary done/t/out fetch is the loop's control dependency, already resident host-side via the async copies
                done_h, t_h, out_h = jax.device_get(ctrl)
                finished = [
                    s for s, k in enumerate(self._keys)
                    if k is not None and done_h[s]
                ]
            res.new_tokens = sum(
                int(t_h[s]) - int(self._t_host[s])
                for s, k in enumerate(self._keys) if k is not None
            )
            # the loop ran a step for every token of the row that went
            # furthest; a row that ended stays at its last slot
            res.steps = int((t_h - self._t_host).max())
            if b.mesh is None:  # under a mesh the segment's attention is dense
                skipped, blocks = b._count_decode_kv_blocks(
                    self._pads_host, self.S + np.minimum(
                        self._t_host + np.arange(res.steps)[:, None], t_h),
                    self.S, self.S + self.max_new)
                seg.note(skipped_kv_blocks=skipped, kv_blocks=blocks)
        res.seconds = seg.dur
        # a segment's steps are what tells its executions apart: the pace
        # is a step's
        b.stats.note_execution(
            "segment", self.slots, self.S, seg, first=not warm,
            units=res.steps, rows=res.live, steps=res.steps,
            kv_blocks=(skipped, blocks))
        self._t_host[:] = t_h
        self._out_snap = out_h
        with host_span("slot", "harvest", sink, rows=len(finished)):
            for s in finished:
                text = self._row_text(out_h[s], int(t_h[s]))
                res.completions.append(SlotCompletion(
                    key=self._keys[s], text=text, slot=s,
                    gen_tokens=int(t_h[s]),
                ))
                self._keys[s] = None
                self._prompts[s] = None
                self._admissions.pop(s, None)
        self.segments += 1
        return res

    # -- preemption / streaming (serve/qos.py + serve/stream.py) ---------

    def evict(self, keys, pin: bool = True) -> list[SlotEviction]:
        """Free the slots of ``keys`` mid-decode (priority-tier preemption
        and request cancellation): their done flags flip on device so the
        next segment skips them, their host rows clear, and — when a
        prefix cache is configured and ``pin`` is True — each evictee's
        prompt prefix is matched and left PINNED (the returned
        SlotEviction.pin) so its cached blocks survive LRU until the
        scheduler releases them. ``pin=False`` is the CANCEL path: the
        request is terminal, so there is no restart prefill to keep warm —
        taking a pin would only be refcount churn the scheduler
        immediately unwinds. The evictee's decode state is dropped either
        way; a preemption requeue restarts it from its prompt (greedy
        restarts are byte-identical by engine determinism)."""
        import jax.numpy as jnp

        b = self.backend
        targets = {id(k) for k in keys}
        slots = [
            s for s, k in enumerate(self._keys)
            if k is not None and id(k) in targets
        ]
        if not slots:
            return []
        self._done = self._done.at[jnp.asarray(slots, jnp.int32)].set(True)
        out: list[SlotEviction] = []
        pc = b.prefix_cache if pin else None
        for s in slots:
            ev_pin = None
            if pc is not None:
                ids = b.tok.encode_batch([self._prompts[s]], add_bos=True)[0]
                m = pc.match(ids, max_tokens=len(ids) - 1)
                ev_pin = (pc, m)
            out.append(SlotEviction(key=self._keys[s], slot=s, pin=ev_pin))
            self._keys[s] = None
            self._prompts[s] = None
            self._admissions.pop(s, None)
        return out

    def partial_outputs(self, keys) -> dict:
        """Decoded-so-far text per resident key, keyed by ``id(key)`` (keys
        are arbitrary caller objects, not necessarily hashable) — the
        streaming harvest. Served from the boundary SNAPSHOT step() left
        behind (the out buffer rode the coalesced done/t/out fetch), so a
        streaming boundary pays zero extra d2h; rows are cut at their
        host-tracked cursor so unwritten tail slots never leak into a
        delta. The device fetch below is the cold fallback only — a caller
        polling between an admit and the next step, where the snapshot was
        invalidated by the adopt scatter."""
        targets = {id(k) for k in keys}
        rows = [
            s for s, k in enumerate(self._keys)
            if k is not None and id(k) in targets
        ]
        if not rows:
            return {}
        out_h = self._out_snap
        if out_h is None:
            import jax

            # lint-allow[host-sync-in-hot-path]: cold fallback off the boundary cadence (post-admit, pre-step); the hot path serves the coalesced snapshot above
            out_h = jax.device_get(self._out)
        return {
            id(self._keys[s]): self._row_text(out_h[s], int(self._t_host[s]))
            for s in rows
        }

    def _row_text(self, row: np.ndarray, t: int) -> str:
        """A row's first ``t`` emitted ids as text, cut after a terminator.
        The row's own cursor says where it ends, not its first pad id: pad
        is an id like any other to the sampler (one draw in the decodable
        vocabulary), and cutting at the first one emptied an answer whose
        first token it was and shortened any other. The tokenizer drops a
        drawn pad as it drops every special id."""
        b = self.backend
        emitted = row[:t]
        b.stats.generated_tokens += int((emitted != b.tok.pad_id).sum())
        ids = trim_to_eos(
            emitted.tolist(), b.tok.eos_id, -1, tuple(self.gen.eos_ids)
        )
        return b.tok.decode(ids).strip()

    # -- lifecycle -------------------------------------------------------

    def outstanding(self) -> list:
        """Keys still resident (the caller drains before closing)."""
        return [k for k in self._keys if k is not None]

    def _segment_kv_blocks(self) -> tuple[int, int]:
        """(skipped, walked) key blocks of this shape's segment program so
        far, from the engine's account."""
        ex = self.backend.stats.executions.get(
            ("segment", self.slots, self.S))
        return (ex.kv_blocks_skipped, ex.kv_blocks) if ex else (0, 0)

    def close(self) -> None:
        if not self._closed:
            skipped, blocks = self._segment_kv_blocks()
            logger.info(
                "slot loop closed after %d segments and %d joined rows: "
                "skipped_kv_blocks %d/%d", self.segments, self.refills,
                skipped - self._kv_blocks_before[0],
                blocks - self._kv_blocks_before[1])
        self._closed = True
        # drop the device state promptly — the resident cache is the big
        # HBM tenant, and a replacement loop allocates its own
        self._cache = None
        self._cur = self._done = self._t = self._out = self._pads = None
        self._out_snap = None
