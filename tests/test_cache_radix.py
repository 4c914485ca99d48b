"""Radix prefix index + paged block store (vnsum_tpu.cache) unit tests.

The acceptance-critical property lives here: eviction under a tight block
budget can never reallocate a block a live match still pins, and chains only
evict tail-first (leaves), so a surviving match can never dangle.
"""
import threading

import numpy as np
import pytest

from vnsum_tpu.cache import BlockStore, PrefixCache, RadixIndex


def seq(n, base=0):
    return [base + i for i in range(n)]


# -- radix index -------------------------------------------------------------


def test_match_is_block_aligned():
    idx = RadixIndex(num_blocks=8, block_tokens=4)
    idx.insert(seq(10), upto=10)  # caches 2 blocks = 8 tokens
    m = idx.match(seq(10))
    assert m.tokens == 8
    assert len(m.blocks) == 2
    idx.release(m)


def test_match_respects_max_tokens():
    idx = RadixIndex(num_blocks=8, block_tokens=4)
    idx.insert(seq(12), upto=12)
    m = idx.match(seq(12), max_tokens=7)  # only 1 whole block fits under 7
    assert m.tokens == 4
    idx.release(m)


def test_divergent_suffixes_share_prefix_blocks():
    idx = RadixIndex(num_blocks=8, block_tokens=4)
    a = seq(4) + [100, 101, 102, 103]
    b = seq(4) + [200, 201, 202, 203]
    idx.insert(a, upto=8)
    idx.insert(b, upto=8)
    assert idx.blocks_used == 3  # shared head + two tails
    ma, mb = idx.match(a), idx.match(b)
    assert ma.blocks[0] == mb.blocks[0]
    assert ma.blocks[1] != mb.blocks[1]
    idx.release(ma)
    idx.release(mb)


def test_insert_reuses_existing_chain():
    idx = RadixIndex(num_blocks=8, block_tokens=4)
    new1 = idx.insert(seq(8), upto=8)
    new2 = idx.insert(seq(8), upto=8)
    assert len(new1) == 2 and new2 == []
    assert idx.stats.inserted_blocks == 2


def test_probe_is_readonly():
    idx = RadixIndex(num_blocks=8, block_tokens=4)
    idx.insert(seq(8), upto=8)
    before = idx.stats.lookups
    assert idx.probe(seq(8)) == 8
    assert idx.probe(seq(3)) == 0
    assert idx.stats.lookups == before  # probes don't count as lookups


def test_lru_evicts_oldest_unpinned_leaf():
    idx = RadixIndex(num_blocks=2, block_tokens=4)
    idx.insert(seq(4, 0), upto=4)
    idx.insert(seq(4, 100), upto=4)
    # touch the first chain so the second becomes LRU
    m = idx.match(seq(4, 0))
    idx.release(m)
    idx.insert(seq(4, 200), upto=4)  # forces one eviction
    assert idx.stats.evictions == 1
    assert idx.probe(seq(4, 0)) == 4      # recently used: survived
    assert idx.probe(seq(4, 100)) == 0    # LRU victim
    assert idx.probe(seq(4, 200)) == 4


def test_pinned_blocks_never_evicted():
    idx = RadixIndex(num_blocks=2, block_tokens=4)
    idx.insert(seq(8), upto=8)  # fills the pool with one 2-block chain
    m = idx.match(seq(8))       # pin both
    # insertion pressure: nothing is evictable while the match is live
    assert idx.insert(seq(4, 500), upto=4) == []
    assert idx.stats.evictions == 0
    assert idx.probe(seq(8)) == 8
    idx.release(m)
    # released: now the tail leaf can go
    assert len(idx.insert(seq(4, 500), upto=4)) == 1
    assert idx.stats.evictions == 1


def test_chains_evict_tail_first():
    idx = RadixIndex(num_blocks=3, block_tokens=2)
    idx.insert(seq(6), upto=6)  # one 3-block chain
    idx.insert(seq(2, 900), upto=2)  # evicts exactly one block
    assert idx.stats.evictions == 1
    # the interior of the chain must have survived: the head 2 blocks match
    assert idx.probe(seq(6)) == 4


def test_release_idempotent():
    idx = RadixIndex(num_blocks=4, block_tokens=2)
    idx.insert(seq(4), upto=4)
    m = idx.match(seq(4))
    idx.release(m)
    idx.release(m)  # second release is a no-op, refs must not go negative
    m2 = idx.match(seq(4))
    assert all(n.refs == 1 for n in m2.nodes)
    idx.release(m2)


def test_concurrent_probes_against_mutation():
    """HTTP-thread probes race the engine thread's match/insert/release
    churn; no exceptions, no negative refs, pool accounting stays sane."""
    idx = RadixIndex(num_blocks=16, block_tokens=4)
    stop = threading.Event()
    errors = []

    def prober():
        while not stop.is_set():
            try:
                idx.probe(seq(16, 0))
                idx.probe(seq(8, 100))
            except Exception as e:  # pragma: no cover - the assertion target
                errors.append(e)
                return

    threads = [threading.Thread(target=prober) for _ in range(4)]
    for t in threads:
        t.start()
    # the "engine thread": steady match/insert/release churn with eviction
    for i in range(300):
        tokens = seq(16, (i % 5) * 1000)
        m = idx.match(tokens, max_tokens=len(tokens) - 1)
        idx.insert(tokens, upto=12)
        idx.release(m)
    stop.set()
    for t in threads:
        t.join()
    assert not errors
    assert 0 <= idx.blocks_used <= 16


# -- block store -------------------------------------------------------------


@pytest.fixture(scope="module")
def jnp():
    return pytest.importorskip("jax.numpy")


def _fake_cache(jnp, L=2, B=3, KV=2, C=32, hd=4, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "k": jnp.asarray(rng.normal(size=(L, B, KV, C, hd)).astype(np.float32)),
        "v": jnp.asarray(rng.normal(size=(L, B, KV, C, hd)).astype(np.float32)),
    }


def test_store_write_gather_roundtrip(jnp):
    BLK = 4
    store = BlockStore(
        num_blocks=8, block_tokens=BLK, n_layers=2, n_kv_heads=2,
        head_dim=4, dtype=jnp.float32,
    )
    src = _fake_cache(jnp)
    # extract two consecutive blocks of row 1 starting at slot 8
    store.write_blocks(src, [(1, 8, 3)])
    store.write_blocks(src, [(1, 12, 5)])
    # gather them into row 0 and row 2 of a zero cache at different offsets
    dst = {k: jnp.zeros_like(v) for k, v in _fake_cache(jnp, seed=1).items()}
    ids = np.array([[3, 5], [store.scratch_id] * 2, [3, 5]], dtype=np.int32)
    starts = np.array([4, 0, 16], dtype=np.int32)
    out = store.gather(dst, ids, starts)
    for name in ("k", "v"):
        slab = np.asarray(src[name])[:, 1, :, 8:16]
        np.testing.assert_array_equal(np.asarray(out[name])[:, 0, :, 4:12], slab)
        np.testing.assert_array_equal(np.asarray(out[name])[:, 2, :, 16:24], slab)
        # scratch-padded row untouched beyond zeros
        np.testing.assert_array_equal(
            np.asarray(out[name])[:, 1], np.zeros_like(np.asarray(out[name])[:, 1])
        )


def test_store_quantized_leaves_roundtrip(jnp):
    BLK = 4
    store = BlockStore(
        num_blocks=4, block_tokens=BLK, n_layers=1, n_kv_heads=1,
        head_dim=4, dtype=jnp.float32, quantized=True,
    )
    assert set(store.pool) == {"k", "v", "ks", "vs"}
    rng = np.random.default_rng(0)
    src = {
        "k": jnp.asarray(rng.integers(-127, 127, size=(1, 2, 1, 16, 4), dtype=np.int8)),
        "v": jnp.asarray(rng.integers(-127, 127, size=(1, 2, 1, 16, 4), dtype=np.int8)),
        "ks": jnp.asarray(rng.normal(size=(1, 2, 1, 16)).astype(np.float32)),
        "vs": jnp.asarray(rng.normal(size=(1, 2, 1, 16)).astype(np.float32)),
    }
    store.write_blocks(src, [(0, 4, 2)])
    dst = {k: jnp.zeros_like(v) for k, v in src.items()}
    out = store.gather(dst, np.array([[2], [store.scratch_id]], np.int32),
                       np.array([8, 0], np.int32))
    for name in src:
        got = np.asarray(out[name])[:, 0, :, 8:12]
        want = np.asarray(src[name])[:, 0, :, 4:8]
        np.testing.assert_array_equal(got, want)


def test_prefix_cache_facade(jnp):
    pc = PrefixCache(
        num_blocks=8, block_tokens=4, n_layers=2, n_kv_heads=2,
        head_dim=4, dtype=jnp.float32,
    )
    cache = _fake_cache(jnp)
    ids = seq(10)
    n = pc.insert(cache, [(0, 2, ids, 9)])  # row 0 at slot 2: 2 whole blocks
    assert n == 2
    assert pc.probe(ids) == 8
    m = pc.match(ids, max_tokens=len(ids) - 1)
    assert m.tokens == 8
    scratch = pc.store.scratch_id
    ids_all = np.array(
        [m.blocks, [scratch] * len(m.blocks), [scratch] * len(m.blocks)],
        np.int32,
    )
    seeded = pc.gather(
        {k: jnp.zeros_like(v) for k, v in cache.items()},
        ids_all, np.array([2, 0, 0], np.int32),
    )
    for name in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(seeded[name])[:, 0, :, 2:10],
            np.asarray(cache[name])[:, 0, :, 2:10],
        )
    pc.release(m)
    st = pc.stats_dict()
    assert st["blocks_used"] == 2 and st["blocks_total"] == 8
    assert st["hbm_bytes"] > 0


# -- batched insertion: one dispatch per list --------------------------------

_GEOM = dict(L=2, B=3, KV=2, C=32, hd=4)
_BLK = 4


def _pool_kind_cache(jnp, kind, seed=0):
    """A [L, B, KV, C(, hd)] batch cache of one pool kind: bf16 leaves, or
    int8 leaves with their float32 scales."""
    g = _GEOM
    rng = np.random.default_rng(seed)
    shape = (g["L"], g["B"], g["KV"], g["C"], g["hd"])
    if kind == "bf16":
        return {
            n: jnp.asarray(rng.normal(size=shape), dtype=jnp.bfloat16)
            for n in ("k", "v")
        }
    cache = {
        n: jnp.asarray(rng.integers(-127, 128, size=shape, dtype=np.int8))
        for n in ("k", "v")
    }
    for n in ("ks", "vs"):
        cache[n] = jnp.asarray(rng.normal(size=shape[:-1]).astype(np.float32))
    return cache


def _pool_kind_store(jnp, kind, num_blocks=8):
    return BlockStore(
        num_blocks=num_blocks, block_tokens=_BLK, n_layers=_GEOM["L"],
        n_kv_heads=_GEOM["KV"], head_dim=_GEOM["hd"], dtype=jnp.bfloat16,
        quantized=kind == "int8",
    )


def _pool_arrays(store):
    # bf16 has no numpy dtype of its own: compare the bits
    import jax.numpy as jnp

    return {
        n: np.asarray(v.view(jnp.uint16) if v.dtype == jnp.bfloat16 else v)
        for n, v in store.pool.items()
    }


def _assert_pools_equal(a, b):
    pa, pb = _pool_arrays(a), _pool_arrays(b)
    assert pa.keys() == pb.keys()
    for name in pa:
        np.testing.assert_array_equal(pa[name], pb[name], err_msg=name)


# (row, slot, block_id) lists; the fixed vector of this cache shape holds
# B * (C // BLK) = 24 entries
_TRIPLE_LISTS = {
    "one_entry": [(1, 8, 3)],
    "ragged_short": [(0, 2, 5), (0, 6, 1), (2, 17, 7), (1, 0, 0)],
    "full_length": [
        (r, s, (r * 8 + s // _BLK) % 8) for r in range(3)
        for s in range(0, 32, _BLK)
    ],
    # block 4 written twice from different slabs: the later write must win
    "block_id_twice": [(0, 4, 4), (1, 12, 2), (2, 20, 4), (1, 28, 6)],
    "last_slot_of_the_row": [(2, 28, 0), (0, 0, 7)],
}


@pytest.mark.parametrize("case", sorted(_TRIPLE_LISTS))
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_write_blocks_equals_one_block_at_a_time(jnp, kind, case):
    """The pool after ONE batched dispatch equals, array for array, the pool
    after the same list one entry a dispatch — and both equal what plain
    numpy slicing says the pool should hold."""
    triples = _TRIPLE_LISTS[case]
    cache = _pool_kind_cache(jnp, kind)
    batched = _pool_kind_store(jnp, kind)
    serial = _pool_kind_store(jnp, kind)
    batched.write_blocks(cache, triples)
    for row, slot, bid in triples:
        serial.write_blocks(cache, [(row, slot, bid)])
    assert batched.write_dispatches == 1
    assert serial.write_dispatches == len(triples)
    _assert_pools_equal(batched, serial)
    got = _pool_arrays(batched)
    for name, buf in cache.items():
        want = np.zeros_like(got[name])
        src = np.asarray(buf.view(jnp.uint16) if kind == "bf16" else buf)
        for row, slot, bid in triples:
            want[bid] = src[:, row, :, slot:slot + _BLK]
        np.testing.assert_array_equal(got[name], want, err_msg=name)


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_write_blocks_empty_list_dispatches_nothing(jnp, kind):
    store = _pool_kind_store(jnp, kind)
    before = dict(store.pool)
    store.write_blocks(_pool_kind_cache(jnp, kind), [])
    assert store.write_dispatches == 0
    assert not store._write_fns            # nothing was even traced
    assert all(store.pool[n] is before[n] for n in before)


def test_write_blocks_one_program_per_cache_shape(jnp):
    """Lists of any length share the executable of their cache shape: the
    vector's length is fixed by the shape and the trip count is an input."""
    store = _pool_kind_store(jnp, "int8")
    cache = _pool_kind_cache(jnp, "int8")
    for case in sorted(_TRIPLE_LISTS):
        store.write_blocks(cache, _TRIPLE_LISTS[case])
    (fn,) = store._write_fns.values()
    assert fn._cache_size() == 1
    with pytest.raises(ValueError, match="at most 24"):
        store.write_blocks(cache, [(0, 0, 0)] * 25)


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_insert_second_row_evicts_first_rows_leaf(jnp, kind):
    """A join of two long rows over a small pool: the second row's index
    insert evicts leaves the first row allocated a moment before (its pins
    are dropped when its own insert ends), so block ids occur twice in the
    join's one list. The pool must equal the per-row, per-block path's."""
    rows = [(0, 3, seq(24), 20), (2, 8, seq(24, base=100), 24)]

    def build():
        return PrefixCache(
            num_blocks=8, block_tokens=_BLK, n_layers=_GEOM["L"],
            n_kv_heads=_GEOM["KV"], head_dim=_GEOM["hd"], dtype=jnp.bfloat16,
            quantized=kind == "int8",
        )

    cache = _pool_kind_cache(jnp, kind, seed=3)
    batched, serial = build(), build()
    seen = []
    write_blocks = batched.store.write_blocks
    batched.store.write_blocks = lambda c, t: (seen.extend(t), write_blocks(c, t))
    assert batched.insert(cache, rows) == 11
    for row, slot_base, ids, upto in rows:
        for block, off in serial.index.insert(ids, upto):
            serial.store.write_blocks(cache, [(row, slot_base + off, block)])
    block_ids = [bid for _, _, bid in seen]
    assert len(block_ids) == 11 and len(set(block_ids)) == 8  # 3 written twice
    assert batched.store.write_dispatches == 1
    assert serial.store.write_dispatches == 11
    assert batched.stats_dict()["evictions"] == 3
    _assert_pools_equal(batched.store, serial.store)
    # and the index hands the same blocks out for the surviving chains
    for _, _, ids, _ in rows:
        a = batched.match(ids)
        b = serial.match(ids)
        assert a.blocks == b.blocks and a.tokens == b.tokens
    # the second row is whole in the pool: gathering it returns its slabs
    m = batched.match(rows[1][2])
    assert m.tokens == 24
    dst = {k: jnp.zeros_like(v) for k, v in cache.items()}
    scratch = [batched.store.scratch_id] * len(m.blocks)
    out = batched.gather(
        dst, np.array([m.blocks, scratch, scratch], np.int32),
        np.array([4, 0, 0], np.int32),
    )
    for name in cache:
        np.testing.assert_array_equal(
            np.asarray(out[name].astype(jnp.float32))[:, 0, :, 4:28],
            np.asarray(cache[name].astype(jnp.float32))[:, 2, :, 8:32],
        )


def test_prefix_cache_stats_count_blocks_and_dispatches(jnp):
    pc = PrefixCache(
        num_blocks=8, block_tokens=4, n_layers=2, n_kv_heads=2,
        head_dim=4, dtype=jnp.float32,
    )
    cache = _fake_cache(jnp)
    assert pc.stats_dict()["write_dispatches"] == 0
    assert pc.insert(cache, [(0, 2, seq(10), 9), (1, 0, seq(13, 50), 12)]) == 5
    st = pc.stats_dict()
    assert st["inserted_blocks"] == 5 and st["write_dispatches"] == 1
    # nothing new to write: no dispatch
    assert pc.insert(cache, [(0, 2, seq(10), 9)]) == 0
    assert pc.stats_dict()["write_dispatches"] == 1
