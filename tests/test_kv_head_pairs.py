"""64-wide KV heads two a lane tile (PR 55): the cache's layout by shapes
alone (`heads_per_lane_tile`, `init_kv_cache`, the prefix pool), what writes
and reads it (`_write_kv`, `dequantize_cache_layer`), and the three GQA
kernels over a packed cache against the one-head-a-tile kernels and the dense
`_attention` on the same values (interpret mode on the CPU)."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vnsum_tpu.models.llama import (
    _attention,
    _quantize_kv,
    _write_kv,
    decode_attention_mask,
    dequantize_cache_layer,
    heads_to_tiles,
    init_kv_cache,
    prefill_attention_mask,
    tiles_to_heads,
    verify_attention_mask,
)
from vnsum_tpu.ops.decode_attention import (
    decode_block_k,
    flash_decode_attention,
    flash_spec_verify_attention,
    place_in_own_lanes,
    take_own_lanes,
)
from vnsum_tpu.ops.flash_attention import (
    flash_prefill_attention,
    heads_per_lane_tile,
)


def _cfg(n_kv_heads, head_dim, n_layers=2, dtype=jnp.bfloat16):
    return types.SimpleNamespace(n_layers=n_layers, n_kv_heads=n_kv_heads,
                                 head_dim=head_dim, dtype=dtype)


# -- the rule and the shapes it gives ----------------------------------------


@pytest.mark.parametrize("n_kv,hd,shards,tile", [
    (8, 64, 1, 2),     # Granite-4.0-H, LFM2, llama3.2-1b
    (8, 64, 2, 2), (8, 64, 4, 2),
    (8, 64, 8, 1),     # a tensor axis of 8 would split every pair
    (2, 64, 2, 1),     # one pair over two shards
    (7, 64, 1, 1),     # an odd head has no neighbour
    (1, 64, 1, 1),
    (8, 128, 1, 1), (10, 128, 1, 1), (4, 128, 1, 1), (2, 128, 1, 1),
    (16, 128, 1, 1),   # every cell at 128: one head a tile, as it was
    (4, 256, 1, 1),    # Gemma3
    (2, 16, 1, 1), (4, 32, 1, 1),   # the tiny test configs: not half a tile
])
def test_two_heads_a_tile_only_where_heads_are_half_a_tile_and_pair_off(
        n_kv, hd, shards, tile):
    assert heads_per_lane_tile(n_kv, hd, shards) == tile


@pytest.mark.parametrize("n_kv,hd,shards,kv_shape", [
    # pinned equal to the parent's: [L, B, KV, C, hd]
    (8, 128, 1, (3, 2, 8, 40, 128)), (10, 128, 1, (3, 2, 10, 40, 128)),
    (4, 128, 1, (3, 2, 4, 40, 128)), (2, 128, 1, (3, 2, 2, 40, 128)),
    (16, 128, 1, (3, 2, 16, 40, 128)), (4, 256, 1, (3, 2, 4, 40, 256)),
    (2, 16, 1, (3, 2, 2, 40, 16)),
    # 64-wide heads that pair off: [L, B, KV/2, C, 128]
    (8, 64, 1, (3, 2, 4, 40, 128)), (8, 64, 4, (3, 2, 4, 40, 128)),
    # ... and that do not: the parent's
    (7, 64, 1, (3, 2, 7, 40, 64)), (8, 64, 8, (3, 2, 8, 40, 64)),
])
@pytest.mark.parametrize("quantized", [False, True])
def test_the_caches_shapes(n_kv, hd, shards, kv_shape, quantized):
    cache = init_kv_cache(_cfg(n_kv, hd, n_layers=3), 2, 40,
                          quantized=quantized, model_shards=shards)
    assert cache["k"].shape == cache["v"].shape == kv_shape
    assert cache["k"].dtype == (jnp.int8 if quantized else jnp.bfloat16)
    if quantized:
        # the scales stay a HEAD, whatever the tile holds
        assert cache["ks"].shape == cache["vs"].shape == (3, 2, n_kv, 40)
    else:
        assert set(cache) == {"k", "v"}


@pytest.mark.parametrize("KV,hd,tiles,width,bk", [
    # pinned equal to the parent's at one head a tile
    (8, 128, 8, 128, 512), (10, 128, 10, 128, 512), (4, 128, 4, 128, 1024),
    (2, 128, 2, 128, 2048), (16, 128, 16, 128, 512), (4, 256, 4, 256, 512),
    # two a tile: 4 tiles of 128, SmallThinker's block
    (8, 64, 4, 128, 1024),
])
def test_the_decode_block_follows_the_caches_own_shape(KV, hd, tiles, width,
                                                       bk):
    tile = heads_per_lane_tile(KV, hd)
    assert (KV // tile, hd * tile) == (tiles, width)
    for C in (8448, 8320):
        assert decode_block_k(tiles, width, 1, C) == bk


def test_the_prefix_pool_is_shaped_as_the_cache():
    from vnsum_tpu.cache.store import BlockStore

    for n_kv, hd in ((8, 64), (8, 128), (7, 64)):
        store = BlockStore(3, 16, n_layers=2, n_kv_heads=n_kv, head_dim=hd,
                           dtype=jnp.bfloat16, quantized=True)
        cache = init_kv_cache(_cfg(n_kv, hd), 1, 16, quantized=True)
        for name, leaf in cache.items():
            # [N, L, ...] for the cache's [L, B, ...], a block's 16 slots
            assert store.pool[name].shape == (4, 2) + leaf.shape[2:]


# -- what writes and reads the layout ----------------------------------------


def test_heads_to_tiles_puts_neighbours_side_by_side_and_back():
    x = jnp.arange(2 * 6 * 5 * 4).reshape(2, 6, 5, 4)
    tiled = heads_to_tiles(x, 2)
    assert tiled.shape == (2, 3, 5, 8)
    np.testing.assert_array_equal(tiled[:, 1, :, :4], x[:, 2])
    np.testing.assert_array_equal(tiled[:, 1, :, 4:], x[:, 3])
    np.testing.assert_array_equal(tiles_to_heads(tiled, 2), x)
    assert heads_to_tiles(x, 1) is x and tiles_to_heads(x, 1) is x


def test_queries_go_to_their_own_heads_lanes_and_back():
    x = jax.random.normal(jax.random.key(0), (2, 4, 3, 8))
    placed = place_in_own_lanes(x)
    assert placed.shape == (2, 4, 3, 16)
    np.testing.assert_array_equal(placed[:, 0::2, :, :8], x[:, 0::2])
    np.testing.assert_array_equal(placed[:, 1::2, :, 8:], x[:, 1::2])
    assert not placed[:, 0::2, :, 8:].any() and not placed[:, 1::2, :, :8].any()
    np.testing.assert_array_equal(take_own_lanes(placed), x)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("rows", [None, [2, 0]])
def test_a_write_stores_the_numbers_it_stored_a_head_a_tile(quantized, rows):
    """`_write_kv` into a packed cache against the same write into a cache
    of one head a tile: the same int8 values and scales (quantized a token
    and HEAD), side by side; `dequantize_cache_layer` gives them back a head
    apiece. With `rows`, a piece of the cache's rows."""
    B, S, KV, hd, C = 2, 5, 4, 64, 12
    k, v = (jax.random.normal(key, (B, S, KV, hd), jnp.bfloat16)
            for key in jax.random.split(jax.random.key(1)))
    packed = init_kv_cache(_cfg(KV, hd), 3, C, quantized=quantized)
    plain = init_kv_cache(_cfg(KV, hd), 3, C, quantized=quantized,
                          model_shards=KV)     # falls back: one head a tile
    assert packed["k"].shape[-1] == 128 and plain["k"].shape[-1] == 64
    where = None if rows is None else jnp.asarray(rows, jnp.int32)
    if rows is None:
        k, v = (jnp.pad(x, ((0, 1), (0, 0), (0, 0), (0, 0))) for x in (k, v))
    packed = _write_kv(packed, k, v, 1, 3, where)
    plain = _write_kv(plain, k, v, 1, 3, where)
    for name in plain:
        want = plain[name]
        if name in ("k", "v"):
            want = heads_to_tiles(want, 2)
        np.testing.assert_array_equal(np.asarray(packed[name]),
                                      np.asarray(want))
    for got, want in zip(dequantize_cache_layer(packed, 1, hd),
                         dequantize_cache_layer(plain, 1)):
        assert got.shape == (3, KV, C, hd)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(packed["k"][1]).any() and not np.asarray(
        packed["k"][0]).any()


# -- the kernels over a packed cache -----------------------------------------

_C, _L, _LAYER = 300, 2, 1

# name: rows' pads, window, KV heads, query heads a KV head, int8 cache;
# blocks of 128 slots in a cache of 300 (a tail block past its end)
_CASES = {
    "no_pad_int8": ([0, 0, 0], 0, 4, 4, True),
    "no_pad_bf16": ([0, 0, 0], 0, 4, 4, False),
    "pads_0_1_and_past_a_block_int8": ([0, 1, 140], 0, 4, 4, True),
    "pads_0_1_and_past_a_block_bf16": ([0, 1, 140], 0, 4, 4, False),
    "window_int8": ([0, 1, 140], 50, 4, 4, True),
    "window_bf16": ([3, 0, 200], 50, 2, 4, False),
    "one_query_head_a_kv_head_int8": ([0, 1, 140], 0, 8, 1, True),
    "one_query_head_a_kv_head_bf16": ([0, 130, 7], 0, 2, 1, False),
    "one_pair_int8": ([0, 1, 140], 0, 2, 4, True),
}


def _case(name, Sq):
    pads, win, KV, G, int8 = _CASES[name]
    B, hd = len(pads), 64
    keys = jax.random.split(jax.random.key(len(name) + Sq), 3)
    q = jax.random.normal(keys[0], (B, Sq, KV * G, hd), jnp.float32) * 2.0
    k, v = (jax.random.normal(key, (_L, B, KV, _C, hd), jnp.bfloat16)
            for key in keys[1:])
    plain = {"k": k, "v": v}
    if int8:
        (k8, ks), (v8, vs) = _quantize_kv(k), _quantize_kv(v)
        plain = {"k": k8, "v": v8, "ks": ks, "vs": vs}
    packed = dict(plain, k=heads_to_tiles(plain["k"], 2),
                  v=heads_to_tiles(plain["v"], 2))
    assert packed["k"].shape == (_L, B, KV // 2, _C, 128)
    kd, vd = (x.astype(jnp.float32)
              for x in dequantize_cache_layer(packed, _LAYER, hd))
    return (q, plain, packed, kd, vd, jnp.asarray(pads, jnp.int32),
            jnp.int32(win), G)


def _windowed(mask, q_slots, win):
    if not int(win):
        return mask
    return mask & (jnp.arange(_C)[None, None, :] > q_slots[:, :, None] - win)


_TOL = dict(rtol=2e-5, atol=2e-5)


def _agree(packed_out, plain_out, dense, valid=True):
    """The one-head-a-tile kernel's output and the dense attention's, at the
    kernel tests' usual float32 tolerance. The other head's lanes meet exact
    zeros, so most cases are equal bit for bit; not all, because the
    interpreter's products sum in another order where a tile holds 2R rows
    for R (seen at R = 1: 1.9e-6)."""
    valid = np.broadcast_to(np.asarray(valid), packed_out.shape)
    got = np.asarray(packed_out)[valid]
    np.testing.assert_allclose(got, np.asarray(plain_out)[valid], **_TOL)
    np.testing.assert_allclose(got, np.asarray(dense)[valid], **_TOL)


@pytest.mark.parametrize("name", sorted(_CASES))
def test_the_decode_kernel_reads_a_pair_at_once(name):
    q, plain, packed, kd, vd, pads, win, G = _case(name, 1)
    fill = 280
    outs = [flash_decode_attention(q, cache, _LAYER, pads, fill, G, win,
                                   block_k=128, interpret=True)
            for cache in (packed, plain)]
    mask = _windowed(decode_attention_mask(pads, fill, _C),
                     jnp.full((len(pads), 1), fill), win)
    _agree(*outs, _attention(q, kd, vd, mask, G))


@pytest.mark.parametrize("name", sorted(_CASES))
def test_the_partial_sums_of_a_pair_are_each_heads_own(name):
    """`return_partials` (the long-context decode's shard-local sums): o, m
    and l of a packed cache equal those of one head a tile."""
    q, plain, packed, kd, vd, pads, win, G = _case(name, 1)
    fill = 299                                   # into the tail block
    (o, m, l), want = (
        flash_decode_attention(q, cache, _LAYER, pads, fill, G, win,
                               block_k=128, interpret=True,
                               return_partials=True)
        for cache in (packed, plain))
    for got, ref in zip((o, m, l), want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **_TOL)
    mask = _windowed(decode_attention_mask(pads, fill, _C),
                     jnp.full((len(pads), 1), fill), win)
    dense = _attention(q, kd, vd, mask, G)
    np.testing.assert_allclose(
        np.asarray(o / l[..., None])[:, None], np.asarray(dense), **_TOL)


@pytest.mark.parametrize("Sq", [1, 3])
@pytest.mark.parametrize("name", sorted(_CASES))
def test_the_verify_kernel_reads_a_pair_at_once(name, Sq):
    """Per-row fills, ``Sq`` queries a row (1: a slot segment's step)."""
    q, plain, packed, kd, vd, pads, win, G = _case(name, Sq)
    fills = jnp.asarray([200, 280, 297], jnp.int32)
    outs = [flash_spec_verify_attention(q, cache, _LAYER, pads, fills, G, win,
                                        block_k=128, interpret=True)
            for cache in (packed, plain)]
    q_slots = fills[:, None] + jnp.arange(Sq)[None, :]
    mask = _windowed(verify_attention_mask(pads, fills, Sq, _C), q_slots, win)
    _agree(*outs, _attention(q, kd, vd, mask, G))


@pytest.mark.parametrize("rows", [None, [2, 0]])
@pytest.mark.parametrize("name", sorted(_CASES))
def test_the_prefill_kernel_reads_its_heads_half_of_a_pairs_tile(name, rows):
    """A chunk of 200 queries at slot 64 of the packed cache; with ``rows``,
    a piece of the cache's rows read in place (``cache_rows``)."""
    q, plain, packed, kd, vd, pads, win, G = _case(name, 200)
    off = 64
    cache_rows = None if rows is None else jnp.asarray(rows, jnp.int32)
    if rows is not None:
        q, pads = q[: len(rows)], pads[jnp.asarray(rows)]
        kd, vd = kd[jnp.asarray(rows)], vd[jnp.asarray(rows)]
    outs = [flash_prefill_attention(q, cache, _LAYER, pads, G, win,
                                    jnp.int32(off), cache_rows, block_q=64,
                                    block_k=128, interpret=True)
            for cache in (packed, plain)]
    q_slots = off + jnp.broadcast_to(jnp.arange(200)[None, :],
                                     (len(pads), 200))
    mask = _windowed(prefill_attention_mask(pads, off + 200, _C)[:, off:],
                     q_slots, win)
    valid = (q_slots >= pads[:, None])[:, :, None, None]   # pad rows: garbage
    _agree(*outs, _attention(q, kd, vd, mask, G), valid)


def test_the_dense_path_reads_a_packed_cache_a_head_apiece():
    """`_cache_attention` with no kernel (the dense fallback, the slot loop
    under a mesh): the packed cache's layer, unpacked by the queries' head."""
    from vnsum_tpu.models.llama import _cache_attention

    q, plain, packed, kd, vd, pads, win, G = _case("no_pad_int8", 1)
    mask = decode_attention_mask(pads, 280, _C)
    got, want = (_cache_attention(q, cache, _LAYER, mask, G)
                 for cache in (packed, plain))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- through the engine: the counter that says it engages ---------------------


def _prompts(lens):
    return ["".join(chr(97 + (i * 7 + j) % 26) for j in range(n - 1))
            for i, n in enumerate(lens)]            # + BOS: n tokens


@pytest.mark.parametrize("hd,paired", [(64, True), (128, False)])
def test_a_one_shot_run_counts_its_paired_blocks(hd, paired):
    """A tiny llama at 2 KV heads of 64 (one pair) and of 128, one-shot
    through the interpreted kernels over a bfloat16 cache: every key block
    the decode steps walked is counted as paired, or none is; the counter
    reaches ``engine_counters()`` and ``engine_record()``; and the packed
    run writes what the dense path (no kernel, the cache unpacked a head
    apiece) writes."""
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models.llama import tiny_llama

    cfg = tiny_llama(head_dim=hd, max_seq_len=128 + 8)
    lens = [20, 100, 61]
    be = TpuBackend(model_config=cfg, batch_size=4, max_new_tokens=6,
                    interpret=True, quantize_kv=False)
    texts = be.generate(_prompts(lens))
    st = be.stats
    assert st.decode_kv_blocks_total > 0
    assert st.decode_kv_blocks_paired == (
        st.decode_kv_blocks_total if paired else 0)
    for account in (be.engine_counters(), be.engine_record()):
        assert account["decode_kv_blocks_paired"] == st.decode_kv_blocks_paired
        assert account["decode_kv_blocks"] == st.decode_kv_blocks_total
    cache = jax.eval_shape(lambda: be._init_cache(4, 136))
    assert cache["k"].shape == ((2, 4, 1, 136, 128) if paired
                                else (2, 4, 2, 136, 128))
    dense = TpuBackend(model_config=cfg, batch_size=4, max_new_tokens=6,
                       flash=False, params=be.params)
    assert dense.generate(_prompts(lens)) == texts
    assert dense.stats.decode_kv_blocks_total == 0


@pytest.mark.parametrize("model_axis,paired", [(2, True), (4, False)])
def test_a_mesh_keeps_a_pair_inside_a_shard_or_stores_a_head_a_tile(
        model_axis, paired):
    """4 KV heads of 64 under a tensor axis of 2 (a pair a shard: the
    kernels run through ``ops/sharded.py`` on each shard's own tiles and
    scales) and of 4 (a pair would be split: one head a tile, by the
    shape), with the prefix pool on: the single device's texts, cold and
    resumed, and the pool shaped as the cache."""
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models.llama import tiny_llama
    from vnsum_tpu.parallel import make_mesh

    cfg = tiny_llama(head_dim=64, n_heads=8, n_kv_heads=4,
                     max_seq_len=256 + 8)
    lens = [40, 250, 131, 77]
    kw = dict(model_config=cfg, batch_size=4, max_new_tokens=6,
              interpret=True, cache_blocks=32, cache_block_tokens=16)
    single = TpuBackend(**kw)
    want = single.generate(_prompts(lens))
    mesh = make_mesh({"data": 1, "model": model_axis, "seq": 1},
                     platform="cpu")
    be = TpuBackend(mesh=mesh, params=single.params, **kw)
    cache = jax.eval_shape(lambda: be._init_cache(4, 264))
    assert cache["k"].shape == ((2, 4, 2, 264, 128) if paired
                                else (2, 4, 4, 264, 64))
    assert cache["ks"].shape == (2, 4, 4, 264)
    for name, leaf in cache.items():
        assert be.prefix_cache.store.pool[name].shape[2:] == (
            leaf.shape[2], 16) + leaf.shape[4:]
    assert be.generate(_prompts(lens)) == want            # cold
    assert be.generate(_prompts(lens)) == want            # from the pool
    assert sum(be.take_cache_report()) > 0
    st = be.stats
    assert st.decode_kv_blocks_paired == (
        st.decode_kv_blocks_total if paired else 0)
    assert st.decode_kv_blocks_total > 0
