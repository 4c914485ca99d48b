"""The DeepSeek-V2 family on the CPU at tiny sizes: routing, the three
kernels (interpreted) against the dense formulations, the engine's one-shot
program through the family seam, the expert counters, the int8 layout of
stacked experts, and the entries that refuse the family by name."""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_harness import engine
from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.models import MODEL_REGISTRY, jitted_init
from vnsum_tpu.models import deepseek as ds
from vnsum_tpu.models.family import family_of
from vnsum_tpu.models.quant import (
    dequantize_params,
    init_params_quantized,
    is_quantized,
    quantize_params,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = ds.tiny_deepseek()
    return cfg, jitted_init(ds.init_params, cfg, 0)


# -- the config and the registry ---------------------------------------------


def test_registry_holds_the_uncut_published_model():
    cfg = MODEL_REGISTRY["deepseek-v2"]()
    assert (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.vocab_size) == (
        60, 5120, 128, 102_400)
    assert (cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.latent_width) == (
        512, 64, 576)
    assert (cfg.n_routed_experts, cfg.n_held, cfg.num_experts_per_tok) == (
        160, 160, 6)
    assert family_of(cfg).name == "deepseek-v2"
    assert family_of(MODEL_REGISTRY["qwen3-8b"]()).name == "llama"


def test_softmax_scale_carries_yarns_m_squared():
    cfg = ds.deepseek_v2()
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert m * m == pytest.approx(1.5896, abs=1e-3)


def test_yarn_frequencies_blend_between_the_correction_dimensions():
    """Hand-worked for the published values: dim 64, base 10000, original
    4096: correction dims floor(10.29) = 10 (32 rotations) and ceil(22.34)
    = 23 (one rotation). Pairs below 10 keep their frequency, pairs from 23
    on are divided by 40, pair 16 is 6/13 of the way."""
    cfg = ds.deepseek_v2()
    inv = np.asarray(ds.yarn_inv_freq(cfg))
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40.0, rtol=1e-6)
    ramp = 6 / 13
    np.testing.assert_allclose(
        inv[16], plain[16] / 40.0 * ramp + plain[16] * (1 - ramp), rtol=1e-6)


def test_a_share_past_the_last_expert_is_refused():
    with pytest.raises(ValueError, match="past n_routed_experts"):
        ds.tiny_deepseek(expert_offset=12, experts_held=8)


# -- routing -----------------------------------------------------------------


def test_router_drops_the_picks_of_the_fourth_group():
    """8 groups of 2, keep 3 groups, pick 6: a token whose six largest
    scores lie in groups 0, 1, 2 and 3 (the fourth by its best member)
    loses the one in group 3 to the next best inside the kept groups."""
    cfg = ds.tiny_deepseek(n_routed_experts=16, n_group=8, topk_group=3,
                           num_experts_per_tok=6, routed_scaling_factor=16.0)
    scores = np.full((1, 16), 0.001, np.float32)
    # group g holds experts 2g, 2g + 1
    scores[0, [0, 1]] = [0.20, 0.15]      # group 0
    scores[0, [2, 3]] = [0.18, 0.02]      # group 1
    scores[0, [4, 5]] = [0.16, 0.01]      # group 2
    scores[0, [6, 7]] = [0.14, 0.003]     # group 3: fourth by its best
    # the six largest overall: 0, 2, 4, 1, 6, 3 -> 6 is in group 3
    ids, weights = ds.route(jnp.asarray(scores), cfg)
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 1, 2, 3, 4, 5]
    assert 6 not in np.asarray(ids[0])
    order = np.argsort(-np.asarray(weights[0]))
    assert np.asarray(ids[0])[order].tolist() == [0, 2, 4, 1, 3, 5]
    # weights are the scores times the scaling factor, not renormalised
    np.testing.assert_allclose(
        np.sort(np.asarray(weights[0]))[::-1],
        16.0 * np.array([0.20, 0.18, 0.16, 0.15, 0.02, 0.01]), rtol=1e-6)


# -- the kernels against the dense formulations -------------------------------


_PREFILL_CASES = [
    (3, 64, 0, [0, 17], 32, 32),       # whole prompt, one row padded
    (3, 48, 80, [0, 100], 32, 32),     # a later chunk; row 1's pad covers 20 queries
    (3, 40, 24, [3, 5], 32, 32),       # lengths that leave partial blocks
    # the cell of PR 34: a group of heads a step, two of them a loop step
    (5, 64, 0, [0, 17], 32, 32),       # a prime H: one group of five, a head a loop step
    (16, 64, 64, [0, 30], 32, 64),     # two groups of eight
    (10, 64, 0, [40, 0], 64, 64),      # two groups of five; row 0's first rows under its pad
    # key blocks wider than query blocks
    (3, 128, 0, [0, 64], 32, 128),     # the diagonal crosses every block; a pad of half a block
    (3, 48, 80, [0, 70], 32, 128),     # a pad that ends inside the one wide block
    (4, 64, 128, [0, 200], 32, 128),   # block 0 interior for every query block, then an edge block; row 1's pad reaches into the chunk
    (3, 64, 64, [0, 128], 32, 128),    # row 1 wholly under its pad
    (3, 96, 64, [0, 9], 32, 128),      # 160 keys: the last key block is partial, its buffer past the end is NaN
    (2, 100, 60, [0, 33], 32, 128),    # the same with a partial last query block
    (3, 128, 128, [0, 140], 64, 128),  # row 1's pad covers key block 0 whole and the first query block
]


def _prefill_operands(H, S, T, B=2, rank=32, dn=16, dr=8, dv=16):
    """Queries, latent rows and the two halves of ``W_kvb`` a head, and the
    keys and values the reference expands from them by einsum."""
    ks = jax.random.split(jax.random.key(S), 5)
    qn = jax.random.normal(ks[0], (B, H, S, dn))
    qr = jax.random.normal(ks[1], (B, H, S, dr))
    lat = jax.random.normal(ks[2], (B, T, rank + dr))
    wk = jax.random.normal(ks[3], (H, rank, dn)) * rank ** -0.5
    wv = jax.random.normal(ks[4], (H, rank, dv)) * rank ** -0.5
    kn = jnp.einsum("btc,hck->bhtk", lat[..., :rank], wk)
    v = jnp.einsum("btc,hck->bhtk", lat[..., :rank], wv)
    return qn, qr, lat, wk, wv, kn, lat[..., rank:], v


def _dense_prefill(qn, qr, kn, kr, v, pad, offset, scale):
    """Masked softmax attention over expanded keys and values, and which
    query rows are real [B, S]."""
    S, T = qn.shape[2], kn.shape[2]
    s = (jnp.einsum("bhsk,bhtk->bhst", qn, kn)
         + jnp.einsum("bhsk,btk->bhst", qr, kr)) * scale
    q_pos = offset + jnp.arange(S)[:, None]
    k_pos = jnp.arange(T)[None, :]
    mask = (k_pos <= q_pos)[None] & (k_pos[None] >= pad[:, None, None])
    want = jnp.einsum(
        "bhst,bhtk->bhsk",
        jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), -1), v)
    return want, np.asarray(q_pos[None, :, 0] >= pad[:, None])


@pytest.mark.parametrize("H,S,offset,pads,bq,bk", _PREFILL_CASES)
def test_prefill_kernel_matches_dense_attention(H, S, offset, pads, bq, bk):
    """The kernel takes the latent rows and the weights; the reference
    expands keys and values by einsum, as the caller did before PR 44."""
    from vnsum_tpu.ops.mla_attention import mla_prefill_attention

    qn, qr, lat, wk, wv, kn, kr, v = _prefill_operands(H, S, offset + S)
    pad = jnp.asarray(pads, jnp.int32)
    got = mla_prefill_attention(qn, qr, lat, wk, wv, pad, scale=0.2,
                                q_offset=offset, block_q=bq, block_k=bk,
                                interpret=True)
    want, real = _dense_prefill(qn, qr, kn, kr, v, pad, offset, 0.2)
    for b in range(qn.shape[0]):
        np.testing.assert_allclose(
            np.asarray(got)[b][:, real[b]], np.asarray(want)[b][:, real[b]],
            atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_a_key_block_under_the_pad_expands_nothing():
    """Row 1's pad covers key block 0 whole: the kernel neither fetches nor
    expands it, so NaN latent rows there reach no output; and rows of the
    partial last block past the keys' end are zeroed before the expansion
    (the interpreter fills them with NaN)."""
    from vnsum_tpu.ops.mla_attention import mla_prefill_attention

    H, S, offset, pads, bq, bk = 3, 96, 64, [0, 140], 32, 128
    qn, qr, lat, wk, wv, kn, kr, v = _prefill_operands(H, S, offset + S)
    pad = jnp.asarray(pads, jnp.int32)
    want, real = _dense_prefill(qn, qr, kn, kr, v, pad, offset, 0.2)
    poisoned = lat.at[1, :bk].set(jnp.nan)
    got = mla_prefill_attention(qn, qr, poisoned, wk, wv, pad, scale=0.2,
                                q_offset=offset, block_q=bq, block_k=bk,
                                interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(got)[b][:, real[b]], np.asarray(want)[b][:, real[b]],
            atol=2e-5)


@pytest.mark.parametrize("H,S,offset,pads,bq,bk", _PREFILL_CASES)
def test_prefill_kernel_reads_the_stacked_cache_in_place(H, S, offset, pads,
                                                         bq, bk):
    """PR 53: handed the stacked cache, a layer and the queries' first batch
    row, the kernel gives bit for bit what it gives for those rows sliced
    out — with NaN in every other layer, every other batch row and every
    slot past the keys, so nothing but the rows' own T slots is read."""
    from vnsum_tpu.ops.mla_attention import mla_prefill_attention

    T = offset + S
    qn, qr, lat, wk, wv, *_ = _prefill_operands(H, S, T)
    pad = jnp.asarray(pads, jnp.int32)
    kw = dict(scale=0.2, q_offset=offset, block_q=bq, block_k=bk,
              interpret=True)
    want = mla_prefill_attention(qn, qr, lat, wk, wv, pad, **kw)
    # layer 1 of 3, the two rows at batch rows 2 and 3 of 5, eleven slots
    # more than the keys
    cache = np.full((3, 5, T + 11, lat.shape[-1]), np.nan, np.float32)
    cache[1, 2:4, :T] = np.asarray(lat)
    got = mla_prefill_attention(qn, qr, jnp.asarray(cache), wk, wv, pad,
                                layer_idx=jnp.int32(1),
                                row_offset=jnp.int32(2), **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_stacked_cache_has_to_hold_the_keys():
    from vnsum_tpu.ops.mla_attention import mla_prefill_attention

    qn, qr, lat, wk, wv, *_ = _prefill_operands(3, 64, 96)
    pad = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="95 keys for queries at"):
        mla_prefill_attention(qn, qr, lat[:, :95], wk, wv, pad, scale=0.2,
                              q_offset=32, interpret=True)
    with pytest.raises(ValueError, match="a cache of 95 slots"):
        mla_prefill_attention(qn, qr, lat[None, :, :95], wk, wv, pad,
                              scale=0.2, q_offset=32, layer_idx=0,
                              interpret=True)


def _brute_force_tiles(pads, S, T, offset, bq, bk):
    """Class counts of the (bq x bk) tiles from the mask itself."""
    from vnsum_tpu.ops.mla_attention import TILE_CLASSES

    counts = dict.fromkeys(TILE_CLASSES, 0)
    for pad in pads:
        for q0 in range(offset, offset + S, bq):
            q = q0 + np.arange(bq)[:, None]
            for k0 in range(0, T, bk):
                k = k0 + np.arange(bk)[None, :]
                mask = (k <= q) & (k >= pad)
                if not mask.any():
                    counts["dead_causal" if k0 > q0 + bq - 1
                           else "dead_pad"] += 1
                else:
                    counts["interior" if mask.all() else "masked"] += 1
    return counts


@pytest.mark.parametrize("H,S,offset,pads,bq,bk", _PREFILL_CASES)
def test_prefill_tile_classes_match_a_brute_force_mask(H, S, offset, pads,
                                                       bq, bk):
    from vnsum_tpu.ops.mla_attention import prefill_tile_classes

    T = offset + S
    got = prefill_tile_classes(pads, S, T, offset, block_q=bq, block_k=bk)
    assert got["tile"] == (min(bq, S), min(bk, T))
    want = _brute_force_tiles(pads, S, T, offset, *got["tile"])
    assert {c: got[c] for c in want} == want
    q = offset + np.arange(S)[None, :, None]
    k = np.arange(T)[None, None, :]
    needed = (k <= q) & (k >= np.asarray(pads)[:, None, None])
    assert got["scores_needed"] == int(needed.sum())
    assert got["scores_computed"] == (
        (want["interior"] + want["masked"]) * int(np.prod(got["tile"])))


def _keys_expanded_by_brute_force(pads, S, T, offset, bq, bk):
    """Keys a call expands a head, from the mask itself: bk for every
    (bq x bk) tile that holds a score some query may see."""
    tiles = _brute_force_tiles(pads, S, T, offset, bq, bk)
    return bk * (tiles["interior"] + tiles["masked"])


@pytest.mark.parametrize("H,S,offset,pads,bq,bk", _PREFILL_CASES)
def test_latent_keys_expanded_match_a_brute_force_mask(H, S, offset, pads,
                                                       bq, bk):
    """The counter of PR 44: a computed tile expands its key block, a dead
    one nothing; the family's hook counts the same at the wrapper's own
    geometry, x layers, beside the keys the rows have."""
    from vnsum_tpu.ops.mla_attention import prefill_tile_classes

    T = offset + S
    got = prefill_tile_classes(pads, S, T, offset, block_q=bq, block_k=bk)
    assert got["keys_expanded"] == _keys_expanded_by_brute_force(
        pads, S, T, offset, *got["tile"])
    cfg = ds.tiny_deepseek()
    counted = ds.prefill_counts(cfg, pads, [(offset, T)])
    tile = prefill_tile_classes(pads, S, T, offset)["tile"]
    assert counted["latent_keys_expanded"] == cfg.n_layers * (
        _keys_expanded_by_brute_force(pads, S, T, offset, *tile))
    assert counted["latent_keys_real"] == cfg.n_layers * sum(
        max(T - pad, 0) for pad in pads)


def test_a_map_dispatch_expands_every_key_four_and_a_half_times():
    """The cell's map dispatch: rows of ~7.8k tokens behind their pads in
    the 8192 bucket, eight chunks of 1,024. Chunk c reads the c + 1 key
    blocks before its end, so a full row's 8 blocks are expanded 36 times:
    the kernel hides the redundancy under its softmax, it does not remove
    it."""
    cfg = ds.deepseek_v2(n_layers=8)
    spans = [(lo, lo + 1024) for lo in range(0, 8192, 1024)]
    full = ds.prefill_counts(cfg, [0], spans)
    assert full == {"latent_keys_expanded": 8 * 36 * 1024,
                    "latent_keys_real": 8 * 8192}
    pads = [392 + 16 * r for r in range(24)]            # 7,800 tokens and fewer
    got = ds.prefill_counts(cfg, pads, spans)
    ratio = got["latent_keys_expanded"] / got["latent_keys_real"]
    assert got["latent_keys_real"] == 8 * sum(8192 - p for p in pads)
    assert 4.5 < ratio < 5.0
    # a row wholly under its pad (a batch's filler row) expands nothing
    assert ds.prefill_counts(cfg, [8192], spans) == {
        "latent_keys_expanded": 0, "latent_keys_real": 0}


def test_a_piece_of_the_prefill_attention_is_four_rows_at_the_cells_shapes():
    """A piece holds queries and outputs alone (168 MB a row at 1,024
    queries of 128 heads): 4 rows of the map dispatch's 24 under ~0.8 GB,
    whatever the number of keys; it was 1 row while a piece held a row's
    expanded keys and values (704 MB at 8,192 keys). Six rows, the issue's
    reckoning at the old 1.2 GB, cost 114 MB more of temporaries on the
    chip and bought nothing (PERF.md section 6, PR 44)."""
    cfg = ds.deepseek_v2()
    assert ds._rows_a_piece(cfg, 24, 1024) == 4
    assert ds._rows_a_piece(cfg, 4, 1024) == 4          # the reduce: one piece
    assert ds._rows_a_piece(cfg, 1, 1024) == 1          # parity's row
    assert ds._rows_a_piece(cfg, 24, 2048) == 2


@pytest.mark.parametrize("rows_a_piece", [None, 1],
                         ids=["one-piece", "a-row-a-piece"])
@pytest.mark.parametrize("offset,pads", [(0, [0, 17]), (128, [0, 150])])
def test_int8_leaves_scales_are_folded_outside_the_kernel(
        offset, pads, rows_a_piece, monkeypatch):
    """``prefill_attention`` with int8 ``wk_b`` / ``wv_b``: the kernel gets
    the leaves' integers in the latent's type, the scales multiply the
    queries and the output (``_expanded_attention``'s rule), and the result
    is the dense path's over the same leaves — the kernel reading layer 1
    of the stacked cache in place, in one piece and a row a piece (each
    piece's keys at its own batch row: PR 53)."""
    cfg = ds.tiny_deepseek()
    if rows_a_piece:
        monkeypatch.setattr(ds, "_rows_a_piece", lambda *_: rows_a_piece)
    params = quantize_params(jitted_init(ds.init_params, cfg, 3))
    lp = jax.tree.map(lambda w: w[0], params["layers"])
    assert isinstance(lp["wk_b"], dict) and isinstance(lp["wv_b"], dict)
    B, S, C = 2, 128, 256                # offset + S <= C
    ks = jax.random.split(jax.random.key(offset), 2)
    c_q = jax.random.normal(ks[0], (B, S, cfg.q_lora_rank), cfg.dtype)
    cache = {"latent": jax.random.normal(
        ks[1], (cfg.n_layers, B, C, cfg.latent_width), cfg.dtype)}
    pad = jnp.asarray(pads, jnp.int32)
    positions = jnp.maximum(offset + jnp.arange(S)[None, :] - pad[:, None], 0)
    rope = ds.rope_cos_sin(cfg, positions)
    q_pos = offset + jnp.arange(S)[None, :, None]
    k_pos = jnp.arange(C)[None, None, :]
    mask = (k_pos <= q_pos) & (k_pos >= pad[:, None, None])
    got = ds.prefill_attention(cfg, pad, offset, interpret=True).attend(
        c_q, rope, cache, 1, lp, False)
    want = ds.dense_attention(cfg, mask).attend(c_q, rope, cache, 1, lp, False)
    real = np.asarray(q_pos[0, :, 0][None] >= pad[:, None])
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[real], np.asarray(want, np.float32)[real],
        atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("pad,waste", [(0, 1.13), (392, 1.25)])
def test_a_full_row_computes_no_tile_above_the_diagonal(pad, waste):
    """The cell's own call shapes at the geometry the wrapper chooses: the
    eight 1,024-query chunks of an 8,192 row. The tiles the kernel computes
    are those that hold a score the row needs; the scores it computes
    beyond them lie in the tiles the diagonal or the pad's end crosses."""
    from vnsum_tpu.ops.mla_attention import prefill_tile_classes

    computed = needed = 0
    for offset in range(0, 8192, 1024):
        T = offset + 1024
        got = prefill_tile_classes([pad], 1024, T, offset)
        want = _brute_force_tiles([pad], 1024, T, offset, *got["tile"])
        assert (got["interior"], got["masked"]) == (
            want["interior"], want["masked"])
        computed += got["scores_computed"]
        needed += got["scores_needed"]
    assert needed == (8192 - pad) * (8192 - pad + 1) // 2
    assert needed < computed < waste * needed


@pytest.mark.parametrize("fill,pads", [(37, [0, 9]), (99, [40, 0])])
def test_absorbed_decode_kernel_matches_dense_attention(fill, pads):
    """All heads against one latent row a token: scores over rank + rope,
    values over rank. C = 100 leaves a partial last block of 32."""
    from vnsum_tpu.ops.mla_attention import mla_decode_attention

    L, B, H, C, rank, dr = 2, 2, 4, 100, 32, 8
    ks = jax.random.split(jax.random.key(fill), 3)
    ql = jax.random.normal(ks[0], (B, H, rank))
    qr = jax.random.normal(ks[1], (B, H, dr))
    cache = jax.random.normal(ks[2], (L, B, C, rank + dr))
    pad = jnp.asarray(pads, jnp.int32)
    got = mla_decode_attention(ql, qr, cache, 1, pad, fill, scale=0.3,
                               rank=rank, block_k=32, interpret=True)
    lat = cache[1]
    s = (jnp.einsum("bhc,btc->bht", ql, lat[..., :rank])
         + jnp.einsum("bhk,btk->bht", qr, lat[..., rank:])) * 0.3
    k_pos = jnp.arange(C)[None, None, :]
    mask = (k_pos <= fill) & (k_pos >= pad[:, None, None])
    want = jnp.einsum("bht,btc->bhc",
                      jax.nn.softmax(jnp.where(mask, s, -1e30), -1),
                      lat[..., :rank])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _expert_inputs(cfg, T, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(ks[0], (T, cfg.dim))
    scores = jax.nn.softmax(
        jax.random.normal(ks[1], (T, cfg.n_routed_experts)) * 2, -1)
    ids, weights = ds.route(scores, cfg)
    local = ids - cfg.expert_offset
    held = (local >= 0) & (local < cfg.n_held)
    return x, jnp.where(held, local, -1), weights


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("held,offset", [(16, 0), (8, 8)])
def test_grouped_experts_match_the_dense_sum(quantized, held, offset):
    cfg = ds.tiny_deepseek(experts_held=held, expert_offset=offset,
                           w8a8_prefill=quantized)
    params = jitted_init(ds.init_params, cfg, 3)
    if quantized:
        params = quantize_params(params)
    experts = {n: params["layers"][n] for n in ds._EXPERTS}
    x, local, weights = _expert_inputs(cfg, 70)
    want = ds.dense_experts(x, local, weights, experts, 1, cfg)
    got = ds.grouped_experts(x, local, weights, experts, 1, cfg,
                             interpret=True)
    # int8 rows round the activations twice; float rows only reorder sums
    tol = 0.03 * float(jnp.abs(want).max()) if quantized else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)
    assert float(jnp.abs(want).max()) > 0.01


def test_no_token_is_dropped_when_every_token_picks_one_expert():
    """Every pick of every token on expert 2: the worst case for a scheme
    with a capacity. The grouped product has none."""
    cfg = ds.tiny_deepseek()
    params = jitted_init(ds.init_params, cfg, 4)
    experts = {n: params["layers"][n] for n in ds._EXPERTS}
    T, k = 300, cfg.num_experts_per_tok
    x = jax.random.normal(jax.random.key(5), (T, cfg.dim))
    local = jnp.full((T, k), 2, jnp.int32)
    weights = jnp.full((T, k), 1.5, jnp.float32)
    got = ds.grouped_experts(x, local, weights, experts, 0, cfg,
                             interpret=True)
    w = {n: experts[n][0, 2] for n in ds._EXPERTS}
    one = (jax.nn.silu(x @ w["we_gate"]) * (x @ w["we_up"])) @ w["we_down"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(one * 1.5 * k),
                               atol=2e-5)


def _layout_case(name):
    rng = np.random.default_rng(7)
    return {
        # (slots, experts, tm)
        "mixed": ([3, -1, 0, 3, 3, 1, -1, 0, 3], 4, 2),
        "all_on_one_expert": ([2] * 37, 5, 8),
        "no_slot_held": ([-1] * 12, 3, 4),
        "group_ends_on_a_tile_edge": ([0] * 8 + [1] * 4 + [-1] + [2] * 12, 3, 4),
        "one_slot": ([0], 1, 16),
        "random_wide": (rng.integers(-1, 64, 600).tolist(), 64, 32),
        "random_mostly_not_held": (
            np.where(rng.random(500) < 0.76, -1,
                     rng.integers(0, 40, 500)).tolist(), 40, 16),
        **_DECODE_STEPS,
    }[name]


def _decode_steps():
    """A decode step's slots in the three cells' proportions, in small:
    Laguna's 120 slots on 256 experts, SmallThinker's 144 on 64,
    DeepSeek-V2's 144 of which a quarter is held among 40, and the two
    ends: every slot on one expert, no slot held."""
    rng = np.random.default_rng(42)
    return {
        "step_more_experts_than_slots": (
            rng.integers(0, 64, 30).tolist(), 64, 32),
        "step_fewer_experts_than_slots": (
            rng.integers(0, 16, 72).tolist(), 16, 16),
        "step_mostly_not_held": (
            np.where(rng.random(72) < 0.76, -1,
                     rng.integers(0, 10, 72)).tolist(), 10, 32),
        "step_every_slot_on_one_expert": ([5] * 30, 64, 16),
        "step_no_slot_held": ([-1] * 24, 64, 32),
    }


_DECODE_STEPS = _decode_steps()


@pytest.mark.parametrize("case", [
    "mixed", "all_on_one_expert", "no_slot_held", "group_ends_on_a_tile_edge",
    "one_slot", "random_wide", "random_mostly_not_held", *_DECODE_STEPS])
def test_expert_layout_gives_every_slot_a_row_of_its_expert(case):
    """The layout's contract: every held slot a row of its own in a tile of
    its expert, in the slots' order inside a group; groups padded to whole
    tiles from a tile's edge; a spare row for the slots not held; each row
    names its slot, padding names none; static M at the worst case, which
    follows the slots: a used tile holds one, so no more tiles than slots
    and no more than the whole tiles plus one an expert."""
    from vnsum_tpu.ops.expert_matmul import expert_layout

    slots, E, tm = _layout_case(case)
    slots = np.asarray(slots, np.int32)
    carry = np.arange(1, len(slots) + 1, dtype=np.float32) * 0.5
    row, slot_of_row, tile_expert, used, sizes, M, (carried,) = expert_layout(
        jnp.asarray(slots), E, tm, [jnp.asarray(carry)])
    row, slot_of_row, tile_expert, sizes = (
        np.asarray(a) for a in (row, slot_of_row, tile_expert, sizes))
    N = len(slots)
    assert M == (min(N, N // tm + E) + 1) * tm == len(slot_of_row)
    assert len(tile_expert) == M // tm
    want_sizes = [int((slots == e).sum()) for e in range(E)]
    assert sizes.tolist() == want_sizes
    n_used = sum(-(-s // tm) for s in want_sizes)
    assert int(used[0]) == n_used <= M // tm - 1     # the spare tile is spare
    held = slots >= 0
    assert len(set(row[held])) == held.sum()          # no two slots share a row
    for r, e in zip(row[held], slots[held]):
        assert tile_expert[r // tm] == e and r < n_used * tm
    assert (row[~held] == M - 1).all() and M - 1 >= n_used * tm
    # a group starts on a tile's edge and keeps the slots' order
    start = 0
    for e, size in enumerate(want_sizes):
        mine = np.flatnonzero(slots == e)
        assert row[mine].tolist() == list(range(start, start + size))
        start += -(-size // tm) * tm
    # the permutation read from the rows' side
    assert (slot_of_row[row[held]] == np.flatnonzero(held)).all()
    assert (slot_of_row >= 0).sum() == held.sum()
    assert slot_of_row.min() >= -1 and slot_of_row.max() < N
    # what a slot carries arrives on its row, 0 on padding
    assert (np.asarray(carried) == np.where(
        slot_of_row >= 0, carry[np.maximum(slot_of_row, 0)], 0)).all()
    if case == "mixed":
        assert want_sizes == [2, 1, 0, 4] and n_used == 1 + 1 + 0 + 2


def _static_grid_product(lhs, lhs_scale, w, w_up, layer, tile_expert,
                         tiles_used, *, tm, tn, out_dtype, act="silu"):
    """The product as it was before its grid followed the slots, kept as the
    plain reference: the kernel's own body on EVERY row tile of the layout,
    a tile past ``tiles_used`` skipped by ``pl.when`` with its block index
    repeated. The grid step is still taken."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from vnsum_tpu.ops import expert_matmul as em

    M, K = lhs.shape
    quantized = isinstance(w, dict)
    N = (w["q"] if quantized else w).shape[-1]
    body = functools.partial(
        em._kernel, quantized=quantized, int8_lhs=lhs.dtype == jnp.int8,
        scaled=lhs_scale is not None, gated=w_up is not None, act=act)

    def kernel(layer_ref, te_ref, used_ref, *refs):
        @pl.when(pl.program_id(1) < used_ref[0])
        def _compute():
            body(layer_ref, te_ref, *refs)

    def tile(m, used):
        return jnp.minimum(m, jnp.maximum(used[0] - 1, 0))

    row = lambda n, m, layer, te, used: (tile(m, used), 0)  # noqa: E731
    weight = lambda n, m, layer, te, used: (  # noqa: E731
        layer[0], te[tile(m, used)], 0, n)
    in_specs, operands = [pl.BlockSpec((tm, K), row)], [lhs]
    if lhs_scale is not None:
        in_specs.append(pl.BlockSpec((tm, 1), row))
        operands.append(lhs_scale)
    for leaf in (w, w_up) if w_up is not None else (w,):
        in_specs.append(pl.BlockSpec((1, 1, K, tn), weight))
        operands.append(leaf["q"] if quantized else leaf)
        if quantized:
            in_specs.append(pl.BlockSpec((1, 1, 1, tn), weight))
            operands.append(leaf["s"][:, :, None, :])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N // tn, M // tm),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, m, layer, te, used: (tile(m, used), n))),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype), interpret=True,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_expert, tiles_used,
      *operands)


def _product_operands(case, rows, gated):
    """One product's operands over a decode step's layout: K = 64 in, two
    column tiles of 128 out, a stack of 2 layers."""
    from vnsum_tpu.ops.expert_matmul import expert_layout

    slots, E, _ = _layout_case(case)
    tm = 32 if rows == "int8" else 16
    _, _, tile_expert, used, _, M, _ = expert_layout(
        jnp.asarray(slots, jnp.int32), E, tm)
    ks = jax.random.split(jax.random.key(len(slots) + E), 6)
    K, N = 64, 256

    def leaf(key):
        if rows == "int8":
            return {"q": jax.random.randint(key, (2, E, K, N), -127, 128,
                                            jnp.int8),
                    "s": jax.random.uniform(key, (2, E, N), jnp.float32,
                                            1e-3, 2e-3)}
        return (jax.random.normal(key, (2, E, K, N)) * 0.1).astype(
            jnp.bfloat16)

    if rows == "int8":
        lhs = jax.random.randint(ks[0], (M, K), -127, 128, jnp.int8)
    else:
        lhs = jax.random.normal(ks[0], (M, K)).astype(jnp.bfloat16)
    scale = jax.random.uniform(ks[1], (M, 1), jnp.float32, 0.01, 0.02)
    args = (lhs, scale, leaf(ks[2]), leaf(ks[3]) if gated else None, 1,
            tile_expert, used)
    return args, dict(tm=tm, tn=128, out_dtype=jnp.bfloat16, act="silu"), \
        (len(slots), E, tm, int(used[0]), M)


@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("rows", ["int8", "bf16"])
@pytest.mark.parametrize("case", list(_DECODE_STEPS))
def test_the_product_on_the_used_tiles_is_the_static_grids_bit_for_bit(
        case, rows, gated):
    """A grid that walks only the tiles that hold a slot gives the rows of
    those tiles what the grid over every tile gave them, to the bit: the
    same products, scales and order of sums, int8 and bf16 rows, gated and
    not. With no slot held there is no grid step and nothing to compare."""
    from vnsum_tpu.ops.expert_matmul import expert_grouped_matmul

    args, kw, (N, E, tm, used, M) = _product_operands(case, rows, gated)
    got = expert_grouped_matmul(*args, interpret=True, **kw)
    want = _static_grid_product(*args, **kw)
    assert got.shape == want.shape == (M, 256) and got.dtype == want.dtype
    live = used * tm
    assert np.array_equal(np.asarray(got[:live].astype(jnp.float32)),
                          np.asarray(want[:live].astype(jnp.float32)))
    if used:
        assert float(jnp.abs(got[:live].astype(jnp.float32)).max()) > 0
    if case == "step_more_experts_than_slots":
        # what the static grid walked, and what part of it was live: the
        # worst-case tiles of every expert for a few dozen slots
        walked = -(-N // tm) + E + 1
        assert used / walked < 0.4 and M // tm == N + 1 < walked


@pytest.mark.parametrize("case", ["step_more_experts_than_slots",
                                  "step_no_slot_held"])
def test_the_products_grid_is_bounded_by_the_tiles_used(case):
    """The row dimension of the grid is ``tiles_used`` itself, a bound read
    when the kernel starts: walked = used, whatever the layout's static
    worst case, and a step with no slot held takes no grid step at all (it
    runs, reads nothing, and nobody reads its output)."""
    from vnsum_tpu.ops.expert_matmul import expert_grouped_matmul

    args, kw, (_, _, _, used, M) = _product_operands(case, "int8", True)
    jaxpr = jax.make_jaxpr(lambda *a: expert_grouped_matmul(
        *a, args[3], 1, *args[5:], interpret=True, **kw))(*args[:3])

    def eqns(j):
        for e in j.eqns:
            yield e
            for v in e.params.values():
                if hasattr(getattr(v, "jaxpr", v), "eqns"):
                    yield from eqns(getattr(v, "jaxpr", v))

    (call,) = [e for e in eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    mapping = call.params["grid_mapping"]
    assert mapping.num_dynamic_grid_bounds == 1
    assert [d for d in mapping.grid if isinstance(d, int)] == [2]
    out = expert_grouped_matmul(*args, interpret=True, **kw)
    assert out.shape == (M, 256)
    if not used:
        poisoned = (jnp.full_like(args[0], 127), args[1],
                    *args[2:5], jnp.full_like(args[5], 10**6), args[6])
        assert expert_grouped_matmul(
            *poisoned, interpret=True, **kw).shape == (M, 256)


# -- the program through the engine ------------------------------------------


def _two_rows(cfg, params, **kw):
    """The harness's engine at this file's own defaults (two rows, whole
    prompts, the cache's type left to the engine), one a test: most of the
    tests below count what their own calls added to ``stats``."""
    return engine(cfg, params, fresh=True, **{
        "batch_size": 2, "prefill_chunk_tokens": 0, "quantize_kv": "auto",
        **kw})


def test_generate_runs_the_kernels_and_counts_the_experts(tiny):
    cfg, params = tiny
    be = _two_rows(cfg, params, prefill_chunk_tokens=128)
    outs = be.generate(["xin chao " * 30, "hello"], max_new_tokens=8)
    assert len(outs) == 2
    assert be.stats.attention_paths == {
        "generate[B=2,S=248]": {"prefill": "kernel", "decode": "kernel"}}
    st = be.stats
    # every real token of the prompts and of 8 steps of both rows, 3 picks
    # in each of the 2 expert layers; pad tokens are routed nowhere
    tokens = st.prompt_tokens + 2 * 8
    assert st.expert_slots_routed == tokens * 3 * 2
    assert st.expert_slots_held == st.expert_slots_routed   # all 16 held
    assert np.asarray(st.expert_tokens).shape == (2, 16)
    assert int(np.sum(st.expert_tokens)) == st.expert_slots_held
    be.generate(["them"], max_new_tokens=8)                 # they add up
    assert int(np.sum(st.expert_tokens)) == st.expert_slots_held > tokens * 6


def test_a_dispatch_counts_the_keys_its_prefill_expanded(tiny):
    """``EngineStats.prefill_blocks`` and the dispatch's INFO line carry the
    family's own counts (``Family.prefill_counts``), though it counts no GQA
    cell: host arithmetic on the pads the dispatch was packed with."""
    import logging

    cfg, params = tiny
    be = _two_rows(cfg, params, prefill_chunk_tokens=128)
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    logger = logging.getLogger("vnsum.engine")
    logger.addHandler(handler)
    try:
        be.generate(["xin chao " * 30, "hello"], max_new_tokens=8)
    finally:
        logger.removeHandler(handler)
    assert not family_of(cfg).counts_prefill_blocks
    S = 248                          # the first prompt fills its bucket
    pads = [0, S - (be.stats.prompt_tokens - S)]
    want = ds.prefill_counts(cfg, pads, [(0, 128), (128, S)])
    assert be.stats.prefill_blocks == want
    assert want["latent_keys_real"] == cfg.n_layers * be.stats.prompt_tokens
    said = [ln for ln in lines if ln.startswith("dispatch B=2 S=248")]
    assert len(said) == 1
    assert (f"latent_keys_expanded {want['latent_keys_expanded']}, "
            f"latent_keys_real {want['latent_keys_real']}") in said[0]
    be.generate(["them"], max_new_tokens=8)                 # they add up
    assert be.stats.prefill_blocks["latent_keys_real"] > want["latent_keys_real"]


def test_a_share_counts_only_the_experts_it_holds():
    cfg = ds.tiny_deepseek(experts_held=4, expert_offset=4)
    be = _two_rows(cfg, jitted_init(ds.init_params, cfg, 0))
    be.generate(["xin chao " * 20], max_new_tokens=8)
    st = be.stats
    assert 0 < st.expert_slots_held < st.expert_slots_routed
    assert np.asarray(st.expert_tokens).shape == (2, 4)


@pytest.mark.parametrize("quantize", [False, True])
def test_kernel_path_and_dense_path_agree(tiny, quantize):
    """The same prompt through the kernels (chunked prefill, absorbed
    decode, grouped experts) and through the dense XLA path."""
    cfg, params = tiny
    ids = list(range(5, 155))
    forced = [7, 8, 9]
    a = _two_rows(cfg, params, prefill_chunk_tokens=128, quantize=quantize)
    b = _two_rows(cfg, params, flash=False, interpret=False, quantize=quantize)
    got = a.prefill_then_decode_logits(ids, forced, bucket=256)
    want = b.prefill_then_decode_logits(ids, forced, bucket=256)
    assert got.shape == (4, cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert a.stats.attention_paths["logits[B=1,S=256]"] == {
        "prefill": "kernel", "decode": "kernel"}
    assert b.stats.attention_paths["logits[B=1,S=256]"] == {
        "prefill": "dense", "decode": "dense"}


def test_the_dense_families_logits_entry_matches_their_forward():
    """``prefill_then_decode_logits`` is the engine's, not this family's."""
    from vnsum_tpu.models import init_params, tiny_llama
    from vnsum_tpu.models.llama import forward_train

    cfg = tiny_llama()
    params = init_params(jax.random.key(0), cfg)
    be = TpuBackend(model_config=cfg, tokenizer="byte", batch_size=2,
                    max_new_tokens=8, params=params, flash=False)
    ids, forced = list(range(3, 40)), [4, 5]
    got = be.prefill_then_decode_logits(ids, forced, bucket=64)
    want = forward_train(params, cfg, jnp.asarray([ids + forced]),
                         remat=False)[0, len(ids) - 1:]
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)


# -- what refuses the family, by name ----------------------------------------


def test_slot_loop_prefix_cache_mesh_and_speculation_refuse_the_family(tiny):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="prefix cache.*no KV heads"):
        _two_rows(cfg, params, cache_blocks=8)
    with pytest.raises(NotImplementedError, match="mesh.*no expert axis"):
        _two_rows(cfg, params, mesh=object())
    with pytest.raises(ValueError, match="cache has no int8 form"):
        _two_rows(cfg, params, quantize_kv=True)
    be = _two_rows(cfg, params)
    assert be.quantize_kv is False
    with pytest.raises(NotImplementedError, match="slot loop.*latent cache"):
        be.start_slot_loop(slots=2)
    with pytest.raises(NotImplementedError, match="slot loop"):
        be._get_seg_fn("slot_prefill", 2, 64, 8, be.gen_cfg)
    from vnsum_tpu.backend.long_context import LongContextBackend
    from vnsum_tpu.core.config import GenerationConfig

    with pytest.raises(NotImplementedError, match="speculative decoding"):
        be.generate(["a"], references=["a"],
                    config=GenerationConfig(spec_k=2))
    with pytest.raises(NotImplementedError, match="long-context backend"):
        LongContextBackend(model_config=cfg, params=params,
                           decode_kernel=False)


# -- int8 leaves of the family ------------------------------------------------


def test_stacked_experts_get_a_scale_per_expert_and_channel(tiny):
    cfg, params = tiny
    q = quantize_params(params)
    assert is_quantized(q)
    L, E, D, F = cfg.n_expert_layers, cfg.n_held, cfg.dim, cfg.moe_intermediate
    assert q["layers"]["we_gate"]["q"].shape == (L, E, D, F)
    assert q["layers"]["we_gate"]["s"].shape == (L, E, F)
    assert q["layers"]["we_down"]["s"].shape == (L, E, D)
    assert q["layers"]["wk_b"]["s"].shape == (L, cfg.n_heads,
                                              cfg.qk_nope_head_dim)
    assert q["dense"]["w_gate"]["s"].shape == (1, cfg.intermediate)
    assert not isinstance(q["layers"]["router"], dict)   # full precision
    back = dequantize_params(q)
    for group in ("dense", "layers"):
        for name, w in params[group].items():
            err = float(jnp.abs(back[group][name] - w).max())
            assert err <= float(jnp.abs(w).max()) / 127 + 1e-9, name


def test_quantized_init_has_the_layout_of_quantize_params(tiny):
    cfg, params = tiny
    made = init_params_quantized(jax.random.key(1), cfg)
    want = jax.eval_shape(quantize_params, params)
    assert jax.tree.structure(made) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_quantizing_a_dense_family_is_unchanged_by_the_new_groups():
    from vnsum_tpu.models import init_params, tiny_llama

    params = init_params(jax.random.key(0), tiny_llama())
    q = quantize_params(params)
    assert set(q) == {"embed", "layers", "final_norm"}   # tied head
    assert q["layers"]["wq"]["s"].shape == params["layers"]["wq"].shape[:1] \
        + params["layers"]["wq"].shape[2:]


def test_w8a8_is_switched_on_by_the_engine_as_for_the_dense_families(tiny):
    cfg, params = tiny
    be = _two_rows(cfg, params, quantize=True, quantize_act=True)
    assert be.cfg.w8a8_prefill and not cfg.w8a8_prefill
    assert dataclasses.replace(cfg, w8a8_prefill=True) == be.cfg


# -- scopes --------------------------------------------------------------------

COMPONENTS = ("embed", "q_lora", "kv_latent", "kv_write", "attn", "attn_out",
              "mlp", "router", "experts", "shared_experts", "lm_head",
              "sample")


def test_the_one_shot_program_carries_the_familys_scopes(tiny):
    """Every component of README "Device time by layer" is a scope of the
    compiled program under both phases, so a device trace can be read by
    them (scope_maps builds the family's own state for the lowering)."""
    cfg, params = tiny
    be = TpuBackend(model_config=cfg, tokenizer="byte", batch_size=2,
                    max_new_tokens=4, params=params, flash=False)
    be._get_fn(2, 64, 4, be.gen_cfg)
    (m,) = be.scope_maps()
    assert m["module"] == "jit_generate"
    seen = {"/".join(p.split("/")[:2]) for p in m["scopes"].values()}
    for phase in ("prefill", "decode"):
        missing = [c for c in COMPONENTS if f"{phase}/{c}" not in seen]
        assert not missing, (phase, missing)
    assert "decode/emit" in seen


def test_the_kernels_keep_their_contracted_names():
    import re
    from pathlib import Path

    ops = Path(__file__).resolve().parents[1] / "vnsum_tpu" / "ops"
    named = set()
    for src in ("mla_attention.py", "expert_matmul.py"):
        named |= set(re.findall(r'^\s+name="(\w+)",$',
                                (ops / src).read_text(), re.M))
    # ``expert_combine`` (PR 40) is a kernel of its own: the products keep
    # the name the trace readers and both rooflines read
    assert named == {"mla_prefill_attention", "mla_decode_attention",
                     "expert_grouped_matmul", "expert_combine"}
