"""``ops/sparse_attention.py`` on the CPU: each kernel interpreted against
its XLA form at tiny shapes — the exact selection (thresholds by bisection
against ``lax.top_k``), ties, pads, row pieces, key blocks that end past the
cache — and the masked attention against dense attention over the same
sets, in prefill and in decode, the masked walk against the gathered form.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vnsum_tpu.models.llama import _attention, dequantize_cache_layer
from vnsum_tpu.ops import sparse_attention as sa

L, B, KV, G, HD, HI, DI = 2, 3, 2, 2, 16, 4, 8
H = KV * G


def _cache(C, quantized=False, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    cache = {"ki": jax.random.normal(ks[0], (L, B, DI, C)).astype(dtype)}
    if quantized:
        cache.update(
            k=jax.random.randint(ks[1], (L, B, KV, C, HD), -127, 128, jnp.int8),
            v=jax.random.randint(ks[2], (L, B, KV, C, HD), -127, 128, jnp.int8),
            ks=jax.random.uniform(ks[3], (L, B, KV, C), jnp.float32, .005, .02),
            vs=jax.random.uniform(ks[4], (L, B, KV, C), jnp.float32, .005, .02))
    else:
        cache.update(k=jax.random.normal(ks[1], (L, B, KV, C, HD)),
                     v=jax.random.normal(ks[2], (L, B, KV, C, HD)))
    return cache


def _queries(R, S, seed=1):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (R, S, H, HD)),
            jax.random.normal(ks[1], (R, S, HI, DI)),
            jax.random.normal(ks[2], (R, S, HI)) * 0.3)


def _visible(pads, off, S, C):
    slot = jnp.arange(C)[None, None, :]
    return (slot >= pads[:, None, None]) & (
        slot <= off + jnp.arange(S)[None, :, None])


def _near_ties_only(got, want, scores, visible, tol=1e-5):
    """The two sets keep as many slots a query, and every slot they do not
    share scores within ``tol`` of the cut."""
    got, want = np.asarray(got), np.asarray(want)
    s = np.where(np.asarray(visible), np.asarray(scores), np.inf)
    cut = np.where(want, s, np.inf).min(-1, keepdims=True)
    with np.errstate(invalid="ignore"):     # a row that sees nothing
        off = (got != want) & (np.abs(s - cut) > tol)
    return (got.sum(-1) == want.sum(-1)).all() and not off.any()


# -- the order and the XLA forms ------------------------------------------------


@pytest.mark.parametrize("values", [
    [-3.5, -1.0, -1e-30, 0.0, 1e-30, 2.0, 7.25],
    [-np.inf, -1e38, -1.0, 1.0, 1e38, np.inf],
    [-2.0, -0.0, 0.0, 2.0],
])
def test_sort_key_orders_as_the_floats_and_is_its_own_inverse(values):
    x = jnp.asarray(values, jnp.float32)
    key = np.asarray(sa.sort_key(x))
    assert (np.diff(key) >= 0).all()
    assert (np.diff(key) > 0).sum() == len(set(float(v) for v in values)) - 1
    back = np.asarray(sa.key_score(jnp.asarray(key)))
    assert (back == np.asarray(x)).all()     # -0.0 == 0.0
    assert np.asarray(sa.key_score(jnp.int32(-2 ** 31))) == -np.inf


def test_select_xla_breaks_ties_to_the_lower_slot_and_never_takes_a_pad():
    scores = jnp.asarray([[[1., 5., 5., 5., 0., 5., 9., 9.]]])
    visible = jnp.asarray([[[False, False, True, True, True, True, True,
                             True]]])
    got = np.asarray(sa.select_xla(scores, visible, 3))[0, 0]
    # the two 9s, then the first visible 5 (slot 2; slot 1 is under the pad)
    assert got.tolist() == [False, False, True, False, False, False, True,
                            True]
    # no more visible than the top-k: all of them, and nothing else
    few = np.asarray(sa.select_xla(scores, visible, 7))[0, 0]
    assert (few == np.asarray(visible)[0, 0]).all()
    none = np.asarray(sa.select_xla(scores, jnp.zeros_like(visible), 3))
    assert not none.any()


def test_index_scores_are_a_relu_then_a_weighted_sum_over_heads():
    _, q_idx, w_idx = _queries(1, 5)
    keys = _cache(12)["ki"][0, :1]
    got = sa.index_scores_xla(q_idx, w_idx, keys)
    per_head = np.einsum("bshd,bdc->bshc", np.asarray(q_idx), np.asarray(keys))
    want = (np.maximum(per_head, 0) * np.asarray(w_idx)[..., None]).sum(2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    low = sa.index_scores_xla(q_idx, w_idx, keys, jnp.bfloat16)
    assert 1e-4 < np.abs(np.asarray(low) - want).max() < 0.1


# -- the prefill's selection -----------------------------------------------------


@pytest.mark.parametrize("S, C, off, pads, topk, bq, bk, rows", [
    (16, 48, 32, [0, 40, 3], 12, 8, 16, None),     # a pad past the chunk's start
    (16, 48, 0, [0, 5, 16], 6, 16, 48, None),      # the first chunk, one block
    (32, 100, 64, [70, 0, 97], 24, 8, 32, None),   # blocks end past the cache
    (16, 64, 48, [3, 50], 1000, 8, 16, [2, 0]),    # a row piece; top-k over all
    (8, 40, 32, [33], 4, 8, 8, [1]),               # one row, many blocks
])
def test_the_selection_kernel_is_exact(S, C, off, pads, topk, bq, bk, rows):
    cache = _cache(C)
    R = len(pads)
    pads = jnp.asarray(pads, jnp.int32)
    rows_v = None if rows is None else jnp.asarray(rows, jnp.int32)
    _, q_idx, w_idx = _queries(R, S)
    mask, last = sa.dsa_index_select(
        q_idx, w_idx, cache, 1, pads, off, rows_v, topk=topk, block_q=bq,
        block_k=bk, interpret=True)
    Cp = -(-C // bk) * bk
    assert mask.shape == (R, S, Cp) and mask.dtype == jnp.int8
    keys = cache["ki"][1][:R] if rows is None else cache["ki"][1][rows_v]
    scores = sa.index_scores_xla(q_idx, w_idx, keys)
    visible = _visible(pads, off, S, C)
    want = sa.select_xla(scores, visible, topk)
    got = np.asarray(mask)[:, :, :C] != 0
    assert not np.asarray(mask)[:, :, C:].any()
    assert not (got & ~np.asarray(visible)).any()    # no pad, nothing ahead
    assert (got.sum(-1) == np.minimum(np.asarray(visible).sum(-1), topk)).all()
    assert _near_ties_only(got, want, scores, visible)
    # the last query's scores, -inf where it sees nothing
    seen = np.asarray(visible)[:, -1]
    np.testing.assert_allclose(np.asarray(last)[:, :C][seen],
                               np.asarray(scores)[:, -1][seen], rtol=1e-5,
                               atol=1e-6)
    assert np.isneginf(np.asarray(last)[:, :C][~seen]).all()


@pytest.mark.parametrize("kinds, topk", [
    (1, 5),    # every key the same: every score ties
    (2, 7),    # two kinds of key: the cut falls inside one kind's ties
    (3, 3),
])
def test_equal_scores_go_to_the_lower_slot_in_the_kernel(kinds, topk):
    """Keys repeated slot after slot give scores equal to the bit: the
    kernel's second bisection (over the slot) takes the first ``need`` of
    them, as ``lax.top_k`` does."""
    S, C, off = 8, 32, 24
    cache = _cache(C)
    base = cache["ki"][:, :, :, :kinds]
    cache["ki"] = jnp.tile(base, (1, 1, 1, C // kinds + 1))[..., :C]
    pads = jnp.asarray([0, 9, 30], jnp.int32)
    _, q_idx, w_idx = _queries(3, S)
    mask, _ = sa.dsa_index_select(q_idx, w_idx, cache, 0, pads, off,
                                  topk=topk, block_q=8, block_k=8,
                                  interpret=True)
    scores = sa.index_scores_xla(q_idx, w_idx, cache["ki"][0])
    want = sa.select_xla(scores, _visible(pads, off, S, C), topk)
    assert (np.asarray(mask)[:, :, :C] != 0).tolist() == np.asarray(
        want).tolist()


def test_a_bfloat16_sum_of_the_heads_is_another_selection_input():
    cache = _cache(64)
    pads = jnp.zeros((1,), jnp.int32)
    _, q_idx, w_idx = _queries(1, 8)
    kw = dict(topk=16, block_q=8, block_k=32, interpret=True)
    _, exact = sa.dsa_index_select(q_idx, w_idx, cache, 0, pads, 56, **kw)
    _, low = sa.dsa_index_select(q_idx, w_idx, cache, 0, pads, 56,
                                 sum_dtype=jnp.bfloat16, **kw)
    gap = np.abs(np.asarray(exact) - np.asarray(low)).max()
    assert 1e-4 < gap < 0.2


# -- the decode step's selection -------------------------------------------------


@pytest.mark.parametrize("rows, C, fill, pads, topk, bk", [
    (3, 48, 40, [0, 12, 39], 8, 16),
    (1, 100, 99, [20], 30, 32),       # the parity check's one row
    (3, 64, 10, [0, 11, 4], 4, 64),   # a row whose pad passes the fill
    (16, 40, 39, list(range(16)), 6, 8),   # two tiles of eight rows
])
def test_the_decode_selection_kernel_is_exact(rows, C, fill, pads, topk, bk):
    cache = _cache(C)
    if rows != B:
        cache = {k: jnp.concatenate([v] * 6, 1)[:, :rows]
                 for k, v in cache.items()}
    pads = jnp.asarray(pads, jnp.int32)
    _, q_idx, w_idx = _queries(rows, 1)
    mask, scores = sa.dsa_index_select_decode(
        q_idx[:, 0], w_idx[:, 0], cache, 1, pads, fill, topk=topk,
        block_k=bk, interpret=True)
    visible = (jnp.arange(C)[None] >= pads[:, None]) & (
        jnp.arange(C)[None] <= fill)
    want_scores = sa.index_scores_xla(q_idx, w_idx, cache["ki"][1])[:, 0]
    want = sa.select_xla(want_scores[:, None], visible[:, None], topk)[:, 0]
    got = np.asarray(mask)[:, :C] != 0
    assert not np.asarray(mask)[:, C:].any()
    assert (got.sum(-1) == np.minimum(np.asarray(visible).sum(-1), topk)).all()
    assert _near_ties_only(got[:, None], np.asarray(want)[:, None],
                           want_scores[:, None], visible[:, None])
    seen = np.asarray(visible)
    np.testing.assert_allclose(np.asarray(scores)[:, :C][seen],
                               np.asarray(want_scores)[seen], rtol=1e-5,
                               atol=1e-6)
    assert np.isneginf(np.asarray(scores)[:, :C][~seen]).all()


# -- attention over a selection ----------------------------------------------------


def _dense(q, cache, layer, rows, sel):
    k, v = dequantize_cache_layer(cache, layer, HD)
    if rows is not None:
        k, v = k[rows], v[rows]
    else:
        k, v = k[:q.shape[0]], v[:q.shape[0]]
    return _attention(q, k.astype(q.dtype), v.astype(q.dtype), sel, G)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("S, C, off, pads, bq, bk, rows", [
    (16, 48, 32, [0, 40, 3], 8, 16, None),
    (32, 100, 64, [70, 97], 16, 32, [2, 1]),
])
def test_masked_prefill_attention_is_dense_attention_over_the_sets(
        quantized, S, C, off, pads, bq, bk, rows):
    cache = _cache(C, quantized)
    R = len(pads)
    pads = jnp.asarray(pads, jnp.int32)
    rows_v = None if rows is None else jnp.asarray(rows, jnp.int32)
    q, q_idx, w_idx = _queries(R, S)
    mask, _ = sa.dsa_index_select(q_idx, w_idx, cache, 1, pads, off, rows_v,
                                  topk=10, block_q=8, block_k=bk,
                                  interpret=True)
    out = sa.dsa_prefill_attention(q, cache, 1, mask, pads, off, rows_v,
                                   block_q=bq, block_k=bk, interpret=True)
    sel = jnp.asarray(np.asarray(mask)[:, :, :C] != 0)
    want = _dense(q, cache, 1, rows_v, sel)
    real = np.asarray(sel).any(-1)
    np.testing.assert_allclose(np.asarray(out)[real], np.asarray(want)[real],
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out)[~real].any()     # a pad query comes back 0
    # exact: a key outside the set contributes nothing, whatever it holds
    poisoned = dict(cache, v=jnp.where(
        (jnp.arange(C) % 2 == 0)[None, None, None, :, None], cache["v"], 100))
    keep_even = mask * (jnp.arange(mask.shape[2]) % 2 == 0)[None, None, :]
    a = sa.dsa_prefill_attention(q, cache, 1, keep_even.astype(jnp.int8),
                                 pads, off, rows_v, block_q=bq, block_k=bk,
                                 interpret=True)
    b = sa.dsa_prefill_attention(q, poisoned, 1, keep_even.astype(jnp.int8),
                                 pads, off, rows_v, block_q=bq, block_k=bk,
                                 interpret=True)
    assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("C, fill, pads, topk, bk", [
    (48, 40, [0, 12, 39], 8, 16),
    (100, 99, [20, 0, 95], 30, 32),
])
def test_the_masked_walk_and_the_gathered_form_give_the_same_rows(
        quantized, C, fill, pads, topk, bk):
    cache = _cache(C, quantized)
    pads = jnp.asarray(pads, jnp.int32)
    q, q_idx, w_idx = _queries(B, 1)
    mask, scores = sa.dsa_index_select_decode(
        q_idx[:, 0], w_idx[:, 0], cache, 0, pads, fill, topk=topk,
        block_k=bk, interpret=True)
    walk = sa.dsa_decode_attention(q[:, 0], cache, 0, mask, pads, fill,
                                   block_k=bk, interpret=True)
    sel = jnp.asarray(np.asarray(mask)[:, :C] != 0)
    idx = jnp.argsort(~sel, axis=-1, stable=True)[:, :topk]
    valid = jnp.take_along_axis(sel, idx, axis=-1)
    gathered = sa.decode_attention_gathered(q[:, 0], cache, 0, idx, valid)
    dense = _dense(q, cache, 0, None, sel[:, None])[:, 0]
    np.testing.assert_allclose(walk, gathered, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(walk, dense, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("C, fill, pads, topk, bk", [
    # the cell's eight rows on the sublanes of one tile, ragged pads: rows
    # that see more slots than the top-k and rows that see fewer
    (72, 69, [0, 36, 5, 60, 1, 24, 52, 68], 12, 16),
    (40, 39, [0, 0, 0, 0, 0, 0, 0, 0], 6, 8),
])
def test_the_eight_row_decode_step_meets_the_reference(quantized, C, fill,
                                                       pads, topk, bk):
    """The form the timed dispatch runs (the parity check runs ONE row):
    the sets against ``reference_keye.top_by_sort`` of the equations' scores
    in float32, the walk against a plain softmax over the kept slots."""
    from benchmarks import reference_keye as reference

    rows = len(pads)
    cache = {k: jnp.concatenate([v] * 3, 1)[:, :rows]
             for k, v in _cache(C, quantized).items()}
    pads = jnp.asarray(pads, jnp.int32)
    q, q_idx, w_idx = (a[:, 0] for a in _queries(rows, 1))
    mask, _ = sa.dsa_index_select_decode(
        q_idx, w_idx, cache, 1, pads, fill, topk=topk, block_k=bk,
        interpret=True)
    got = np.asarray(mask)[:, :C] != 0
    visible = (jnp.arange(C)[None] >= pads[:, None]) & (
        jnp.arange(C)[None] <= fill)
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("bh,bhc->bc", w_idx, jnp.maximum(jnp.einsum(
            "bhd,bdc->bhc", q_idx, cache["ki"][1]), 0.0))
        own = reference.top_by_sort(scores, visible, topk)
        assert _near_ties_only(got, own, scores, visible)
        k, v = cache["k"][1].astype(jnp.float32), cache["v"][1].astype(
            jnp.float32)
        if quantized:
            k, v = k * cache["ks"][1][..., None], v * cache["vs"][1][..., None]
        s = jnp.einsum("bkgd,bkcd->bkgc", q.reshape(rows, KV, G, HD), k) \
            / jnp.sqrt(jnp.float32(HD))
        p = jax.nn.softmax(jnp.where(got[:, None, None, :], s, -jnp.inf), -1)
        want = jnp.einsum("bkgc,bkcd->bkgd", p, v).reshape(rows, H, HD)
    walk = sa.dsa_decode_attention(q, cache, 1, mask, pads, fill, block_k=bk,
                                   interpret=True)
    np.testing.assert_allclose(walk, want, rtol=2e-5, atol=2e-5)


# -- the counters --------------------------------------------------------------------


def test_prefill_score_counts_by_hand():
    # one row of 5 real tokens behind a pad of 3 in a chunk [0, 8); one of 8
    got = sa.prefill_score_counts([3, 0], [(0, 8)], topk=2, block_q=4,
                                  tile_q=8, block_k=4)
    assert got["visible"] == (1 + 2 + 3 + 4 + 5) + 36
    assert got["selected"] == (1 + 2 * 4) + (1 + 2 * 7)
    # tiles of 8 queries: blocks 0..1 for both rows -> 2 x (8 x 8)
    assert got["index_computed"] == 2 * 8 * 8
    # blocks of 4 queries: row 0 ends [4: block 0], [8: blocks 0-1];
    # row 1 the same -> (1 + 2) x 2 blocks of 4 x 4
    assert got["attention_computed"] == 2 * 3 * 16
    dead = sa.prefill_score_counts([8], [(0, 8), (8, 16)], topk=2, block_q=4,
                                   tile_q=8, block_k=4)
    # the first chunk lies under the pad: nothing visible, nothing computed
    assert dead["visible"] == 36 and dead["index_computed"] == 8 * 8
