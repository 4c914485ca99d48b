"""DeepSeek Sparse Attention (DSA) for the one-shot program: a learned
indexer scores every visible key for a query, the ``topk`` best are kept —
ONE set a query token, for all heads — and attention runs over that set
alone. Three pieces, each as its XLA form and as a Pallas TPU kernel.

The equations, per row and layer (``Hi`` indexer heads of ``di`` dims
against ONE indexer key a token; ``w_t [Hi]`` float32 head weights, scaled
by the caller):

    I[t, s] = sum_j w_t[j] * relu(qI_t[j] . kI_s)      s <= t, s not pad
    T_t     = the topk visible s of largest I[t, s]    (all of them when t
              sees <= topk keys; of equal scores the LOWER s)
    o_t^a   = sum_{s in T_t} softmax_{s in T_t}(q_t^a . k_s / sqrt hd) v_s

Products run in the inputs' type (bfloat16 on the chip: DSA's own FP8 is a
stated departure, a v5e has none), the sum over heads in float32
(``sum_dtype``: a parity check shows a bfloat16 sum failing). A score of
``-0.0`` counts as ``0.0``. The selection is EXACT in both forms: no
approximate top-k anywhere.

**The indexer-key cache** is ``cache["ki"] [L, B, di, C]`` in the model's
type — TRANSPOSED, the slots on the lanes: a 64-wide minor dim is half a
lane tile, and the compiler re-tiled the whole stacked cache (a 409 MB copy
a call at the cell's sizes) to hand ``[L, B, C, 64]`` to a kernel; with the
slots minor a block is whole tiles and ``qI kI^T`` a plain product — beside
llama's keys and values ``[L, B, KV, C, hd]`` (int8 with a float32 scale a
token and KV head); every kernel takes the stacked caches
with the layer's index as a prefetched scalar and reads the layer in place,
and a ROW PIECE of the engine's prefill through ``cache_rows`` (a
prefetched vector that steers the index maps alone).

**Selection as a threshold, not a sort** (``dsa_index_select``,
``dsa_index_select_decode``; XLA form ``select_xla``: ``lax.top_k``, whose
ties go to the lower index). A float32 score maps to an int32 of the same
order (``sort_key``). The kernel keeps a query tile's keys against every
visible slot in VMEM and finds each query's ``topk``-th largest by
BISECTION over the 32 bits: at bit b it counts the keys ``>=`` the
candidate prefix and keeps the bit where at least ``topk`` remain — 32
passes of compare-and-count on the vector unit, no matrix work and no data
movement; a sort of 16,640 scores for each of 2,048 queries would move them
``log^2`` times. With ``P`` the ``topk``-th largest key, the set is
``{key > P}`` and, of the slots with ``key == P``, the first ``need = topk
- count(key > P)`` by slot: where a tile has a query with more ties than it
needs (rare: two float32 sums equal to the bit) a second bisection, over
the SLOT, finds the cut ``J`` with exactly ``need`` ties at or below it;
otherwise every tie is taken. The result is a mask ``[R, S, Cp]`` int8 (Cp:
C rounded up to whole key blocks): a threshold and a tie count would make
the attention kernel recompute every index score to apply them, and an
index list is what a gather wants and the masked attention does not.
Grid (rows, query tiles, key blocks): the indexer keys stream through VMEM
a block at a time; a block wholly under the row's left pad or wholly above
the tile's causal line is neither fetched (its index is clamped onto a live
neighbour and Pallas elides the copy) nor computed. The decode form puts a
step's ROWS on the sublanes of one tile (each row scored against its own
keys), so one bisection serves eight rows.

**Prefill attention is the MASKED form** (``dsa_prefill_attention``): every
causal block of keys and values is fetched and scored, scores outside
``T_t`` go to -inf before the softmax, so no key outside the set
contributes — what DeepSeek's own release does for short prefills. At
12,000-token prompts it computes ~3.5 x the selected scores
(``dsa_attention_scores_computed`` / ``_selected``); a form that skips or
gathers is queued (ROADMAP). Online softmax over key blocks, the GQA group
of a KV head in one step, int8 keys and values dequantized by their scales
in the kernel, as ``ops/flash_attention.py``.

**Decode attention** (``dsa_decode_attention``) is the masked walk of the
row's blocks between its pad and its fill under the step's mask ``[B, Cp]``;
``decode_attention_gathered`` is the other form — the ``topk`` selected
slots gathered by index (XLA) and attended densely — which the tests hold
to the same rows and ``chip_smoke.py --phase keye`` times against the walk:
the cache keeps a KV head's slots ``[C, hd]`` apart, so a selected slot is
8 reads of 128 B (4 heads x keys and values) and 2,048 of them a row and
layer are slower than streaming the row whole (PERF.md section 6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_NEG = -1e30
_INT_MIN = -(2 ** 31)
# queries a tile of the prefill selection holds (their keys against every
# slot stay in VMEM: tile x Cp x 4 B = 17.8 MB at 17,408), slots a key block
# holds, and the prefill attention's query block (a step scores G x this many
# rows). Measured on the chip at the cell's last chunk (2,048 queries, 11.8k
# visible keys; PERF.md section 6, PR 63): selection 2.72 ms at (128, 512),
# 2.14 at (256, 512), 1.94 at (128, 1024), 1.66 at (256, 1024); attention
# 6.10, 4.85, 3.57, 3.41
_SELECT_TILE = 256
_KEY_BLOCK = 1024
_QUERY_BLOCK = 256
# rows a tile of the decode selection holds (one a sublane), and a decode
# step's key block (8 rows: selection 0.202 / 0.157 / 0.140 ms and the walk
# 0.427 / 0.324 / 0.288 ms at 512 / 1024 / 2048)
_DECODE_ROWS = 8
_DECODE_KEY_BLOCK = 2048
_NT = (((1,), (1,)), ((), ()))   # contract the last dim of both operands
_NN = (((1,), (0,)), ((), ()))   # a plain product


def sort_key(x: jax.Array) -> jax.Array:
    """float32 -> int32 of the same order (``-0.0`` as ``0.0``); its own
    inverse on the bits (``key_score``)."""
    x = jnp.where(x == 0.0, 0.0, x)
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return i ^ ((i >> 31) & 0x7FFFFFFF)


def key_score(key: jax.Array) -> jax.Array:
    """The way back; the key of an invisible slot reads -inf."""
    i = key ^ ((key >> 31) & 0x7FFFFFFF)
    return jnp.where(key == _INT_MIN, -jnp.inf,
                     jax.lax.bitcast_convert_type(i, jnp.float32))


def _blocks(n: int, most: int, block: int | None) -> int:
    """The block of a dim of ``n``: ``block`` if given, ``most`` where the
    dim holds one, else the dim whole."""
    if block:
        return block
    return most if n >= most else n


# -- XLA forms ----------------------------------------------------------------


def index_scores_xla(q_idx, w_idx, k_idx, sum_dtype=jnp.float32):
    """q_idx [B, S, Hi, di], w_idx [B, S, Hi] float32, k_idx [B, di, C] ->
    I [B, S, C] float32: a product a head in the inputs' type with float32
    results, relu, the heads' weighted sum one head after the other."""
    acc = jnp.zeros(q_idx.shape[:2] + (k_idx.shape[2],), jnp.float32)
    for j in range(q_idx.shape[2]):
        s = jnp.einsum("bsd,bdc->bsc", q_idx[:, :, j], k_idx,
                       preferred_element_type=jnp.float32)
        acc = (acc + w_idx[:, :, j, None] * jnp.maximum(s, 0.0)
               ).astype(sum_dtype).astype(jnp.float32)
    return jnp.where(acc == 0.0, 0.0, acc)


def select_xla(scores, visible, topk: int):
    """scores [B, S, C] float32, visible [B, S, C] bool -> selected [B, S,
    C] bool: each query's ``topk`` visible slots of largest score, all of
    them where it sees no more; equal scores to the lower slot."""
    B, S, C = scores.shape
    vals, idx = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf),
                              min(topk, C))
    rows = jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None]
    return jnp.zeros((B, S, C), bool).at[(*rows, idx)].set(vals > -jnp.inf)


def decode_attention_gathered(q, cache: dict, layer_idx, idx, valid):
    """One token a row over its selected slots, GATHERED: q [B, H, hd],
    ``idx`` [B, k] int32 cache slots and ``valid`` [B, k] (a row with fewer
    visible keys than k) -> [B, H, hd]. The selected slots of the layer's
    keys and values (and their scales) are taken by index, then attended
    densely."""
    B, H, hd = q.shape
    KV = cache["k"].shape[2]
    at = idx[:, None, :, None]

    def taken(name, scale):
        x = jnp.take_along_axis(
            jax.lax.dynamic_index_in_dim(cache[name], layer_idx, 0, False),
            at, axis=2)                                    # [B, KV, k, hd]
        if scale not in cache:
            return x.astype(jnp.float32)
        s = jnp.take_along_axis(
            jax.lax.dynamic_index_in_dim(cache[scale], layer_idx, 0, False),
            idx[:, None, :], axis=2)
        return x.astype(jnp.float32) * s[..., None]

    k, v = taken("k", "ks"), taken("v", "vs")
    qg = q.reshape(B, KV, H // KV, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,bkch->bkgc", qg, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgc,bkch->bkgh", p, v).reshape(B, H, hd).astype(q.dtype)


# -- selection: the kernels ---------------------------------------------------


def _threshold(count, k_eff, n_rows: int, slot_bits: int, slots: int):
    """(P, J, need) [n_rows, 1] int32 of a tile: ``P`` each row's
    ``k_eff``-th largest key, ``need`` how many of the slots at ``P`` it
    takes, ``J`` the slot at or below which it takes them.
    ``count(pred)`` sums ``pred(keys block, its slots)`` over the tile's
    live key blocks -> [n_rows, 1] int32."""
    c0 = count(lambda blk, slot: blk >= 0)
    prefix = jnp.where(c0 >= k_eff, 0, _INT_MIN).astype(jnp.int32)

    def key_bit(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), 30 - i)
        c = count(lambda blk, slot: blk >= cand)
        return jnp.where(c >= k_eff, cand, prefix)

    P = jax.lax.fori_loop(0, 31, key_bit, prefix)
    need = k_eff - count(lambda blk, slot: blk > P)
    ties = count(lambda blk, slot: blk == P)

    def tie_cut():
        # the largest J with fewer than ``need`` ties below it
        def slot_bit(i, J):
            cand = J | jnp.left_shift(jnp.int32(1), slot_bits - 1 - i)
            c = count(lambda blk, slot: (blk == P) & (slot < cand))
            return jnp.where(c < need, cand, J)

        return jax.lax.fori_loop(0, slot_bits, slot_bit,
                                 jnp.zeros((n_rows, 1), jnp.int32))

    more_than_needed = jnp.max(
        jnp.where(ties != need, 1.0, 0.0).astype(jnp.float32)) > 0.0
    J = jax.lax.cond(more_than_needed, tie_cut,
                     lambda: jnp.full((n_rows, 1), slots, jnp.int32))
    return P, J, need


def _selected(blk, slot, P, J, need):
    return (blk != _INT_MIN) & (
        (blk > P) | ((blk == P) & (slot <= J) & (need > 0)))


def _counter(keys_ref, kb_lo, n_live, bk: int, n_rows: int):
    """``count`` of ``_threshold`` over ``keys_ref [n_rows, Cp]``'s blocks
    ``kb_lo .. kb_lo + n_live``."""
    def count(pred):
        def body(i, c):
            off = pl.multiple_of((kb_lo + i) * bk, bk)
            slot = off + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            hit = pred(keys_ref[:, pl.ds(off, bk)], slot)
            return c + jnp.sum(jnp.where(hit, 1, 0).astype(jnp.int32),
                               axis=1, keepdims=True)

        return jax.lax.fori_loop(0, n_live, body,
                                 jnp.zeros((n_rows, 1), jnp.int32))

    return count


def _write_selection(keys_ref, write, k_eff, kb_lo, n_live, n_rows: int,
                     bk: int, nkb: int):
    """The second half of both selection kernels: each row's threshold over
    the live blocks of ``keys_ref [n_rows, Cp]``, then ``write(offset,
    0 / 1 [n_rows, bk])`` a live block."""
    P, J, need = _threshold(_counter(keys_ref, kb_lo, n_live, bk, n_rows),
                            k_eff, n_rows,
                            max((nkb * bk - 1).bit_length(), 1), nkb * bk)

    def block(i, _):
        off = pl.multiple_of((kb_lo + i) * bk, bk)
        slot = off + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        sel = _selected(keys_ref[:, pl.ds(off, bk)], slot, P, J, need)
        write(off, jnp.where(sel, 1, 0))
        return 0

    jax.lax.fori_loop(0, n_live, block, 0)


def _select_kernel(lidx_ref, pad_ref, off_ref, rows_ref, q_ref, w_ref, k_ref,
                   mask_ref, last_ref, keys_ref, *, tq: int, bk: int,
                   nkb: int, topk: int, n_heads: int, sum_dtype):
    r, qi, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    pad = pad_ref[r]
    q_start = off_ref[0] + qi * tq
    kb_lo = pad // bk
    kb_hi = jnp.minimum((q_start + tq - 1) // bk, nkb - 1)
    real = pad < q_start + tq        # the tile holds a real query
    qslot = q_start + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    @pl.when(real & (kb >= kb_lo) & (kb <= kb_hi))
    def _score():
        kblk = k_ref[0, 0]
        w = w_ref[0]
        acc = jnp.zeros((tq, bk), jnp.float32)
        for j in range(n_heads):
            s = jax.lax.dot_general(q_ref[0, j], kblk, _NN,
                                    preferred_element_type=jnp.float32)
            acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
            if sum_dtype != jnp.float32:
                acc = acc.astype(sum_dtype).astype(jnp.float32)
        off = pl.multiple_of(kb * bk, bk)
        slot = off + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        visible = (slot >= pad) & (slot <= qslot)
        keys_ref[:, pl.ds(off, bk)] = jnp.where(
            visible, sort_key(acc), _INT_MIN)

    @pl.when(kb == nkb - 1)
    def _select():
        mask_ref[...] = jnp.zeros(mask_ref.shape, mask_ref.dtype)

        def write(off, sel):
            mask_ref[0, :, pl.ds(off, bk)] = sel.astype(mask_ref.dtype)

        _write_selection(
            keys_ref, write,
            jnp.minimum(topk, jnp.maximum(qslot + 1 - pad, 0)), kb_lo,
            jnp.where(real, kb_hi - kb_lo + 1, 0), tq, bk, nkb)
        # the tile's last queries' keys, for a parity check's record (the
        # last tile's are what the row's block keeps)
        last_ref[0] = keys_ref[tq - last_ref.shape[1]:, :]


def dsa_index_select(q_idx, w_idx, cache: dict, layer_idx, pad_lens,
                     q_offset=0, cache_rows=None, *, topk: int,
                     sum_dtype=jnp.float32, block_q: int | None = None,
                     block_k: int | None = None, interpret: bool = False):
    """A chunk's selection. q_idx [R, S, Hi, di] and w_idx [R, S, Hi]
    float32 of the queries at cache slots ``q_offset .. q_offset + S`` of
    rows ``cache_rows`` (None: row r) with left pads ``pad_lens`` [R],
    against ``cache["ki"]`` [L, B, di, C] of layer ``layer_idx`` (the
    chunk's own keys written). Returns (mask [R, S, Cp] int8 — 1 where
    query t keeps slot s —, the LAST query's scores [R, Cp] float32, -inf
    where it sees nothing)."""
    R, S, Hi, di = q_idx.shape
    C = cache["ki"].shape[3]
    tq = _blocks(S, _SELECT_TILE, block_q)
    bk = _blocks(C, _KEY_BLOCK, block_k)
    if S % tq:
        raise ValueError(f"{S} queries are no whole tiles of {tq}")
    nq, nkb = S // tq, pl.cdiv(C, bk)
    Cp = nkb * bk
    keep = min(8, tq)
    rows = (jnp.arange(R, dtype=jnp.int32) if cache_rows is None
            else cache_rows.astype(jnp.int32))

    def key_index(r, qi, kb, lidx, pad, off, rows):
        q_start = off[0] + qi * tq
        hi = jnp.minimum((q_start + tq - 1) // bk, nkb - 1)
        return (lidx[0], rows[r], 0,
                jnp.minimum(jnp.maximum(kb, pad[r] // bk), hi))

    mask, last = pl.pallas_call(
        functools.partial(_select_kernel, tq=tq, bk=bk, nkb=nkb, topk=topk,
                          n_heads=Hi, sum_dtype=sum_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(R, nq, nkb),
            in_specs=[
                pl.BlockSpec((1, Hi, tq, di),
                             lambda r, qi, kb, *_: (r, 0, qi, 0)),
                pl.BlockSpec((1, tq, Hi), lambda r, qi, kb, *_: (r, qi, 0)),
                pl.BlockSpec((1, 1, di, bk), key_index),
            ],
            out_specs=[
                pl.BlockSpec((1, tq, Cp), lambda r, qi, kb, *_: (r, qi, 0)),
                pl.BlockSpec((1, keep, Cp), lambda r, qi, kb, *_: (r, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((tq, Cp), jnp.int32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((R, S, Cp), jnp.int8),
                   jax.ShapeDtypeStruct((R, keep, Cp), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
        name="dsa_index_select",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        pad_lens.astype(jnp.int32),
        jnp.asarray(q_offset, jnp.int32).reshape(1), rows,
        q_idx.transpose(0, 2, 1, 3), w_idx.astype(jnp.float32), cache["ki"],
    )
    slot = jnp.arange(Cp)[None, :]
    seen = (slot >= pad_lens[:, None]) & (slot < q_offset + S)
    return mask, jnp.where(seen, key_score(last[:, -1]), -jnp.inf)


def _select_decode_kernel(lidx_ref, pad_ref, fill_ref, q_ref, w_ref, k_ref,
                          mask_ref, keys_ref, *, rows: int, bk: int, nkb: int,
                          topk: int, sum_dtype):
    g, kb = pl.program_id(0), pl.program_id(1)
    fill = fill_ref[0]
    pads = [pad_ref[g * rows + r] for r in range(rows)]
    low = pads[0]
    for p in pads[1:]:
        low = jnp.minimum(low, p)
    kb_lo = low // bk
    kb_hi = jnp.minimum(fill // bk, nkb - 1)

    @pl.when((kb >= kb_lo) & (kb <= kb_hi))
    def _score():
        off = pl.multiple_of(kb * bk, bk)
        slot = off + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        for r in range(rows):
            # the row's heads on the sublanes: [Hi, bk]
            s = jax.lax.dot_general(q_ref[r], k_ref[0, r], _NN,
                                    preferred_element_type=jnp.float32)
            t = (w_ref[r] * jnp.maximum(s, 0.0)).astype(sum_dtype)
            acc = jnp.sum(t, axis=0, keepdims=True).astype(jnp.float32)
            visible = (slot >= pads[r]) & (slot <= fill)
            keys_ref[pl.ds(r, 1), pl.ds(off, bk)] = jnp.where(
                visible, sort_key(acc), _INT_MIN)

    @pl.when(kb == nkb - 1)
    def _select():
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        pad_v = jnp.zeros((rows, 1), jnp.int32)
        for r in range(rows):
            pad_v = jnp.where(row == r, pads[r], pad_v)
        mask_ref[...] = jnp.zeros(mask_ref.shape, mask_ref.dtype)

        def write(off, sel):
            # a block live for the group may lie under a row's own pad: its
            # keys there are invisible, and never selected
            mask_ref[:, pl.ds(off, bk)] = sel.astype(mask_ref.dtype)

        _write_selection(
            keys_ref, write,
            jnp.minimum(topk, jnp.maximum(fill + 1 - pad_v, 0)), kb_lo,
            kb_hi - kb_lo + 1, rows, bk, nkb)


def dsa_index_select_decode(q_idx, w_idx, cache: dict, layer_idx, pad_lens,
                            fill, *, topk: int, sum_dtype=jnp.float32,
                            block_k: int | None = None,
                            interpret: bool = False):
    """A decode step's selection: q_idx [B, Hi, di], w_idx [B, Hi] of the
    one query a row at cache slot ``fill`` (its own indexer key written).
    Returns (mask [B, Cp] int32, scores [B, Cp] float32, -inf where the
    row sees nothing). Eight rows a tile, one a sublane."""
    B, Hi, di = q_idx.shape
    C = cache["ki"].shape[3]
    rows = min(B, _DECODE_ROWS)
    if B % rows:
        raise ValueError(f"{B} rows are no whole tiles of {rows}")
    bk = _blocks(C, _DECODE_KEY_BLOCK, block_k)
    nkb = pl.cdiv(C, bk)
    Cp = nkb * bk
    pads = pad_lens.astype(jnp.int32)

    def key_index(g, kb, lidx, pad, fill):
        low = pad[g * rows]
        for r in range(1, rows):
            low = jnp.minimum(low, pad[g * rows + r])
        hi = jnp.minimum(fill[0] // bk, nkb - 1)
        return (lidx[0], g, 0, jnp.minimum(jnp.maximum(kb, low // bk), hi))

    mask, keys = pl.pallas_call(
        functools.partial(_select_decode_kernel, rows=rows, bk=bk, nkb=nkb,
                          topk=topk, sum_dtype=sum_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B // rows, nkb),
            in_specs=[
                pl.BlockSpec((rows, Hi, di), lambda g, kb, *_: (g, 0, 0)),
                pl.BlockSpec((rows, Hi, 1), lambda g, kb, *_: (g, 0, 0)),
                pl.BlockSpec((1, rows, di, bk), key_index),
            ],
            out_specs=[
                pl.BlockSpec((rows, Cp), lambda g, kb, *_: (g, 0)),
                pl.BlockSpec((rows, Cp), lambda g, kb, *_: (g, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Cp), jnp.int32),
                   jax.ShapeDtypeStruct((B, Cp), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",) * 2),
        interpret=interpret,
        name="dsa_index_select",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1), pads,
        jnp.asarray(fill, jnp.int32).reshape(1),
        q_idx, w_idx.astype(jnp.float32)[..., None], cache["ki"],
    )
    slot = jnp.arange(Cp)[None, :]
    seen = (slot >= pads[:, None]) & (slot <= fill)
    return mask, jnp.where(seen, key_score(keys), -jnp.inf)


# -- attention over a selection: the kernels ----------------------------------


def _softmax_step(s, sel, v, v_scale, acc_ref, m_ref, l_ref, at, dtype,
                  first_slot, C: int):
    """One key block of the online softmax for the rows ``at`` of the
    state: s [N, bk] float32 scores, sel [N, bk] who counts, v [bk, hd]
    from slot ``first_slot`` of a cache of ``C``; the weights meet the
    values in ``dtype`` (the queries')."""
    v = v.astype(dtype)
    if C % v.shape[0]:
        # the last block ends past the cache and holds whatever was there:
        # a weight of zero does not silence a NaN
        row = first_slot + jax.lax.broadcasted_iota(
            jnp.int32, (v.shape[0], 1), 0)
        v = jnp.where(row < C, v, jnp.zeros_like(v))
    s = jnp.where(sel, s, _NEG)
    m_prev = m_ref[at]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(sel, jnp.exp(s - m_new), 0.0)
    l_ref[at] = alpha * l_ref[at] + jnp.sum(p, axis=1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    acc_ref[at] = alpha * acc_ref[at] + jax.lax.dot_general(
        p.astype(dtype), v,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[at] = m_new


def _reset(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)


def _prefill_kernel(lidx_ref, pad_ref, off_ref, rows_ref, q_ref, k_ref, v_ref,
                    *rest, bq: int, bk: int, G: int, C: int, scale: float,
                    quantized: bool):
    if quantized:
        ks_ref, vs_ref, mask_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        mask_ref, o_ref, acc_ref, m_ref, l_ref = rest
    r, h, i, j = (pl.program_id(a) for a in range(4))
    q_start = off_ref[0] + i * bq
    pad = pad_ref[r]

    pl.when(j == 0)(functools.partial(_reset, acc_ref, m_ref, l_ref))

    @pl.when((pad < q_start + bq) & (j >= pad // bk)
             & (j <= (q_start + bq - 1) // bk))
    def _block():
        hd = q_ref.shape[-1]
        q = q_ref[0, 0].reshape(G * bq, hd)
        s = jax.lax.dot_general(q, k_ref[0, 0, 0].astype(q.dtype), _NT,
                                preferred_element_type=jnp.float32) * scale
        slot = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        qslot = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        # the selection holds pad and causality; a block past the cache's
        # end holds whatever was there
        sel = (mask_ref[0].astype(jnp.int32) != 0) & (slot <= qslot) \
            & (slot < C)
        sel = jnp.broadcast_to(sel[None], (G, bq, bk)).reshape(G * bq, bk)
        v_scale = None
        if quantized:
            s = s * ks_ref[0, 0, pl.ds(h, 1), :]
            v_scale = jnp.where(slot < C, vs_ref[0, 0, pl.ds(h, 1), :], 0.0)
        _softmax_step(s, sel, v_ref[0, 0, 0], v_scale, acc_ref, m_ref, l_ref,
                      ..., q.dtype, j * bk, C)

    @pl.when(j == pl.num_programs(3) - 1)
    def _out():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = out.reshape(G, bq, -1).astype(o_ref.dtype)


def dsa_prefill_attention(q, cache: dict, layer_idx, mask, pad_lens,
                          q_offset=0, cache_rows=None, *,
                          block_q: int | None = None,
                          block_k: int | None = None,
                          interpret: bool = False):
    """Attention of a chunk's queries over their sets, MASKED: q [R, S, H,
    hd] at cache slots ``q_offset ..`` of rows ``cache_rows``, ``mask`` [R,
    S, Cp] int8 from ``dsa_index_select`` at the same ``block_k``. Returns
    [R, S, H, hd]; a query under its row's pad comes back as zeros."""
    R, S, H, hd = q.shape
    KV, C = cache["k"].shape[2], cache["k"].shape[3]
    G = H // KV
    quantized = "ks" in cache
    bq = _blocks(S, _QUERY_BLOCK, block_q)
    bk = _blocks(C, _KEY_BLOCK, block_k)
    if S % bq or mask.shape[2] != pl.cdiv(C, bk) * bk:
        raise ValueError(f"{S} queries in blocks of {bq}, a mask of "
                         f"{mask.shape[2]} slots for {C} in blocks of {bk}")
    nq, nkb = S // bq, pl.cdiv(C, bk)
    rows = (jnp.arange(R, dtype=jnp.int32) if cache_rows is None
            else cache_rows.astype(jnp.int32))

    def live_j(r, i, j, pad, off):
        hi = (off[0] + i * bq + bq - 1) // bk
        return jnp.minimum(jnp.maximum(j, pad[r] // bk),
                           jnp.minimum(hi, nkb - 1))

    def kv_index(r, h, i, j, lidx, pad, off, rows):
        return (lidx[0], rows[r], h, live_j(r, i, j, pad, off), 0)

    def scale_index(r, h, i, j, lidx, pad, off, rows):
        return (lidx[0], rows[r], 0, live_j(r, i, j, pad, off))

    q_spec = pl.BlockSpec((1, 1, G, bq, hd),
                          lambda r, h, i, j, *_: (r, h, 0, i, 0))
    in_specs = [q_spec, pl.BlockSpec((1, 1, 1, bk, hd), kv_index),
                pl.BlockSpec((1, 1, 1, bk, hd), kv_index)]
    operands = [q.transpose(0, 2, 1, 3).reshape(R, KV, G, S, hd),
                cache["k"], cache["v"]]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, KV, bk), scale_index)] * 2
        operands += [cache["ks"], cache["vs"]]
    in_specs.append(pl.BlockSpec(
        (1, bq, bk),
        lambda r, h, i, j, lidx, pad, off, rows: (
            r, i, live_j(r, i, j, pad, off))))
    operands.append(mask)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, bq=bq, bk=bk, G=G, C=C,
                          scale=1.0 / (hd ** 0.5), quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(R, KV, nq, nkb),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((G * bq, hd), jnp.float32),
                            pltpu.VMEM((G * bq, 1), jnp.float32),
                            pltpu.VMEM((G * bq, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((R, KV, G, S, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",) * 4),
        interpret=interpret,
        name="dsa_prefill_attention",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        pad_lens.astype(jnp.int32),
        jnp.asarray(q_offset, jnp.int32).reshape(1), rows, *operands,
    )
    return out.reshape(R, H, S, hd).transpose(0, 2, 1, 3)


def _decode_kernel(lidx_ref, pad_ref, fill_ref, q_ref, k_ref, v_ref, *rest,
                   bk: int, KV: int, C: int, scale: float, quantized: bool):
    if quantized:
        ks_ref, vs_ref, mask_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        mask_ref, o_ref, acc_ref, m_ref, l_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)

    pl.when(j == 0)(functools.partial(_reset, acc_ref, m_ref, l_ref))

    @pl.when((j >= pad_ref[b] // bk) & (j <= fill_ref[0] // bk))
    def _block():
        slot = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        sel = (mask_ref[0] != 0) & (slot <= fill_ref[0]) & (slot < C)
        for h in range(KV):
            q = q_ref[0, h]                                    # [G, hd]
            s = jax.lax.dot_general(q, k_ref[0, 0, h].astype(q.dtype), _NT,
                                    preferred_element_type=jnp.float32) * scale
            v_scale = None
            if quantized:
                s = s * ks_ref[0, 0, h:h + 1, :]
                v_scale = jnp.where(slot < C, vs_ref[0, 0, h:h + 1, :], 0.0)
            _softmax_step(s, jnp.broadcast_to(sel, s.shape), v_ref[0, 0, h],
                          v_scale, acc_ref, m_ref, l_ref, h, q.dtype,
                          j * bk, C)

    @pl.when(j == pl.num_programs(1) - 1)
    def _out():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def dsa_decode_attention(q, cache: dict, layer_idx, mask, pad_lens, fill, *,
                         block_k: int | None = None,
                         interpret: bool = False):
    """One token a row over its set, the MASKED WALK: q [B, H, hd] at cache
    slot ``fill``, ``mask`` [B, Cp] int32 from ``dsa_index_select_decode``
    at the same ``block_k``. The row's blocks between its pad and its fill
    are streamed (every KV head of a block in one step) and scored under
    the mask. Returns [B, H, hd]."""
    B, H, hd = q.shape
    KV, C = cache["k"].shape[2], cache["k"].shape[3]
    G = H // KV
    quantized = "ks" in cache
    bk = _blocks(C, _DECODE_KEY_BLOCK, block_k)
    nkb = pl.cdiv(C, bk)
    if mask.shape[1] != nkb * bk:
        raise ValueError(f"a mask of {mask.shape[1]} slots for {C} in "
                         f"blocks of {bk}")

    def live_j(b, j, pad, fill):
        return jnp.minimum(jnp.maximum(j, pad[b] // bk),
                           jnp.minimum(fill[0] // bk, nkb - 1))

    def kv_index(b, j, lidx, pad, fill):
        return (lidx[0], b, 0, live_j(b, j, pad, fill), 0)

    def scale_index(b, j, lidx, pad, fill):
        return (lidx[0], b, 0, live_j(b, j, pad, fill))

    q_spec = pl.BlockSpec((1, KV, G, hd), lambda b, j, *_: (b, 0, 0, 0))
    in_specs = [q_spec, pl.BlockSpec((1, 1, KV, bk, hd), kv_index),
                pl.BlockSpec((1, 1, KV, bk, hd), kv_index)]
    operands = [q.reshape(B, KV, G, hd), cache["k"], cache["v"]]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, KV, bk), scale_index)] * 2
        operands += [cache["ks"], cache["vs"]]
    in_specs.append(pl.BlockSpec(
        (1, 1, bk), lambda b, j, lidx, pad, fill: (
            b, 0, live_j(b, j, pad, fill))))
    operands.append(mask[:, None, :])
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bk=bk, KV=KV, C=C,
                          scale=1.0 / (hd ** 0.5), quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nkb),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((KV, G, hd), jnp.float32),
                            pltpu.VMEM((KV, G, 1), jnp.float32),
                            pltpu.VMEM((KV, G, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",) * 2),
        interpret=interpret,
        name="dsa_decode_attention",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        pad_lens.astype(jnp.int32),
        jnp.asarray(fill, jnp.int32).reshape(1), *operands,
    )
    return out.reshape(B, H, hd)


# -- what a dispatch's prefill computed, on the host --------------------------


def prefill_score_counts(pad_lens, spans, topk: int, block_q: int,
                         tile_q: int, block_k: int) -> dict:
    """Per layer and head-free, from the pads a dispatch was packed with and
    its prefill's query spans [lo, hi): ``visible`` (a real query's visible
    keys, summed), ``selected`` (min(visible, topk), summed),
    ``index_computed`` (query x key pairs of the blocks the selection
    kernel scored: a tile of ``tile_q`` queries against the key blocks
    between its row's pad and its causal line) and ``attention_computed``
    (the same for the masked attention kernel at ``block_q``)."""
    import numpy as np

    pads = np.asarray(pad_lens, np.int64)
    out = dict(visible=0, selected=0, index_computed=0, attention_computed=0)
    for lo, hi in spans:
        seen = np.clip(np.arange(lo, hi)[None, :] + 1 - pads[:, None], 0, None)
        out["visible"] += int(seen.sum())
        out["selected"] += int(np.minimum(seen, topk).sum())
        for name, t in (("index_computed", tile_q),
                        ("attention_computed", block_q)):
            t = min(t, hi - lo)
            ends = np.arange(lo + t, hi + 1, t)[None, :]       # tile ends
            first = pads[:, None] // block_k
            last = (ends - 1) // block_k
            blocks = np.where(pads[:, None] < ends, last - first + 1, 0)
            out[name] += int(blocks.sum()) * t * block_k
    return out
