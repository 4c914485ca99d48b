"""Pallas TPU attention kernels for latent attention (MLA).

A latent-attention layer caches one row of ``kv_lora_rank + rope`` values
per token (models/deepseek.py: 512 of normalised ``c_kv`` + 64 of rotated
``k_rope``), shared by every head. Two kernels read it, one per phase:

- ``mla_prefill_attention``: causal attention over keys and values that the
  KERNEL expands from the latent rows, a key block and a head at a time, in
  VMEM (``k_nope = c_kv W_kb`` and ``v = c_kv W_vb`` per head, rounded to
  the inputs' type; ``k_rope`` is the block's last lanes, shared by all
  heads): nothing expanded is written to HBM. The query/key width (nope +
  rope = 192) differs from the value width (128), which the GQA kernels of
  ops/flash_attention.py cannot express. It reads the stacked cache ``[L,
  B, C, 576]`` in place, as the decode kernel does (layer index and the
  queries' first batch row by scalar prefetch): no layer's rows are sliced
  out of the cache for it. Left-padded rows and chunked prefill
  (``q_offset``) as there: a block above the diagonal or under a row's pad
  is neither fetched nor expanded nor computed, a block that needs no mask
  builds none (_tile_class; ``prefill_tile_classes`` counts a call's tiles
  and the keys it expands on the host by the same rule). A
  grid step holds a GROUP of heads (their queries and their blocks of
  ``W_kb`` / ``W_vb``) against one latent block, as a GQA group shares its
  K/V block there, and a head computes a (1024, 1024) tile of scores at a
  time. A chunked prefill calls it once a chunk, so a row's keys are
  expanded once for every chunk that reads them (4.6 times at the cell's
  map dispatch) — and that costs nothing to speak of: a head and tile is
  32 passes of 1,024 rows through the 128 x 128 units (8 for the expansion,
  8 + 8 for the scores — the 64-wide rope product costs what a 128-wide one
  does — and 8 for the values), but its pace is the handling of its (1024,
  1024) float32 scores, beside which the products run, for which a loop
  step is WRITTEN products first (for_each_head). A kernel that expanded a
  block once for all of a call's query tiles ran a tile in the same 5.7 us
  a head (PR 53, the comment over _BLOCK).
- ``mla_decode_attention``: the ABSORBED decode step. The caller folds
  ``W_kvb``'s key half into the query (``q_lat = q_nope . W_k^T``, 512 wide)
  and the kernel is multi-query attention of all heads over the one latent
  row per token: ``score = q_lat . c_kv + q_rope . k_rope``, ``o_lat = P .
  c_kv``. Keys and values are never expanded. It reads the stacked cache
  ``[L, B, C, 576]`` in place (layer index by scalar prefetch), blocks past
  the fill or under the row's pad are not fetched.

Inference only. float32 softmax state; products in the inputs' type.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_LANES = 128
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


# -- prefill ------------------------------------------------------------------

# What one grid step holds, from a sweep on the v5e at the cell's call shapes
# (q [1, 128, 1024, 128 | 64], 1,024-8,192 keys, bf16; PERF.md section 6,
# PR 34). The tile of scores one head computes at a time sets the pace, and a
# (1024, 1024) tile costs half of a (512, 512) one a score (5.4 against 10.7 ns
# per 1,024); wider key blocks, sub-tiles of an edge block and a tile of 512
# queries all cost more. The heads of a group share the step's latent block,
# mask and positions, which buys 1-3%; they go two at a time, which buys 3%
# more where four at a time spill (+50%; +35-47% with the expansion inside,
# PR 44, at a scoped limit raised to fit them). Past this cell what counts
# is the order a step is written in (for_each_head).
#
# The pace is NOT the matrix units', though a head and tile's 32 passes of
# 1,024 rows (8 of them the expansion's) take 5.5 us at their peak and the
# tile 5.7 (PR 53, the cell's device trace): a kernel whose step held all of
# a call's query tiles — spans of 4,096 queries a call, the first tile
# expanding the block into VMEM, the others taking it, 12 expansions a row
# of 8,192 keys for 36 — ran a tile that took its keys from VMEM in 0.730 ms
# a row and one that expanded them in 0.734, a full row in 26.98 ms for
# 27.26. Since the products are written ahead of the softmax the expansion
# hides under it, and what a tile waits for is the passes over its (1024,
# 1024) float32 scores (PERF.md section 6, PR 53, has the probes). That
# kernel and its engine rule are not in the tree: they bought no time.
_BLOCK = 1024
# the widest group whose step fits 48 MiB of scoped VMEM at that tile (16
# heads do not compile)
_GROUP = 8
_HEADS_UNROLLED = 2
# ... alone in a program. Reading the cache in place (PR 53), the same step
# is booked 49.35 MiB inside the cell's (4, 2048) and (4, 4096) programs,
# which then do not compile at 48 (the (24, 8192) program and the kernel
# alone do): the limit is the expert product's, half of the chip's 128
_PREFILL_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _prefill_geometry(H: int, S: int, T: int, block_q: int | None = None,
                      block_k: int | None = None) -> tuple[int, int, int]:
    """(G, bq, bk) of the prefill kernel's cell: the wrapper's rule, also
    the counter's (prefill_tile_classes). G is the largest divisor of H of
    at most _GROUP heads."""
    bq = min(block_q or _BLOCK, S)
    bk = min(block_k or _BLOCK, T)
    G = max(g for g in range(1, _GROUP + 1) if H % g == 0)
    return G, bq, bk


def _tile_class(q_start, k_start, pad, rows: int, cols: int):
    """What the (rows x cols) tile of scores at query slot ``q_start`` / key
    slot ``k_start`` holds for a row with ``pad`` left-pad slots, as three
    flags (above, under, interior): wholly above the causal diagonal; every
    key or every query under the pad; the mask would be all true. A tile
    with no flag set is one the diagonal or the pad's end crosses. Only
    comparisons and bit operators: the kernel calls it on SMEM scalars and
    prefill_tile_classes on numpy arrays, so the host's count is the
    kernel's behaviour."""
    q_last = q_start + (rows - 1)
    k_last = k_start + (cols - 1)
    above = k_start > q_last
    under = (k_last < pad) | (q_last < pad)
    interior = (k_last <= q_start) & (k_start >= pad)
    return above, under, interior


def _prefill_kernel(pad_ref, at_ref, qn_ref, qr_ref, lat_ref, wk_ref, wv_ref,
                    o_ref, acc_ref, m_ref, l_ref, *, group: int, block_q: int,
                    block_k: int, n_keys: int, rank: int, scale: float):
    # at: (q_offset, row 0's place among the latent's rows); qn [1,G,bq,dn]
    # qr [1,G,bq,dr] lat [1,bk,rank+dr] wk/wv [G,rank,dn|dv];
    # acc [G*bq,dv], m/l [G*bq,LANES]: a head's state is a slice of rows
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)
    pad = pad_ref[b]
    q_start = at_ref[0] + i * block_q
    k_start = j * block_k

    def rows_of(g):
        return pl.ds(pl.multiple_of(g * block_q, block_q), block_q)

    def for_each_head(front, back):
        """``front(g)`` for a few of the group's heads, then ``back(g,
        front's result)`` for the same heads, unrolled into one loop step:
        code size and compile time stay those of _HEADS_UNROLLED heads,
        whatever the group. The order is the point. Mosaic runs a step
        much as it is written, and work overlaps what stands next to it:
        with every head's products (``front``: its keys and scores) written
        before the first head's softmax (``back``), that softmax runs
        beside the next head's products and not after them — 6.90 -> 6.14
        ms a row of 8,192 keys on the v5e (PERF.md section 6, PR 44)."""
        n = _HEADS_UNROLLED if group % _HEADS_UNROLLED == 0 else 1

        def several(step, _):
            heads = [step * n + u for u in range(n)]
            held = [front(g) for g in heads]
            for g, h in zip(heads, held):
                back(g, h)

        jax.lax.fori_loop(0, group // n, several, None)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _accumulate(masked: bool):
        # what does not hang on the head is built once a step: the latent
        # block's two halves, positions, the mask, the zeroing of a ragged
        # tail
        blk = lat_ref[0]                                     # [bk, rank+dr]
        mask = None
        if masked:
            shape = (block_q, block_k)
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            mask = (k_pos <= q_pos) & (k_pos >= pad)
            if n_keys % block_k:
                # a partial last key block holds stale memory past the
                # keys' end: masked scores there are selected away, but a
                # probability of 0 times a stale NaN value is NaN, so the
                # rows are zeroed before anything is expanded from them. A
                # block that reaches past the keys' end is never interior
                row_ok = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, 1), 0) < n_keys
                blk = jnp.where(row_ok, blk, jnp.zeros_like(blk))
        c, kr = blk[:, :rank], blk[:, rank:]

        def expanded(w_ref, g):
            # the head's keys or values of this block, rounded as the
            # caller's einsum rounded them when it expanded them into HBM
            return jnp.dot(c, w_ref[g], preferred_element_type=jnp.float32
                           ).astype(c.dtype)                 # [bk, dn | dv]

        def _scores(g):
            s = jax.lax.dot_general(
                qn_ref[0, g], expanded(wk_ref, g), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(
                qr_ref[0, g], kr, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s * scale                                    # [bq, bk]
            return jnp.where(mask, s, _NEG) if masked else s

        def _attend(g, s):
            rows = rows_of(g)
            m_prev = m_ref[rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[rows] = jnp.broadcast_to(
                alpha * l_ref[rows, :1] + jnp.sum(p, axis=-1, keepdims=True),
                (block_q, l_ref.shape[1]))
            # the values are expanded here, beside the exponentials
            v = expanded(wv_ref, g)
            acc_ref[rows] = alpha * acc_ref[rows] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[rows] = jnp.broadcast_to(m_new, (block_q, m_ref.shape[1]))

        for_each_head(_scores, _attend)

    above, under, interior = _tile_class(q_start, k_start, pad,
                                         block_q, block_k)

    @pl.when(interior)
    def _interior():
        _accumulate(False)

    @pl.when(~(above | under | interior))
    def _edge():
        _accumulate(True)

    @pl.when(j == nj - 1)
    def _finalize():
        # a query row under its pad saw only masked scores (l > 0 still:
        # exp(0) sums) or, its whole block under the pad, no tile at all
        # (0 / 1e-30): finite either way, the caller never reads it
        def _store(g, rows):
            o_ref[0, g] = (acc_ref[rows] / jnp.maximum(l_ref[rows, :1], 1e-30)
                           ).astype(o_ref.dtype)

        for_each_head(rows_of, _store)


@functools.partial(jax.jit, static_argnames=(
    "scale", "q_offset", "block_q", "block_k", "interpret"))
def mla_prefill_attention(q_nope, q_rope, latent, wk, wv, pad_lens, *,
                          scale: float, q_offset: int = 0,
                          layer_idx=None, row_offset=0,
                          block_q: int | None = None,
                          block_k: int | None = None,
                          interpret: bool = False):
    """Causal attention of ``S`` queries at cache slots ``[q_offset,
    q_offset + S)`` over the ``T = q_offset + S`` keys before them.

    q_nope [B, H, S, dn], q_rope [B, H, S, dr]; latent [B, T, rank + dr]
    is the cache's rows of those slots (``c_kv`` then the one rotated key
    all heads share) — or, with ``layer_idx``, the stacked cache [L, Bc, C,
    rank + dr] itself, read in place: that layer, query row b's keys at
    batch row ``row_offset + b``, its first T slots; wk [H, rank, dn] and
    wv [H, rank, dv] are the two halves of ``W_kvb`` a head, in the
    latent's type: the kernel expands a key block's ``k_nope = c_kv wk[h]``
    and ``v = c_kv wv[h]`` itself; pad_lens [B] left pads. Returns [B, H, S,
    dv]. The cell is chosen from the shapes (_prefill_geometry);
    ``block_q``/``block_k`` are for tests."""
    B, H, S, dn = q_nope.shape
    dr = q_rope.shape[-1]
    rank, dv = wk.shape[1], wv.shape[2]
    T = q_offset + S
    if layer_idx is None:
        if latent.shape[1] != T:
            raise ValueError(f"{latent.shape[1]} keys for queries at "
                             f"[{q_offset}, {T})")
        first_row = 0
    else:
        # every layer's batch rows in one axis: a reshape that moves nothing
        first_row = layer_idx * latent.shape[1] + row_offset
        latent = latent.reshape((-1,) + latent.shape[2:])
    if latent.shape[1] < T:
        raise ValueError(f"a cache of {latent.shape[1]} slots for queries "
                         f"at [{q_offset}, {T})")
    if latent.shape[2] != rank + dr:
        raise ValueError(f"latent rows of {latent.shape[2]} for rank {rank} "
                         f"and {dr} rotated lanes")
    # whole blocks at the engine's shapes (chunks and buckets are multiples
    # of 512 there); any other length gets a partial last block
    G, bq, bk = _prefill_geometry(H, S, T, block_q, block_k)
    off = q_offset

    def latent_block(b, h, i, j, pad, at):
        # clamp to the blocks this query block reads: a repeated index is
        # not fetched again, so dead blocks cost no DMA
        first = pad[b] // bk
        last = (off + i * bq + bq - 1) // bk
        return (at[1] + b, jnp.clip(j, jnp.minimum(first, last), last), 0)

    kernel = functools.partial(
        _prefill_kernel, group=G, block_q=bq, block_k=bk, n_keys=T,
        rank=rank, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // G, pl.cdiv(S, bq), pl.cdiv(T, bk)),
            in_specs=[
                pl.BlockSpec((1, G, bq, dn),
                             lambda b, h, i, j, pad, o: (b, h, i, 0)),
                pl.BlockSpec((1, G, bq, dr),
                             lambda b, h, i, j, pad, o: (b, h, i, 0)),
                pl.BlockSpec((1, bk, rank + dr), latent_block),
                # the group's weights: fetched when the group changes
                pl.BlockSpec((G, rank, dn),
                             lambda b, h, i, j, pad, o: (h, 0, 0)),
                pl.BlockSpec((G, rank, dv),
                             lambda b, h, i, j, pad, o: (h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, G, bq, dv),
                                   lambda b, h, i, j, pad, o: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((G * bq, dv), jnp.float32),
                pltpu.VMEM((G * bq, _LANES), jnp.float32),
                pltpu.VMEM((G * bq, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT_BYTES),
        interpret=interpret,
        # a contract: the device trace and the benchmark's metrics name this
        # kernel by it
        name="mla_prefill_attention",
    )(pad_lens.astype(jnp.int32),
      jnp.stack([jnp.asarray(x, jnp.int32) for x in (off, first_row)]),
      q_nope, q_rope, latent, wk, wv)


TILE_CLASSES = ("dead_causal", "dead_pad", "interior", "masked")


def prefill_tile_classes(pad_lens, S: int, T: int, q_offset: int = 0, *,
                         block_q: int | None = None,
                         block_k: int | None = None) -> dict:
    """What one mla_prefill_attention call computes, counted on the host a
    head: the (bq x bk) tiles of its grid by class — ``dead_causal``
    (wholly above the diagonal, skipped before the pad is looked at),
    ``dead_pad`` (under the row's pad), ``interior`` (no mask built) and
    ``masked`` — with ``tile`` = (bq, bk), ``scores_computed`` (interior +
    masked tiles, whole), ``scores_needed`` (pad <= key <= query) and
    ``keys_expanded`` (a computed tile expands its key block's bk keys and
    values from the latent, dead tiles none). Pure numpy: the wrapper's
    geometry, the kernel's class rule (_tile_class)."""
    import numpy as np

    _, bq, bk = _prefill_geometry(1, S, T, block_q, block_k)
    pad = np.asarray(pad_lens, np.int64).reshape(-1, 1, 1)
    q_start = q_offset + bq * np.arange(-(-S // bq), dtype=np.int64)
    k_start = bk * np.arange(-(-T // bk), dtype=np.int64)
    above, under, interior = _tile_class(
        q_start[None, :, None], k_start[None, None, :], pad, bq, bk)
    grid = np.select([above, under, interior], [0, 1, 2], default=3)
    out = {name: int((grid == c).sum()) for c, name in enumerate(TILE_CLASSES)}
    out["tile"] = (bq, bk)
    out["keys_expanded"] = (out["interior"] + out["masked"]) * bk
    out["scores_computed"] = out["keys_expanded"] * bq
    q_pos = q_offset + np.arange(S, dtype=np.int64)[None, :]
    out["scores_needed"] = int(np.maximum(q_pos - pad[:, 0] + 1, 0).sum())
    return out


# -- absorbed decode ----------------------------------------------------------


def _decode_kernel(lidx_ref, fill_ref, pad_ref, ql_ref, qr_ref, c_ref,
                   o_ref, acc_ref, m_ref, l_ref, *, block_k: int,
                   cache_len: int, rank: int, scale: float):
    # ql [1,H,rank] qr [1,H,dr] c [1,1,bk,rank+dr]
    b, j = pl.program_id(0), pl.program_id(1)
    nj = pl.num_programs(1)
    fill = fill_ref[0]
    pad = pad_ref[b]
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((k_start <= fill) & (k_start + block_k > pad))
    def _compute():
        blk = c_ref[0, 0]                                    # [bk, rank+dr]
        slot = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)
        # a partial tail block is fetched only to the cache's end; the rest
        # of its buffer is stale VMEM, NaN patterns included, and 0 * NaN
        # is NaN on the value side (ops/decode_attention._zero_past_cache)
        blk = jnp.where(slot < cache_len, blk, jnp.zeros_like(blk))
        c = blk[:, :rank]
        kr = blk[:, rank:]
        s = jax.lax.dot_general(
            ql_ref[0], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(
            qr_ref[0], kr, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = s * scale                                        # [H, bk]
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((k_pos <= fill) & (k_pos >= pad), s, _NEG)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "rank", "block_k", "interpret"))
def mla_decode_attention(q_lat, q_rope, latent_cache, layer_idx, pad_lens,
                         fill, *, scale: float, rank: int,
                         block_k: int = 512, interpret: bool = False):
    """One absorbed decode step over the stacked latent cache.

    q_lat [B, H, rank] (queries already folded through the key half of
    ``W_kvb``), q_rope [B, H, dr]; latent_cache [L, B, C, rank + dr];
    ``fill`` the last valid cache slot (inclusive, shared by the batch);
    pad_lens [B]. Returns o_lat [B, H, rank]: the caller folds it through
    the value half of ``W_kvb``."""
    B, H, _ = q_lat.shape
    dr = q_rope.shape[-1]
    C = latent_cache.shape[2]
    bk = min(block_k, C)

    def block_j(b, j, lidx, fill, pad):
        first = pad[b] // bk
        last = fill[0] // bk
        return (lidx[0], b, jnp.clip(j, jnp.minimum(first, last), last), 0)

    kernel = functools.partial(_decode_kernel, block_k=bk, cache_len=C,
                               rank=rank, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, pl.cdiv(C, bk)),
            in_specs=[
                pl.BlockSpec((1, H, rank), lambda b, j, *_: (b, 0, 0)),
                pl.BlockSpec((1, H, dr), lambda b, j, *_: (b, 0, 0)),
                pl.BlockSpec((1, 1, bk, rank + dr), block_j),
            ],
            out_specs=pl.BlockSpec((1, H, rank), lambda b, j, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, rank), jnp.float32),
                pltpu.VMEM((H, _LANES), jnp.float32),
                pltpu.VMEM((H, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        # a contract, as above
        name="mla_decode_attention",
    )(jnp.asarray(layer_idx, jnp.int32).reshape(1),
      jnp.asarray(fill, jnp.int32).reshape(1),
      pad_lens.astype(jnp.int32), q_lat, q_rope, latent_cache)
