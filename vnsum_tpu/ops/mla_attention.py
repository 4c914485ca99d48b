"""Pallas TPU attention kernels for latent attention (MLA).

A latent-attention layer caches one row of ``kv_lora_rank + rope`` values
per token (models/deepseek.py: 512 of normalised ``c_kv`` + 64 of rotated
``k_rope``), shared by every head. Two kernels read it, one per phase:

- ``mla_prefill_attention``: causal attention over keys and values that the
  caller EXPANDED from the latent for a piece of the batch (``k_nope`` and
  ``v`` per head, ``k_rope`` once for all heads). The query/key width
  (nope + rope = 192) differs from the value width (128), which the GQA
  kernels of ops/flash_attention.py cannot express. Left-padded rows and
  chunked prefill (``q_offset``) as there: a block above the diagonal or
  under a row's pad is neither fetched nor computed, a block that needs no
  mask builds none.
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


def _prefill_kernel(pad_ref, off_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                    o_ref, acc_ref, m_ref, l_ref, *, block_q: int,
                    block_k: int, n_keys: int, scale: float):
    # qn [1,1,bq,dn] qr [1,1,bq,dr] kn/v [1,1,bk,dn|dv] kr [1,bk,dr]
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)
    pad = pad_ref[b]
    q_start = off_ref[0] + i * block_q
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    seen = (k_start <= q_start + block_q - 1) & (k_start + block_k > pad)
    interior = (k_start + block_k - 1 <= q_start) & (k_start >= pad)

    def _accumulate(masked: bool):
        s = jax.lax.dot_general(
            qn_ref[0, 0], kn_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(
            qr_ref[0, 0], kr_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = s * scale                                        # [bq, bk]
        v = v_ref[0, 0]
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where((k_pos <= q_pos) & (k_pos >= pad), s, _NEG)
            # a partial last key block holds stale memory past the keys'
            # end: masked scores there are selected away, but a probability
            # of 0 times a stale NaN value is NaN. Such a block holds the
            # diagonal, so it is always a masked one
            v_slot = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            v = jnp.where(v_slot < n_keys, v, jnp.zeros_like(v))
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(seen & interior)
    def _interior():
        _accumulate(False)

    @pl.when(seen & ~interior)
    def _edge():
        _accumulate(True)

    @pl.when(j == nj - 1)
    def _finalize():
        # a query row wholly under its pad saw only masked scores: l > 0
        # still (exp(0) sums), the row is garbage the caller never reads
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "q_offset", "block_q", "block_k", "interpret"))
def mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, pad_lens, *,
                          scale: float, q_offset: int = 0,
                          block_q: int = 512, block_k: int = 512,
                          interpret: bool = False):
    """Causal attention of ``S`` queries at cache slots ``[q_offset,
    q_offset + S)`` over the ``T = q_offset + S`` keys before them.

    q_nope [B, H, S, dn], q_rope [B, H, S, dr]; k_nope [B, H, T, dn] and
    v [B, H, T, dv] are each head's keys and values expanded from the
    latent; k_rope [B, T, dr] is the one rotated key all heads share;
    pad_lens [B] left pads. Returns [B, H, S, dv]."""
    B, H, S, dn = q_nope.shape
    dr = q_rope.shape[-1]
    T, dv = v.shape[2], v.shape[3]
    if T != q_offset + S:
        raise ValueError(f"{T} keys for queries at [{q_offset}, {q_offset + S})")
    # whole blocks at the engine's shapes (chunks and buckets are multiples
    # of 512 there); any other length gets a partial last block
    bq, bk = min(block_q, S), min(block_k, T)
    off = q_offset

    def visible_j(b, i, j, pad, _off):
        # clamp to the blocks this query block reads: a repeated index is
        # not fetched again, so dead blocks cost no DMA
        first = pad[b] // bk
        last = (off + i * bq + bq - 1) // bk
        return jnp.clip(j, jnp.minimum(first, last), last)

    kernel = functools.partial(_prefill_kernel, block_q=bq, block_k=bk,
                               n_keys=T, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, pl.cdiv(S, bq), pl.cdiv(T, bk)),
            in_specs=[
                pl.BlockSpec((1, 1, bq, dn),
                             lambda b, h, i, j, pad, o: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bq, dr),
                             lambda b, h, i, j, pad, o: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bk, dn),
                             lambda b, h, i, j, pad, o:
                             (b, h, visible_j(b, i, j, pad, o), 0)),
                pl.BlockSpec((1, bk, dr),
                             lambda b, h, i, j, pad, o:
                             (b, visible_j(b, i, j, pad, o), 0)),
                pl.BlockSpec((1, 1, bk, dv),
                             lambda b, h, i, j, pad, o:
                             (b, h, visible_j(b, i, j, pad, o), 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, dv),
                                   lambda b, h, i, j, pad, o: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, dv), jnp.float32),
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        # a contract: the device trace and the benchmark's metrics name this
        # kernel by it
        name="mla_prefill_attention",
    )(pad_lens.astype(jnp.int32), jnp.full((1,), off, jnp.int32),
      q_nope, q_rope, k_nope, k_rope, v)


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
