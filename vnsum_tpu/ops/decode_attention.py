"""Pallas TPU decode attention over the full stacked KV cache.

Single-token decode attention is pure HBM streaming, but the XLA lowering of
the naive formulation adds ~3x traffic on top of the mandatory cache read
(measured on a 48x1088 Llama-3.2-3B cache, 25.3 GB touched per step vs ~9 GB
mandatory):

- `dynamic_index_in_dim(cache, layer)` materializes a per-layer cache copy
  inside the layer scan (107 MB x 2 x 28 layers per step);
- XLA pins the while-loop cache carry to one layout while the attention
  einsum prefers another, inserting TWO whole-cache layout-conversion copies
  (3.1 GB each) per step, in each direction.

This kernel sidesteps both by consuming the stacked [L, B, KV, C, hd] cache
directly: the layer index arrives via scalar prefetch and only steers the
BlockSpec index_map, so exactly the needed blocks are DMA'd — no extraction,
no conversion.

Block geometry matters more than anything here: a first cut that gridded
over (B, KV, C/BK) issued tens-of-KB DMAs and ran 3x SLOWER than the XLA
path (92 ms/step) because the pipeline never got deep enough. This version
grids over (B/BB, ceil(C/BK)) with each block carrying all KV heads and BB
batch rows (~MB-scale DMAs); the BB x KV attention groups are computed as an
unrolled loop of small MXU dots against VMEM-resident tiles.

Blocks past the current fill position are elided by clamping the index_map
(Pallas skips the DMA when consecutive grid steps address the same block)
and `pl.when` skips their compute, so a step at fill=600 in a C=1152 cache
reads only ~half the cache. The key block (``block_k``, 128) at the widest
group a cell runs — G=16 on 2 KV heads, 12 rows at fill 8,320 of an int8
cache, one call timed from the host, PR 47: 128 / 256 / 512 / 1024 slots
0.220 / 0.200 / 0.203 / 0.205 ms, of which ~0.2 ms is the call itself — sets
nothing a host's clock can tell apart, and stays; the cell's traced run
gives the kernel's own seconds (``nemotron_decode_attention_roofline``).

int8 KV caches (models.llama.init_kv_cache(quantized=True)) stream half the
bytes again: the kernel loads int8 K/V blocks plus per-(token, head) f32
scales and folds dequantization into the softmax algebra — scores multiply
by the K scale per cache slot, and probabilities multiply by the V scale
before the PV dot (diag-scale commutes through both contractions).

Inference-only (no VJP). The reference has no analog — its decode happens
inside Ollama (SURVEY.md §1 L1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (
    _LANES,
    _NEG,
    VMEM_LIMIT_BYTES,
    head_dim_supported,
)


def _zero_past_cache(vb, k_start, cache_len: int):
    """Zero the value rows of a K/V block that lie past the end of the cache.

    A partial tail block (cache_len % block_k != 0) is only DMA'd up to the
    cache's end; the rest of its VMEM buffer holds whatever was there, NaN
    bit patterns included. Those slots' scores are masked, but a probability
    of 0 still meets the value side in a multiply, and 0 * NaN is NaN (see
    ops/flash_attention._kernel). With an int8 cache the values are finite
    and the kernels zero the f32 V-scale row instead. vb [BKV, BK, hd]."""
    v_slot = k_start + jax.lax.broadcasted_iota(jnp.int32, vb.shape[:2] + (1,), 1)
    return jnp.where(v_slot < cache_len, vb, 0.0)


def _kernel(
    lidx_ref,  # [1] int32 (SMEM) — layer to read
    fill_ref,  # [1] int32 (SMEM) — last valid cache slot (inclusive)
    win_ref,   # [1] int32 (SMEM) — sliding window; 0 = global
    *refs,
    block_b: int,
    block_k: int,
    n_kv: int,
    cache_len: int,
    scale: float,
    quantized: bool,
    return_partials: bool = False,
):
    if return_partials:
        # outputs are the UNNORMALIZED online-softmax state (acc, m, l) —
        # the shard-local form the long-context path LSE-merges across the
        # seq axis (backend.long_context make_long_decode_attention)
        if quantized:
            (q_ref, pads_ref, k_ref, v_ref, ks_ref, vs_ref,
             o_ref, mo_ref, lo_ref, acc_ref, m_ref, l_ref) = refs
        else:
            (q_ref, pads_ref, k_ref, v_ref,
             o_ref, mo_ref, lo_ref, acc_ref, m_ref, l_ref) = refs
            ks_ref = vs_ref = None
    elif quantized:
        q_ref, pads_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
        mo_ref = lo_ref = None
    else:
        q_ref, pads_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = mo_ref = lo_ref = None
    # q_ref/o_ref [1, BB*KV, G, hd] (host pre-merges the batch/head dims —
    # Mosaic supports MERGING leading dims in-kernel but not splitting them,
    # and tpu.matmul takes a single batch dim); pads_ref [1, BB*KV, 1, BK]
    # (per-row left-pads pre-broadcast on host: SMEM scalars can't be
    # stacked into a vector in-kernel); k_ref/v_ref [1, BB, KV, BK, hd];
    # ks_ref/vs_ref [1, BB, KV, BK]; scratch acc [BB*KV, G, hd],
    # m/l [BB*KV, G, LANES]

    j = pl.program_id(1)
    nj = pl.num_programs(1)
    fill = fill_ref[0]
    win = win_ref[0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # blocks wholly past the fill point — or, with a sliding window, wholly
    # below the window floor — were never DMA'd (clamped index_map); skip
    # their compute so the clamped duplicate block isn't double-counted
    @pl.when(
        (j * block_k <= fill)
        & ((win == 0) | (j * block_k + block_k - 1 >= fill - win + 1))
    )
    def _compute():
        G = q_ref.shape[2]
        hd = q_ref.shape[3]
        BKV = block_b * n_kv
        # one batched dot over the merged (BB, KV) dim instead of BBxKV
        # unrolled small dots: the unrolled form was VPU-bound (its softmax
        # bookkeeping ran once per head) and an int8 cache gave no speedup
        qb = q_ref[0].astype(jnp.float32)                       # [BKV, G, hd]
        kb = k_ref[0].astype(jnp.float32).reshape(BKV, block_k, hd)
        vb = v_ref[0].astype(jnp.float32).reshape(BKV, block_k, hd)

        s = jax.lax.dot_general(
            qb, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [BKV, G, BK]
        if quantized:
            s = s * ks_ref[0].reshape(BKV, 1, block_k)

        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (BKV, 1, block_k), 2
        )
        in_cache = k_pos < cache_len
        if not quantized:
            vb = _zero_past_cache(vb, j * block_k, cache_len)
        mask = (k_pos >= pads_ref[0]) & (k_pos <= fill)  # [BKV, 1, BK]
        # window in slot space, matching the dense path's k_slot > fill - win
        mask = mask & ((win == 0) | (k_pos > fill - win))
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[:, :, :1]                         # [BKV, G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)

        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :, :1] * corr + jnp.sum(p, axis=2, keepdims=True),
            l_ref.shape,
        )
        if quantized:
            p = p * jnp.where(
                in_cache, vs_ref[0].reshape(BKV, 1, block_k), 0.0
            )
        pv = jax.lax.dot_general(
            p, vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [BKV, G, hd]
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        if return_partials:
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)
            mo_ref[0] = m_ref[...]
            lo_ref[0] = l_ref[...]
        else:
            l = jnp.maximum(l_ref[:, :, :1], 1e-30)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _verify_kernel(
    lidx_ref,   # [1] int32 (SMEM) — layer to read
    fmax_ref,   # [1] int32 (SMEM) — max over rows of (fill + Sq - 1)
    fmin_ref,   # [1] int32 (SMEM) — min over rows of fill
    win_ref,    # [1] int32 (SMEM) — sliding window; 0 = global
    *refs,
    block_b: int,
    block_k: int,
    n_kv: int,
    n_q: int,
    cache_len: int,
    scale: float,
    quantized: bool,
):
    """Multi-position decode ("verify") attention for speculative decoding.

    Same block geometry and online-softmax bookkeeping as _kernel, but each
    row carries Sq query positions at PER-ROW cache offsets: query (b, s)
    attends slots pad_b <= j <= fill_b + s. The per-(row, query) visibility
    limit arrives as a pre-broadcast VMEM operand (limits_ref) because the
    merged (bb*KV, Sq*G) row layout cannot be assembled from SMEM scalars
    in-kernel; the SCALAR fill bounds (fmax/fmin) only steer DMA elision."""
    if quantized:
        (q_ref, pads_ref, lim_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    else:
        (q_ref, pads_ref, lim_ref, k_ref, v_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
        ks_ref = vs_ref = None
    # q_ref/o_ref [1, BB*KV, Sq*G, hd] (row index s*G + g: query position s,
    # group head g); pads_ref [1, BB*KV, 1, BK]; lim_ref [1, BB*KV, SqG,
    # LANES] (per-(row, query) last visible slot, lane-broadcast);
    # k_ref/v_ref [1, BB, KV, BK, hd]; scratch acc [BB*KV, SqG, hd],
    # m/l [BB*KV, SqG, LANES]

    j = pl.program_id(1)
    nj = pl.num_programs(1)
    fill_hi = fmax_ref[0]
    fill_lo = fmin_ref[0]
    win = win_ref[0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # blocks wholly past EVERY row's last visible slot — or, with a window,
    # wholly below every row's window floor — were never DMA'd (clamped
    # index_map); skip their compute so the duplicate block isn't counted
    @pl.when(
        (j * block_k <= fill_hi)
        & ((win == 0) | (j * block_k + block_k - 1 >= fill_lo - win + 1))
    )
    def _compute():
        hd = q_ref.shape[3]
        BKV = block_b * n_kv
        SG = q_ref.shape[2]
        qb = q_ref[0].astype(jnp.float32)                       # [BKV, SG, hd]
        kb = k_ref[0].astype(jnp.float32).reshape(BKV, block_k, hd)
        vb = v_ref[0].astype(jnp.float32).reshape(BKV, block_k, hd)

        s = jax.lax.dot_general(
            qb, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [BKV, SG, BK]
        if quantized:
            s = s * ks_ref[0].reshape(BKV, 1, block_k)

        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (BKV, 1, block_k), 2
        )
        in_cache = k_pos < cache_len
        if not quantized:
            vb = _zero_past_cache(vb, j * block_k, cache_len)
        limit = lim_ref[0, :, :, :1]                     # [BKV, SG, 1]
        mask = (k_pos >= pads_ref[0]) & (k_pos <= limit)
        # window in slot space per query: k_slot > (fill_b + s) - win
        mask = mask & ((win == 0) | (k_pos > limit - win))
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[:, :, :1]                         # [BKV, SG, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)

        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :, :1] * corr + jnp.sum(p, axis=2, keepdims=True),
            l_ref.shape,
        )
        if quantized:
            p = p * jnp.where(
                in_cache, vs_ref[0].reshape(BKV, 1, block_k), 0.0
            )
        pv = jax.lax.dot_general(
            p, vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [BKV, SG, hd]
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _pick_block_b(batch: int) -> int:
    for b in (8, 4, 2, 1):
        if batch % b == 0:
            return b
    return 1


def supports_decode(cache_len: int, head_dim: int) -> bool:
    """Ceil-div grid handles any C; only the head dim matters (whole lane
    tiles, or half of one: flash_attention.head_dim_supported)."""
    return head_dim_supported(head_dim)


@functools.partial(
    jax.jit,
    static_argnames=("q_per_kv", "block_k", "interpret", "return_partials"),
)
def flash_decode_attention(
    q: jax.Array,          # [B, 1, H, hd]
    cache: dict,           # stacked {"k","v"[, "ks","vs"]} (llama.init_kv_cache)
    layer_idx: jax.Array,  # scalar int32
    pad_lens: jax.Array,   # [B] int32
    fill: jax.Array,       # scalar int32 — last valid slot (inclusive)
    q_per_kv: int,
    window: jax.Array | None = None,  # scalar int32; 0/None = global
    *,
    block_k: int = 128,
    interpret: bool = False,
    return_partials: bool = False,
) -> jax.Array:
    """Semantics match _attention(q, dequantized cache[layer],
    mask=pad<=j<=fill); returns [B, 1, H, hd]. ``window`` > 0 restricts to
    the last ``window`` slots (Gemma sliding layers): below-window blocks
    are compute-skipped and DMA-elided like past-fill blocks, so a sliding
    layer's step reads only ~window worth of cache however long the fill.

    ``return_partials=True`` returns the unnormalized online-softmax state
    ``(o [B, H, hd] f32, m [B, H] f32, l [B, H] f32)`` instead — the
    shard-local partial the long-context decode LSE-merges across the seq
    axis (same contract as backend.long_context._prefill_partial_local)."""
    k_all, v_all = cache["k"], cache["v"]
    quantized = "ks" in cache
    B, S, H, hd = q.shape
    L, _, KV, C, _ = k_all.shape
    if S != 1:
        raise ValueError(f"decode kernel is single-token (S=1), got S={S}")
    if not (head_dim_supported(hd) or interpret):
        raise ValueError(f"unsupported decode head_dim={hd}")
    bk = min(block_k, C)
    bb = _pick_block_b(B)

    qg = q.reshape(B // bb, bb * KV, q_per_kv, hd)
    # per-row left-pads, pre-broadcast to the merged-row block shape (the
    # kernel can't assemble a vector out of SMEM scalars)
    pads = jnp.broadcast_to(
        pad_lens.astype(jnp.int32).reshape(B // bb, bb, 1, 1, 1),
        (B // bb, bb, KV, 1, bk),
    ).reshape(B // bb, bb * KV, 1, bk)
    grid = (B // bb, pl.cdiv(C, bk))

    def visible_j(j, fill, win, blk=bk):
        # clamp past-fill (and, under a window, below-window) blocks onto
        # the nearest visible block: consecutive grid steps then address the
        # same block and Pallas elides the DMA
        lo = jnp.where(
            win[0] > 0, jnp.maximum(fill[0] - win[0] + 1, 0) // blk, 0
        )
        return jnp.clip(j, lo, fill[0] // blk)

    def kv_index(b, j, lidx, fill, win):
        return (lidx[0], b, 0, visible_j(j, fill, win), 0)

    def scale_index(b, j, lidx, fill, win):
        return (lidx[0], b, 0, visible_j(j, fill, win))

    in_specs = [
        pl.BlockSpec(
            (1, bb * KV, q_per_kv, hd),
            lambda b, j, lidx, fill, win: (b, 0, 0, 0),
        ),
        pl.BlockSpec(
            (1, bb * KV, 1, bk), lambda b, j, lidx, fill, win: (b, 0, 0, 0)
        ),
        pl.BlockSpec((1, bb, KV, bk, hd), kv_index),
        pl.BlockSpec((1, bb, KV, bk, hd), kv_index),
    ]
    operands = [qg, pads, k_all, v_all]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, bb, KV, bk), scale_index),
            pl.BlockSpec((1, bb, KV, bk), scale_index),
        ]
        operands += [cache["ks"], cache["vs"]]

    kernel = functools.partial(
        _kernel, block_b=bb, block_k=bk, n_kv=KV, cache_len=C,
        scale=1.0 / (hd ** 0.5), quantized=quantized,
        return_partials=return_partials,
    )
    out_block = lambda shape: pl.BlockSpec(  # noqa: E731
        (1, *shape), lambda b, j, lidx, fill, win: (b,) + (0,) * len(shape)
    )
    if return_partials:
        out_specs = (
            out_block((bb * KV, q_per_kv, hd)),
            out_block((bb * KV, q_per_kv, _LANES)),
            out_block((bb * KV, q_per_kv, _LANES)),
        )
        out_shape = (
            jax.ShapeDtypeStruct((B // bb, bb * KV, q_per_kv, hd), jnp.float32),
            jax.ShapeDtypeStruct((B // bb, bb * KV, q_per_kv, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((B // bb, bb * KV, q_per_kv, _LANES), jnp.float32),
        )
    else:
        out_specs = out_block((bb * KV, q_per_kv, hd))
        out_shape = jax.ShapeDtypeStruct(
            (B // bb, bb * KV, q_per_kv, hd), q.dtype
        )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((bb * KV, q_per_kv, hd), jnp.float32),
                pltpu.VMEM((bb * KV, q_per_kv, _LANES), jnp.float32),
                pltpu.VMEM((bb * KV, q_per_kv, _LANES), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        # a contract: the device trace, the ledger and benchmark metrics name
        # this kernel by it, whatever the wrapper is called
        name="flash_decode_attention",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        jnp.asarray(fill, jnp.int32).reshape(1),
        jnp.asarray(0 if window is None else window, jnp.int32).reshape(1),
        *operands,
    )
    if return_partials:
        o, m, l = out
        return (
            o.reshape(B, H, hd),
            m[..., 0].reshape(B, H),
            l[..., 0].reshape(B, H),
        )
    return out.reshape(B, 1, H, hd)


@functools.partial(
    jax.jit,
    static_argnames=("q_per_kv", "block_k", "interpret"),
)
def flash_spec_verify_attention(
    q: jax.Array,          # [B, Sq, H, hd] — Sq = spec_k + 1 verify queries
    cache: dict,           # stacked {"k","v"[, "ks","vs"]} (llama.init_kv_cache)
    layer_idx: jax.Array,  # scalar int32
    pad_lens: jax.Array,   # [B] int32
    fills: jax.Array,      # [B] int32 — per-row cache slot of query 0
    q_per_kv: int,
    window: jax.Array | None = None,  # scalar int32; 0/None = global
    *,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Multi-position decode attention for the speculative verify step:
    query (b, s) sits at cache slot fills_b + s and attends
    pad_b <= j <= fills_b + s (models.llama.verify_attention_mask
    semantics). Returns [B, Sq, H, hd].

    This is the decode kernel generalized along two axes at once: several
    query positions per row (the Sq*G rows of one grid cell share each K/V
    block, so a verify step streams the cache ONCE for all k+1 positions —
    the whole point of batched verification) and PER-ROW fill offsets
    (after ragged draft acceptance, rows sit at different cache lengths).
    DMA elision clamps against the batch-max fill; masking uses the exact
    per-(row, query) limit."""
    k_all, v_all = cache["k"], cache["v"]
    quantized = "ks" in cache
    B, Sq, H, hd = q.shape
    L, _, KV, C, _ = k_all.shape
    if not (head_dim_supported(hd) or interpret):
        raise ValueError(f"unsupported verify head_dim={hd}")
    G = q_per_kv
    if H != KV * G:
        raise ValueError(f"q_per_kv={q_per_kv} inconsistent with H/KV={H // KV}")
    bk = min(block_k, C)
    bb = _pick_block_b(B)
    SG = Sq * G

    # merged layout [B//bb, bb*KV, Sq*G, hd] with query position MAJOR over
    # the group heads (row s*G + g) so one limits row covers a position's
    # whole GQA group
    qg = (
        q.transpose(0, 2, 1, 3)               # [B, H, Sq, hd]
        .reshape(B, KV, G, Sq, hd)
        .transpose(0, 1, 3, 2, 4)             # [B, KV, Sq, G, hd]
        .reshape(B // bb, bb * KV, SG, hd)
    )
    pads = jnp.broadcast_to(
        pad_lens.astype(jnp.int32).reshape(B // bb, bb, 1, 1, 1),
        (B // bb, bb, KV, 1, bk),
    ).reshape(B // bb, bb * KV, 1, bk)
    # per-(row, query) last visible slot, lane-broadcast (the kernel cannot
    # assemble the merged-row vector from SMEM scalars)
    limits = fills.astype(jnp.int32)[:, None] + jnp.arange(Sq, dtype=jnp.int32)
    limits = jnp.broadcast_to(
        limits[:, None, :, None, None], (B, KV, Sq, G, _LANES)
    ).reshape(B // bb, bb * KV, SG, _LANES)
    fill_hi = jnp.max(fills) + Sq - 1
    fill_lo = jnp.min(fills)
    grid = (B // bb, pl.cdiv(C, bk))

    def visible_j(j, fmax, fmin, win, blk=bk):
        lo = jnp.where(
            win[0] > 0, jnp.maximum(fmin[0] - win[0] + 1, 0) // blk, 0
        )
        return jnp.clip(j, lo, fmax[0] // blk)

    def kv_index(b, j, lidx, fmax, fmin, win):
        return (lidx[0], b, 0, visible_j(j, fmax, fmin, win), 0)

    def scale_index(b, j, lidx, fmax, fmin, win):
        return (lidx[0], b, 0, visible_j(j, fmax, fmin, win))

    row_block = lambda shape: pl.BlockSpec(  # noqa: E731
        (1, *shape), lambda b, j, lidx, fmax, fmin, win: (b,) + (0,) * len(shape)
    )
    in_specs = [
        row_block((bb * KV, SG, hd)),
        row_block((bb * KV, 1, bk)),
        row_block((bb * KV, SG, _LANES)),
        pl.BlockSpec((1, bb, KV, bk, hd), kv_index),
        pl.BlockSpec((1, bb, KV, bk, hd), kv_index),
    ]
    operands = [qg, pads, limits, k_all, v_all]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, bb, KV, bk), scale_index),
            pl.BlockSpec((1, bb, KV, bk), scale_index),
        ]
        operands += [cache["ks"], cache["vs"]]

    kernel = functools.partial(
        _verify_kernel, block_b=bb, block_k=bk, n_kv=KV, n_q=Sq, cache_len=C,
        scale=1.0 / (hd ** 0.5), quantized=quantized,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=in_specs,
            out_specs=row_block((bb * KV, SG, hd)),
            scratch_shapes=[
                pltpu.VMEM((bb * KV, SG, hd), jnp.float32),
                pltpu.VMEM((bb * KV, SG, _LANES), jnp.float32),
                pltpu.VMEM((bb * KV, SG, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B // bb, bb * KV, SG, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        # a contract: the device trace, the ledger and benchmark metrics name
        # this kernel by it, whatever the wrapper is called
        name="flash_spec_verify_attention",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        jnp.asarray(fill_hi, jnp.int32).reshape(1),
        jnp.asarray(fill_lo, jnp.int32).reshape(1),
        jnp.asarray(0 if window is None else window, jnp.int32).reshape(1),
        *operands,
    )
    return (
        out.reshape(B, KV, Sq, G, hd)
        .transpose(0, 2, 1, 3, 4)             # [B, Sq, KV, G, hd]
        .reshape(B, Sq, H, hd)
    )
