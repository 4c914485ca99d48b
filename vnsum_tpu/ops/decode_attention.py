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
path (92 ms/step) because the pipeline never got deep enough. Until PR 49 a
block carried all KV heads of ``bb`` batch rows over 128 slots (~MB-scale
DMAs), which left every row read from slot 0: a row's left pad was copied,
upcast, multiplied and masked away. Now a block carries all KV heads of ONE
row over ``bk`` slots, ``bk`` chosen from the shapes so that the block's
keys stay ~512 KiB as VMEM tiles them — a slot weighed as 8 heads of 128
at most, so 1 MiB at 16 heads — (``decode_block_k``: 512 slots at KV=8 x hd=128, at Phi-4's KV=10 and at
Ouro's KV=16, 1,024 at SmallThinker's KV=4 and at Granite-4.0-H's and
LFM2's 8 heads of 64, which the cache holds as 4 tiles of 128 — "two KV
heads a lane tile" below —, 2,048 at Nemotron-H's KV=2; half the slots for
a bfloat16 cache), and each row
walks its own blocks: grid step j of row b is block ``first_b + j``, from
the block of the row's first real slot (under a sliding window, of its
window's floor) to the block of its fill, and the grid takes as many steps
a row as the row with most blocks needs (``_row_blocks``, computed once a
call and prefetched: deriving the two bounds in each of the four index
maps cost the all-live Qwen3 step 3%). A row with fewer blocks stays on
its last one — Pallas skips the DMA when consecutive grid steps address
the same block — and ``pl.when`` skips the compute, so a step at fill=600
in a C=1152 cache reads only ~half the cache, a row that is three quarters
pad a quarter of its slots, and a window layer's call is a few steps a row
however long the cache. The KV attention groups are one batched MXU dot
against VMEM-resident tiles.

The sweep that chose it (PR 49, TPU v5e, ``scripts/profile_decode_blocks.py``:
ms a call inside a jitted loop of 72 calls, int8 cache at fill 8,320;
all-live rows / the cell's pads — the served mix's four rows, an offline
group's four tails of 20-75% pad):

  shape (rows x KV x G, hd)        bb x 128 (PR 48)    one row x bk (rule)
  Qwen3 offline  8 x 8 x 4         0.2047 / 0.2054     0.2066 / 0.1684  (512)
  Qwen3 served   4 x 8 x 4, Sq=1   0.1162 / 0.1147     0.1107 / 0.0694  (512)
  Phi-4         12 x 10 x 4        0.4027 / 0.4017     0.3633 / 0.3180  (512)
  SmallThinker  24 x 4 x 7         0.3412 / 0.3433     0.3199 / 0.3024  (1,024)
   ... window 4,096                0.1972 / 0.1968     0.1812 / 0.1790
  Laguna full   12 x 8 x 6         0.3427 / 0.3442     0.3050 / 0.2671  (512)
   ... sliding, G=9, window 512    0.0785 / 0.0771     0.0480 / 0.0465
  Granite-H     24 x 8 x 4, hd 64  0.6844 / 0.6867     0.6873 / 0.6503  (512)
   ... two KV heads a tile (PR 55; LFM2's shape is the same)
                                                       0.3264 / 0.3119  (1,024)
  Nemotron-H    12 x 2 x 16        0.1663 / 0.1679     0.0986 / 0.0922  (2,048)

Off the rule: 256 slots at KV=8 read 0.286 (all-live Qwen3), 1,024 read
0.2103 and 2,048 0.2194; SmallThinker at 512 0.3804; Granite at 1,024
0.6987 / 0.6647; Nemotron-H at 512 / 1,024 0.159 / 0.111. Whole-grid
residency of the queries and outputs (no block a row) read the same as a
block a row; three buffers a block are refused by this Mosaic. The one
shape that did not gain is the one whose old block was already 1 MiB of
keys (8 rows x 8 KV heads): +0.9% all-live, inside the sweep's own repeat
(0.2025-0.2054 over four readings of the old kernel).

**Two KV heads a lane tile** (PR 55). A head of 64 fills half a lane tile,
and until PR 55 the cache held one a tile: the kernel copied, upcast and
multiplied tiles that were half empty, and Granite's row above read 1.5 x
Qwen3's real bytes in 3.3 x its time (0.6873 ms for 0.2066; 311 GB/s where
heads of 128 read 660-720). Now ``models.llama.init_kv_cache`` stores heads
2p and 2p+1 side by side, ``[L, B, KV/2, C, 128]`` with the scales still a
head, and ``_attend`` hands the kernel the tiles as its heads: a tile's rows
are head a's R merged rows over head b's, each query in its own head's 64
lanes and zeros in the other's (``place_in_own_lanes``, a few KB a call in
XLA), so ONE product against the key tile is both heads' scores — the other
head's lanes meet zeros, and adding exact zeros in float32 changes nothing
—, ``P.V`` against the value tile is both heads' outputs, each in its own
lanes (``take_own_lanes``), in the passes one padded head cost, and the
block is what the rule gives 4 heads of 128. Inside the kernel only the
scales know a tile is two heads (``scale_rows``); the mask, the running max
and the sums go by row. The same sweep, this tree against its parent's
kernel on the same numbers (all-live / the group's four tails): 0.6875 /
0.6507 -> **0.3264 / 0.3119** at the rule's 1,024 slots; 512 read 0.3848 /
0.3644, 2,048 0.3386 / 0.3245. The call's least time at 819 GB/s (217 MB of
keys, values and scales) is 81% of 0.3264 ms: 666 GB/s, the pace heads of
128 read at. The outputs equal the
one-head-a-tile kernel's (bit for bit in interpret mode but where a tile's
2R rows sum in another order than R: 1.9e-6 at R = 1,
``tests/test_kv_head_pairs.py``).

At 16 KV heads of one query head each (Ouro-2.6B; PR 50, the same script:
8 rows, all-live / the group's four tails): 128 slots 0.5475 / 0.4509; 256
— 512 KiB of keys, the byte rule's own — 0.4117 / 0.3353; **512 0.3936 /
0.3146**; 1,024 0.4050 / 0.3306. A block of 1 MiB of keys wins by 4-6%
here as 512 slots did at KV=8: what a grid step costs beside its bytes is
paid a block, and under 512 slots a row of 8k has more than 17 of them. So
the rule weighs a slot as 8 heads of 128 at most: more heads a slot get the
slots 8 heads get, not fewer; no other shape a cell or a test runs is wider
than that, and their blocks are what they were. 0.3936 ms a call is 87% of the
call's least time at 819 GB/s (281 MB of keys, values and scales).

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
    cache_heads_per_tile,
    head_dim_supported,
)


# key bytes of a K/V block in the cache's own type, and the most key
# elements a slot is weighed at: 8 heads of 128, so that a slot of more
# heads is no reason for fewer slots than those 8 get (module docstring:
# the sweeps that chose them)
_BLOCK_KEY_BYTES = 512 * 1024
_SLOT_KEYS_MOST = 1024


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


def decode_block_k(n_kv: int, head_dim: int, itemsize: int,
                   cache_len: int) -> int:
    """Key slots of a K/V block, which holds ONE row's KV heads: the fewest
    whole lane tiles whose keys ``[KV, bk, hd]`` hold ``_BLOCK_KEY_BYTES``
    as VMEM tiles them (a head narrower than a lane tile padded to whole
    lanes: what an odd count of 64-wide heads costs; heads of 64 that pair
    off arrive here as ``KV/2`` tiles of 128, ``_attend``), a slot
    weighed at ``_SLOT_KEYS_MOST`` key elements at most (many KV heads: 16
    of 128 weigh 512 KiB at 256 slots and read 4-6% faster at the 512 that
    8 heads get), no more than
    leave half of ``VMEM_LIMIT_BYTES`` free beside what a grid step keeps
    of them — K and V, two buffers each, and their float32 upcasts — and
    never more than the cache. Shapes alone decide (module docstring)."""
    tiled = n_kv * -(-head_dim // _LANES) * _LANES   # a slot's key elements
    most = VMEM_LIMIT_BYTES // 2 // (tiled * (4 * itemsize + 8))
    weighed = min(tiled, _SLOT_KEYS_MOST) * itemsize
    slots = min(-(-_BLOCK_KEY_BYTES // weighed), most)
    return min(max(-(-slots // _LANES) * _LANES, _LANES), cache_len)


def _in_upper_lanes(x: jax.Array) -> jax.Array:
    # x [B, KV, ...]: whether head kv's lanes are its tile's upper half
    return (jnp.arange(x.shape[1]) % 2 == 1).reshape(
        (1, -1) + (1,) * (x.ndim - 2))


def place_in_own_lanes(x: jax.Array) -> jax.Array:
    """Queries x [B, KV, ..., hd] of heads stored two a lane tile ->
    [B, KV, ..., 2 * hd]: head kv's values in the lanes its keys hold of
    their tile (the lower half for an even head, the upper for an odd one)
    and zeros in its neighbour's, so that a product against the whole key
    tile is the head's own scores: the other lanes meet zeros, and adding
    exact zeros in float32 changes nothing."""
    upper, zeros = _in_upper_lanes(x), jnp.zeros_like(x)
    return jnp.concatenate(
        [jnp.where(upper, zeros, x), jnp.where(upper, x, zeros)], axis=-1)


def take_own_lanes(x: jax.Array) -> jax.Array:
    """The way back on x [B, KV, ..., 2 * hd], a head's probabilities
    against a whole value tile: the head's own lanes (the others hold its
    probabilities against its neighbour's values)."""
    hd = x.shape[-1] // 2
    return jnp.where(_in_upper_lanes(x), x[..., hd:], x[..., :hd])


def _row_blocks(pad, fill, n_q: int, win, block_k: int, cache_len: int):
    """(first, last) key block each row reads: from the block of its first
    real slot — under a window the block of its first query's window floor,
    where that lies higher — to the block of its last query's slot, or the
    cache's last. Grid step j of a row is block first + j, clamped at last
    (the index maps): the blocks under the row's pad, below its window and
    past its fill are never copied, and a row past its last block stays on
    it (consecutive grid steps that address the resident block copy
    nothing) while the kernel skips the compute. A row that is all pad has
    first > last: nothing is computed, its sums stay empty."""
    floor = jnp.where(win > 0, jnp.maximum(fill - win + 1, 0), 0)
    last = jnp.minimum(fill + n_q - 1, cache_len - 1) // block_k
    return jnp.maximum(pad, floor) // block_k, last


def _kernel(
    lidx_ref,   # [1] int32 (SMEM) — layer to read
    first_ref,  # [B] int32 (SMEM) — each row's first key block
    last_ref,   # [B] int32 (SMEM) — and its last (_row_blocks)
    fills_ref,  # [B] int32 (SMEM) — each row's cache slot of query 0
    win_ref,    # [1] int32 (SMEM) — sliding window; 0 = global
    pads_ref,   # [B] int32 (SMEM) — each row's left pad
    *refs,
    block_k: int,
    cache_len: int,
    scale: float,
    quantized: bool,
    per_query: bool,
    return_partials: bool,
    paired: bool,
):
    """One grid step of both kernels: row ``b``'s query positions (row
    s*G + g of the merged [KV, Sq*G] layout: position s, group head g)
    against key block ``j`` of that row. Query s sits at slot fill_b + s and
    attends pad_b <= slot <= fill_b + s. The single-token kernel's limit is
    the scalar fill; the verify kernel's per-(row, query) limit arrives as a
    lane-broadcast VMEM operand (``per_query``), because the merged rows
    cannot be assembled from SMEM scalars in-kernel.

    ``paired``: a K/V tile holds two KV heads, 64 lanes each, and "KV"
    below counts the tiles. A tile's R rows are then head a's merged rows
    over head b's, each with its values in its own head's lanes and zeros
    in the other's (``_attend``), so the one product a tile gives both
    heads' scores; the mask, the running max and the sums go by row as
    ever, and only the scales, a HEAD's, have to be told apart by row."""
    refs = list(refs)
    q_ref = refs.pop(0)                     # [1, KV, R, hd]
    lim_ref = refs.pop(0) if per_query else None  # [1, KV, R, LANES]
    k_ref, v_ref = refs.pop(0), refs.pop(0)       # [1, 1, KV, BK, hd]
    # [1, 1, KV, BK]; paired, a row a head: [1, 1, 2 KV, BK]
    ks_ref = refs.pop(0) if quantized else None
    vs_ref = refs.pop(0) if quantized else None
    # outputs [1, KV, R, hd] (+ m, l [1, KV, R, LANES] with return_partials:
    # the UNNORMALIZED online-softmax state, the shard-local form the
    # long-context path LSE-merges across the seq axis, backend.long_context
    # make_long_decode_attention); scratch acc [KV, R, hd], m/l [KV, R, LANES]
    *out_refs, acc_ref, m_ref, l_ref = refs

    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    pad = pads_ref[b]
    fill = fills_ref[b]
    win = win_ref[0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # grid step j is the row's j-th block from its first; the grid takes as
    # many steps a row as the row with most blocks needs, and a row with
    # fewer stays on its last block (clamped index_map: no DMA): skip the
    # compute so the duplicate block isn't double-counted
    jb = first_ref[b] + j

    def scale_rows(ref):
        """A block's scales against the scores' rows: [KV, 1 or R, BK]."""
        n_kv, n_rows = q_ref.shape[1:3]
        scales = ref[0, 0]
        if not paired:
            return scales.reshape(n_kv, 1, block_k)
        # the upper half of a tile's rows are its second head's
        second = jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, 1), 0) >= n_rows // 2
        return jnp.stack([
            jnp.where(second, scales[2 * t + 1:2 * t + 2],
                      scales[2 * t:2 * t + 1])
            for t in range(n_kv)])

    @pl.when(jb <= last_ref[b])
    def _compute():
        n_kv = k_ref.shape[2]
        # one batched dot over the KV heads instead of unrolled small dots:
        # the unrolled form was VPU-bound (its softmax bookkeeping ran once
        # per head) and an int8 cache gave no speedup
        qb = q_ref[0].astype(jnp.float32)                       # [KV, R, hd]
        kb = k_ref[0, 0].astype(jnp.float32)                    # [KV, BK, hd]
        vb = v_ref[0, 0].astype(jnp.float32)

        s = jax.lax.dot_general(
            qb, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [KV, R, BK]
        if quantized:
            s = s * scale_rows(ks_ref)

        k_pos = jb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (n_kv, 1, block_k), 2
        )
        in_cache = k_pos < cache_len
        if not quantized:
            vb = _zero_past_cache(vb, jb * block_k, cache_len)
        limit = lim_ref[0, :, :, :1] if per_query else fill     # [KV, R, 1]
        mask = (k_pos >= pad) & (k_pos <= limit)
        # window in slot space per query, matching the dense path's
        # k_slot > (fill_b + s) - win
        mask = mask & ((win == 0) | (k_pos > limit - win))
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[:, :, :1]                         # [KV, R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)

        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :, :1] * corr + jnp.sum(p, axis=2, keepdims=True),
            l_ref.shape,
        )
        if quantized:
            p = p * jnp.where(in_cache, scale_rows(vs_ref), 0.0)
        pv = jax.lax.dot_general(
            p, vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [KV, R, hd]
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        if return_partials:
            o_ref, mo_ref, lo_ref = out_refs
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)
            mo_ref[0] = m_ref[...]
            lo_ref[0] = l_ref[...]
        else:
            l = jnp.maximum(l_ref[:, :, :1], 1e-30)
            out_refs[0][0] = (acc_ref[...] / l).astype(out_refs[0].dtype)


def _attend(
    qg: jax.Array,             # [B, KV, R, hd] — R = n_q * G merged rows
    limits: jax.Array | None,  # [B, KV, R, LANES] int32, or None: the fill
    cache: dict,
    layer_idx,
    pad_lens: jax.Array,       # [B] int32
    fills: jax.Array,          # [B] int32
    window,
    *,
    n_q: int,
    name: str,
    block_k: int | None,
    interpret: bool,
    return_partials: bool = False,
):
    """The ``pallas_call`` both wrappers make: grid (row, key block), every
    K/V block one row's KV heads over ``bk`` slots. Returns the kernel's
    outputs ``[B, KV, R, hd]`` (and m, l ``[B, KV, R, LANES]``).

    Where the cache holds two KV heads a lane tile (``heads_per_lane_tile``)
    the kernel is handed the tiles as its heads — ``KV/2`` of 128, the block
    the rule gives that shape — and a tile's two heads' merged rows as one
    tile's ``2R``, each query in its own head's lanes; the outputs' own
    lanes are taken on the way back. The merged rows are head-major, so
    both are plain reshapes around the lane placement."""
    k_all, v_all = cache["k"], cache["v"]
    quantized = "ks" in cache
    heads = B, KV, R, hd = qg.shape
    paired = cache_heads_per_tile(cache, hd) == 2
    # 1/sqrt of the head's own size, whatever the tile's
    scale = 1.0 / (hd ** 0.5)
    if paired:
        # from here on KV counts the tiles, R a tile's rows, hd its lanes
        KV, R, hd = KV // 2, 2 * R, 2 * hd
        qg = place_in_own_lanes(qg).reshape(B, KV, R, hd)
        if limits is not None:
            limits = limits.reshape(B, KV, R, _LANES)
    C = k_all.shape[3]
    bk = min(block_k, C) if block_k else decode_block_k(
        KV, hd, k_all.dtype.itemsize, C)

    pads, fills = pad_lens.astype(jnp.int32), fills.astype(jnp.int32)
    win = jnp.asarray(0 if window is None else window, jnp.int32).reshape(1)
    first, last = _row_blocks(pads, fills, n_q, win, bk, C)
    # the steps a row: the most blocks any row reads (a window layer's few,
    # whatever the cache's length)
    steps = jnp.maximum(jnp.max(last - first) + 1, 1)

    def scale_index(b, j, lidx, first, last, *_):
        return (lidx[0], b, 0, jnp.minimum(first[b] + j, last[b]))

    def kv_index(*args):
        return scale_index(*args) + (0,)

    row_block = lambda *shape: pl.BlockSpec(  # noqa: E731
        (1, *shape), lambda b, j, *_: (b,) + (0,) * len(shape)
    )
    in_specs = [row_block(KV, R, hd)]
    operands = [qg]
    if limits is not None:
        in_specs.append(row_block(KV, R, _LANES))
        operands.append(limits)
    in_specs += [pl.BlockSpec((1, 1, KV, bk, hd), kv_index)] * 2
    operands += [k_all, v_all]
    if quantized:
        in_specs += [pl.BlockSpec(
            (1, 1, cache["ks"].shape[2], bk), scale_index)] * 2
        operands += [cache["ks"], cache["vs"]]

    # the output, with ``return_partials`` in float32 and its m and l beside
    # it: the shapes of the scratch the kernel sums a row in
    state = [(KV, R, hd), (KV, R, _LANES), (KV, R, _LANES)]
    outs = state if return_partials else state[:1]
    out_dtype = jnp.float32 if return_partials else qg.dtype
    out = pl.pallas_call(
        functools.partial(
            _kernel, block_k=bk, cache_len=C, scale=scale,
            quantized=quantized, per_query=limits is not None,
            return_partials=return_partials, paired=paired,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B, steps),
            in_specs=in_specs,
            out_specs=[row_block(*shape) for shape in outs],
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in state],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, *shape), out_dtype)
                   for shape in outs],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name=name,
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1), first, last, fills, win,
        pads, *operands,
    )
    if not paired:
        return out
    o, *sums = (x.reshape(*heads[:3], x.shape[-1]) for x in out)
    return [take_own_lanes(o), *sums]


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
    block_k: int | None = None,
    interpret: bool = False,
    return_partials: bool = False,
) -> jax.Array:
    """Semantics match _attention(q, dequantized cache[layer],
    mask=pad<=j<=fill); returns [B, 1, H, hd]. ``window`` > 0 restricts to
    the last ``window`` slots (Gemma sliding layers): below-window blocks
    are compute-skipped and DMA-elided like a row's pad and past-fill
    blocks, so a sliding layer's step reads only ~window worth of cache
    however long the fill. ``block_k`` is for tests: the block follows the
    shapes (``decode_block_k``).

    ``return_partials=True`` returns the unnormalized online-softmax state
    ``(o [B, H, hd] f32, m [B, H] f32, l [B, H] f32)`` instead — the
    shard-local partial the long-context decode LSE-merges across the seq
    axis (same contract as backend.long_context._prefill_partial_local)."""
    B, S, H, hd = q.shape
    KV = cache["k"].shape[2] * cache_heads_per_tile(cache, hd)
    if S != 1:
        raise ValueError(f"decode kernel is single-token (S=1), got S={S}")
    if not (head_dim_supported(hd) or interpret):
        raise ValueError(f"unsupported decode head_dim={hd}")
    out = _attend(
        q.reshape(B, KV, q_per_kv, hd), None, cache, layer_idx, pad_lens,
        jnp.broadcast_to(jnp.asarray(fill, jnp.int32), (B,)), window,
        n_q=1, block_k=block_k, interpret=interpret,
        return_partials=return_partials,
        # a contract: the device trace, the ledger and benchmark metrics name
        # this kernel by it, whatever the wrapper is called
        name="flash_decode_attention",
    )
    if return_partials:
        o, m, l = out
        return (
            o.reshape(B, H, hd),
            m[..., 0].reshape(B, H),
            l[..., 0].reshape(B, H),
        )
    return out[0].reshape(B, 1, H, hd)


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
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Multi-position decode attention for the speculative verify step and
    the slot segment (Sq = 1): query (b, s) sits at cache slot fills_b + s
    and attends pad_b <= j <= fills_b + s
    (models.llama.verify_attention_mask semantics). Returns [B, Sq, H, hd].

    This is the decode kernel generalized along two axes at once: several
    query positions per row (the Sq*G rows of one grid cell share each K/V
    block, so a verify step streams the cache ONCE for all k+1 positions —
    the whole point of batched verification) and PER-ROW fill offsets
    (after ragged draft acceptance, rows sit at different cache lengths).
    Each row reads from its own pad to its own last query; masking uses the
    exact per-(row, query) limit."""
    B, Sq, H, hd = q.shape
    KV = cache["k"].shape[2] * cache_heads_per_tile(cache, hd)
    if not (head_dim_supported(hd) or interpret):
        raise ValueError(f"unsupported verify head_dim={hd}")
    G = q_per_kv
    if H != KV * G:
        raise ValueError(f"q_per_kv={q_per_kv} inconsistent with H/KV={H // KV}")

    # merged layout [B, KV, Sq*G, hd] with query position MAJOR over the
    # group heads (row s*G + g) so one limits row covers a position's whole
    # GQA group
    qg = (
        q.transpose(0, 2, 1, 3)               # [B, H, Sq, hd]
        .reshape(B, KV, G, Sq, hd)
        .transpose(0, 1, 3, 2, 4)             # [B, KV, Sq, G, hd]
        .reshape(B, KV, Sq * G, hd)
    )
    # per-(row, query) last visible slot, lane-broadcast (the kernel cannot
    # assemble the merged-row vector from SMEM scalars)
    limits = fills.astype(jnp.int32)[:, None] + jnp.arange(Sq, dtype=jnp.int32)
    limits = jnp.broadcast_to(
        limits[:, None, :, None, None], (B, KV, Sq, G, _LANES)
    ).reshape(B, KV, Sq * G, _LANES)
    (out,) = _attend(
        qg, limits, cache, layer_idx, pad_lens, fills, window, n_q=Sq,
        block_k=block_k, interpret=interpret,
        # a contract, as the decode kernel's
        name="flash_spec_verify_attention",
    )
    return (
        out.reshape(B, KV, Sq, G, hd)
        .transpose(0, 2, 1, 3, 4)             # [B, Sq, KV, G, hd]
        .reshape(B, Sq, H, hd)
    )
