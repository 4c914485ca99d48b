"""Pallas TPU flash attention for the prefill path.

The XLA attention in models.llama materializes the full [B, KV, G, S, C]
f32 score tensor — at S=8k, C=9k that alone is >30 GB, capping chunk sizes
far below the reference's 12k-token chunks (SURVEY.md §5). This kernel
computes attention blockwise with online-softmax scratch accumulators, so
VMEM holds only (BQ × BK) score tiles and HBM never sees a score tensor:

- grid (B, KV, ⌈S/BQ⌉, ⌈C/BK⌉), K-block innermost; the whole GQA GROUP
  (G = H/KV query heads) rides one grid cell — each K/V block is DMA'd
  ONCE per group instead of once per query head (the original (B, H, …)
  grid streamed every block G times; for Llama's 24:8 that was 3x the
  mandatory attention bytes). The causal/pad/window mask is also computed
  once per cell and shared by the G heads;
- scratch (acc, m, l) carries the running softmax across K blocks per
  head (a head's state is BQ rows of one buffer — leading dims may MERGE
  in-kernel but never split, so row slices beat a reshape); output
  written on the last K block;
- **the group's heads go one tile at a time, written one product ahead**
  (_kernel's for_each_head): K and V blocks, their casts, the value scales
  and the mask are built once a cell, then each head computes its [BQ, BK]
  tile of scores against them (_scores) and runs its softmax and ``P.V``
  over it (_attend). Up to four heads are a static unroll; a wider group is
  a loop of two heads a step (an odd head out after it), so the score
  temporaries alive, the code size and the compile time do not grow with
  the group;
- **the order a cell is written in** (PERF.md section 6, PR 45; the latent
  kernel's finding of PR 44): Mosaic runs a cell much as it is written and
  overlaps work with what stands next to it, no further. The unroll is
  written ``Aq Bq A.. Cq B.. Dq C.. D..`` (q = a head's score product, ..
  = its running max, exponentials, row sum, ``P.V`` and three state
  stores): every softmax but the last stands beside the next head's
  product. Kernel alone on the v5e at Qwen3's group of three map
  dispatches (8 rows x 8 KV heads x 4, 36 layers; every output equal to
  the head-by-head kernel's bit for bit): head by head 4.69 s, pairs
  ``Aq Bq A.. B.. Cq Dq C.. D..`` 4.34, **one ahead 4.10 (-12.5%)**, two
  ahead 4.68, all four products first 4.57. The loop does not take it: two
  heads a step written products first cost 4.73 ns per 1,024 computed
  scores for 4.60 at G=7, 4.77 for 4.60 at G=6, 5.48 for 5.46 at G=9 (and
  5.36 for 5.13-5.19 at G=4), three a step the same, so a looped step stays
  head by head (_heads_ahead) and its kernel is the one it was; a static
  unroll of 6, 7 or 9 heads, or an odd head joined to the last pair, on
  the (1024, 1024) tile costs 12-24 ns — 2.6-4.3 x;
- **ceil-division grids with masked tails**: block sizes stay MXU-friendly
  for ANY S/C. An earlier divisor-only picker collapsed to 32-wide
  K blocks at C=2080 (8 KB DMAs) and the kernel ran 60% of total profile
  time — tail masking costs one wasted partial block instead;
- **what binds it, measured on the v5e** (kernel alone, int8 cache, the
  benchmark's shapes: four 2048-query chunks of an S=8192 dispatch over
  C=8448, bq 512 / bk 1024; PERF.md, PRs 27 and 45): written head by head
  a computed cell cost 10.5-11.0 us (Qwen3 KV=8/G=4, 8 rows: 50.7 ms a
  layer over 4,608 cells; Phi-4 KV=10/G=4, 12 rows: 94.8 ms over 8,640),
  **one product ahead it costs 9.2 us** (Phi-4 9.23, the served join's
  four rows 10.0 for 11.3, Granite's 64-wide heads 9.1 for 10.5), against
  5.4 us for its two matrix products at the bf16 peak and 0.3 us for its
  262 KB of int8 K and V at the HBM peak. So neither the DMA nor the MXU
  sets the pace (an earlier machine's reading, "DMA-granularity-bound",
  does not hold here). Taking both selects and the mask build out of EVERY
  cell bought 9% (46.1 ms), folding ``scale`` into the ``ks`` row bought
  nothing: it is not the count of vector operations per score either.
  What the order shows: the [bq, bk] f32 score tile's vector work (the row
  max, the exp, the row sum, the cast, 2 MB a head through VMEM) runs
  after the products unless a product is written next to it, and then
  1.3 of its ~5 us hide; the last head's softmax has nothing beside it,
  and more products ahead lose again (their tiles wait in VMEM). The lever
  that pays most is still not running a cell: with a group's four tail
  chunks in the dispatch, 50.2 -> 35.8 ms;
- **block geometry** (_block_geometry, from G and hd alone). The cost a
  score follows the tile ONE head computes at a time, above all its key
  width, not the group. Swept on the v5e, kernel alone at the SmallThinker
  cell's map dispatch (24 rows of 4 KV heads x 7, hd 128, int8 cache, four
  2048-query chunks over C=8448, 4 global and 12 window-4096 layers;
  seconds for the dispatch's cells / ns per 1,024 computed scores / MiB
  Mosaic needs; PERF.md section 6, PR 36): the parent's unroll of 7 at
  (512, 512) 2.42 s / 8.5 / 12; looped, one and two heads a step:
  (512, 512) 2.68 and 2.52 / 9.4 and 8.9; (512, 1024) 1.67 and 1.55 /
  5.3 and 5.0 / 15 and 16; (512, 2048) 1.77 and 1.71 / 4.8 and 4.6 / 21
  and 24 (19% more scores computed: the window's floor and the diagonal
  cost a whole tile each); **(1024, 1024) 1.47 and 1.44 / 4.7 and 4.6 / 28
  and 31 — adopted for every group wider than four**, at 512 query rows
  where the q, o and state rows of 1024 pass the budget (G > 16). At G=4
  (Qwen3's dispatch, (512, 1024)) the loop loses to the unroll — 1.400 s
  a head a step, 1.301 two, 1.28-1.32 unrolled — so groups of up to four
  keep the unroll and the geometry they had: bq=512 / bk=2048 at G<=3 (an
  earlier machine's choice), bk 1024 at G=4 and at hd=256 (Gemma3);
  under the order above (1024, 1024) ties at G=4 (4.1035 s for 4.1051 at
  Qwen3's group, -0.9% at Phi-4's, -0.7% at the join's, -0.4% at
  Granite's, the control's noise 0.4-1.2%; PR 45), so the tile stays;
  at G=16 on 2 KV heads (Nemotron-H's 32/2 heads of 128, the widest group
  a cell runs and the last the rule holds at 1024 query rows; kernel alone
  at its map dispatch — 12 rows, ten full and two tail rows, int8 cache, 2
  layers; seconds / ns per 1,024 computed scores; PR 47): (512, 512) 0.2009
  / 8.6; (512, 1024) 0.1162 / 4.7; **(1024, 1024) 0.1100 / 4.4, the
  rule's**; (512, 2048) 0.1209 / 4.3 (13% more scores computed);
  (1024, 512) 0.2070 / 8.4 — the key width sets the cost at G=16 as at
  G=7, and the rule stands;
  at G=1 (Ouro-2.6B's 16 query heads on 16 KV heads of 128: every K/V
  block's int8-to-float upcast serves ONE head's query rows; kernel alone
  at its group's three map dispatches — 8 rows each, the first with four
  tail rows, int8 cache, 48 cache layers = 4 passes x 12 layers; seconds
  the group / the all-live dispatch / ns per 1,024 computed scores; PR 50):
  (512, 512) 6.736 / 2.455 / 11.8; (512, 1024) 4.324 / 1.562 / 7.1;
  (512, 2048), the rule's until then, 4.422 / 1.589 / 6.4; (1024, 2048)
  4.119 / 1.480 / 6.0; (2048, 1024) 3.741 / 1.358 / 5.5; **(1024, 1024)
  3.545 / 1.282 / 5.8 — adopted at G=1** (-20%: twice the query rows an
  upcast, and 11% fewer scores computed than under a 2,048-wide key block);
  G = 2, 3 and hd=256 have no cell (ROADMAP Queue 1 item 1c). _vmem_bytes
  counts what a
  geometry needs; past the 32 MiB the attention kernels share, this
  kernel alone asks for its count, and a group that fits nothing under 64
  MiB (G > 42 at hd 128) is a ValueError with the numbers;
- **consumes the FULL stacked cache [L, B, KV, C, hd]** like the decode twin
  (ops/decode_attention.py): the layer index arrives via scalar prefetch and
  steers the index_map, eliminating the per-layer 2×(B·C·hd·KV) extraction
  copies XLA otherwise materializes inside the layer scan;
- **64-wide KV heads arrive two a lane tile** (PR 55): where the cache
  holds heads 2p and 2p+1 side by side (``heads_per_lane_tile``,
  ``models.llama.init_kv_cache``: ``[L, B, KV/2, C, 128]``, the scales a
  head) the grid still walks the KV heads, a cell is handed its pair's
  full-width K/V block (``kv_index``) and takes its own head's half of the
  lanes (``_own_half``) before the cast; queries, outputs, the state and
  the rest of the body are a head wide, as they were. What sets this
  kernel's pace is the score tile's vector work, not its loads (below), so
  nothing is gained here: kernel alone at Granite's and LFM2's map dispatch
  (24 rows, 8 KV x 4, hd 64, int8, (512, 1024)) 9.35 us a cell for the
  parent's 9.10 (0.694 s for 0.677 over 6 layers; the lane shift as a
  32-bit roll of the cast block 0.687, the queries zero-padded to 128
  lanes in XLA instead 0.765) — the decode kernel is where the pair pays
  (ops/decode_attention.py: 0.6875 -> 0.3264 ms a call);
- causal + left-pad masking fused (same semantics as
  models.llama.prefill_attention_mask: pad_b <= j <= i);
- **each cell does its class's work** (_block_class, from the scalars the
  cell already holds; splash attention's three-way split): *dead* cells —
  above the causal diagonal, below the window floor, under the row's left
  pad, or any cell of a query block that is all pad (a batch-bucketing
  filler row is dead everywhere) — are neither fetched nor computed;
  *interior* cells, whose mask would be all true, build no mask and run
  no select — under a window too, where the cell's first slot is inside
  the last query row's window (18 of a full row's 30 computed cells on a
  4096-window layer at (1024, 1024)); *edge* cells run the masked body.
  The output is the same
  bit for bit as masking every cell. ``prefill_block_classes`` counts the
  cells of a call on the host with the same rule.

Inference-only (no VJP); training uses dense or ring attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # python float: jnp constants would be captured by the kernel
_LANES = 128
# Scoped VMEM the attention kernels ask Mosaic for. The compiler's default is
# 16 MiB, which the int8-cache variants of the block geometries below fit
# and their bf16-cache twins do not: on libtpu 0.0.34 the prefill kernel at
# G=3/bk=2048 needs 18.93 MiB and the verify kernel at KV=10/G=4/Sq=5 needs
# 16.66 MiB (compile errors, chip_smoke.py kernels phase). 32 MiB of a v5e
# core's 128 MiB covers both with room; block geometry is unchanged.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _block_class(q_start, k_start, pad, win, q_end, cache_len,
                 block_q: int, block_k: int):
    """What the (block_q x block_k) cell at query slot ``q_start`` / cache
    slot ``k_start`` holds for a row with ``pad`` left-pad slots, as three
    flags (seen, padded, interior):

    - not ``seen``: wholly above the causal diagonal, or wholly below the
      first query row's window floor — dead before the pad is looked at;
    - ``padded``: every K slot is under the pad, or every query the block
      really holds is (queries end at ``q_end`` = q_offset + S; a filler
      row, pad == S, is padded everywhere) — dead;
    - ``interior``: the per-element mask would be all true — wholly at or
      under every query's diagonal, past the pad, every query real
      (< ``q_end``), every slot in the cache, and with a window the first
      slot inside the LAST query row's window (then every slot is inside
      every row's);
    - the rest is edge.

    Only comparisons and bit operators: the kernel calls it on SMEM
    scalars, prefill_block_class_grid on numpy arrays — one rule, so the
    host's count is the kernel's behaviour."""
    q_last = q_start + (block_q - 1)
    k_last = k_start + (block_k - 1)
    seen = (k_start <= q_last) & ((win == 0) | (k_last >= q_start - win + 1))
    padded = (k_last < pad) | (q_last < pad) | (q_end <= pad)
    interior = (
        (k_last <= q_start) & (k_start >= pad) & (q_last < q_end)
        & (k_last < cache_len) & ((win == 0) | (k_start > q_last - win))
    )
    return seen, padded, interior


def _own_half(block, upper, hd: int):
    """A head's own half of its pair's K/V block [BK, 2 hd], in the cache's
    type: the upper lanes for an odd head (``upper``, a traced scalar: the
    grid walks the heads). A lane shift a block, beside a head's [BQ, BK]
    score tile next to nothing; the DMA moved full tiles."""
    return jnp.where(upper, block[:, hd:], block[:, :hd])


def _kernel(
    lidx_ref,  # [1] int32 (scalar prefetch, SMEM) — layer to read
    pad_ref,   # [B] int32 (scalar prefetch, SMEM)
    win_ref,   # [1] int32 (scalar prefetch, SMEM) — sliding window; 0 = global
    off_ref,   # [1] int32 (scalar prefetch, SMEM) — cache slot of query 0
    *refs,
    block_q: int,
    block_k: int,
    seq_len: int,
    cache_len: int,
    scale: float,
    quantized: bool,
    q_per_kv: int,
    heads_per_step: int,
    heads_ahead: int,
    paired: bool = False,
):
    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    # q_ref/o_ref [1, 1, G, BQ, hd]; k_ref/v_ref [1, 1, 1, BK, hd] (``paired``:
    # [1, 1, 1, BK, 2 hd], this head's tile, which it shares with its pair);
    # ks_ref/vs_ref [1, 1, KV, BK] (full KV axis — Mosaic requires the
    # second-minor block dim be 8-divisible or whole; the group's row is
    # selected in-kernel); scratch acc [G*BQ, hd] f32, m/l [G*BQ, LANES]
    # f32 — a head's state is a slice of BQ rows of one buffer

    b = pl.program_id(0)
    kv = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    # chunked prefill: queries live at cache slots off..off+S-1 (chunk c of
    # a longer prompt); off = 0 is the classic whole-prompt prefill
    q_start = off_ref[0] + i * block_q
    k_start = j * block_k
    win = win_ref[0]
    pad = pad_ref[b]
    seen, padded, interior = _block_class(
        q_start, k_start, pad, win, off_ref[0] + seq_len, cache_len,
        block_q, block_k,
    )

    def for_each_head(back, front=lambda g: None):
        """``back(g, rows, front(g))`` for the group's heads, ``rows`` the
        head's rows of the scratch. ``heads_per_step`` heads are unrolled
        into one step of a loop: the [BQ, BK] f32 score temporaries alive at
        a time, the code size and the compile time are those of one step's
        heads whatever the group (ops/mla_attention.py has the same loop). A
        group of no more heads than that is the plain static unroll. The
        order inside a step is the point: a head's ``front`` (its score
        product) is written ``heads_ahead`` heads before its ``back`` (its
        softmax and ``P.V``), because Mosaic runs a step much as it is
        written and overlaps work with what stands next to it — that softmax
        then runs beside the next head's product, not after it (module
        docstring, "the order a cell is written in")."""
        def written(heads, aligned=lambda row: row):
            # a head's index and rows are computed as it is reached (the
            # loop hands a generator), so a looped step traces to the
            # equations it always had (tests/test_ops_flash_order.py)
            held = []
            for g in heads:
                rows = pl.ds(aligned(g * block_q), block_q)
                held.append((g, rows, front(g)))
                if len(held) > heads_ahead:
                    back(*held.pop(0))
            for h in held:
                back(*h)

        def several(step, _):
            written(
                (step * heads_per_step + u for u in range(heads_per_step)),
                lambda row: pl.multiple_of(row, block_q),
            )

        steps = q_per_kv // heads_per_step
        looped = steps * heads_per_step if steps > 1 else 0
        if looped:
            jax.lax.fori_loop(0, steps, several, None)
        written(range(looped, q_per_kv))  # the unroll, or an odd head out

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _accumulate(masked: bool):
        # what does not hang on the head is built once a cell, outside the
        # loop over the group: one [BK, hd] conversion of each block, not G
        # (int8 cache values are exact in the query dtype — see the dot
        # comment below), the value scales, the mask
        def block(ref):
            x = ref[0, 0, 0]
            if paired:
                x = _own_half(x, kv % 2 == 1, q_ref.shape[-1])
            return x.astype(q_ref.dtype)

        kb, vb = block(k_ref), block(v_ref)
        v_scale = vs_ref[0, 0, kv][None, :] if quantized else None
        mask = None
        if masked:
            # A partial tail block (cache_len % block_k != 0) is only DMA'd
            # up to the end of the cache; the rest of its VMEM buffer holds
            # whatever was there, NaN bit patterns included. Scores of those
            # slots are masked below, but their probability 0 still meets
            # the value side in a multiply (0 * NaN = NaN — seen on the chip
            # as NaN rows, int8 cache at C=3200/bk=2048), so the value side
            # is zeroed past the end: the f32 scale row when quantized (int8
            # garbage is finite), else the value rows themselves.
            if quantized:
                in_cache = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_k), 1
                ) < cache_len
                v_scale = jnp.where(in_cache, v_scale, 0.0)
            else:
                in_cache = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, 1), 0
                ) < cache_len
                vb = jnp.where(in_cache, vb, jnp.zeros_like(vb))

            # mask depends on positions only, not the head — ONE copy serves
            # the whole GQA group
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            # k_pos <= q_pos also kills the masked tail of a partial K block
            # (those slots have k_pos > any valid q_pos); q_pos of a partial
            # Q-block tail produces garbage rows the caller never reads.
            # Window semantics in SLOT space match the dense path
            # (models.llama._block: k_slot > q_slot - window) — left pad
            # shifts q and k slots identically, so the token-space window is
            # preserved
            mask = (
                (k_pos <= q_pos) & (k_pos >= pad)
                & (q_pos < off_ref[0] + seq_len)
            )
            mask = mask & ((win == 0) | (k_pos > q_pos - win))

        def _scores(g):
            # MXU inputs stay in the QUERY dtype with f32 accumulation
            # (preferred_element_type): f32 parity tests keep exact f32
            # dots, the engine's bf16 takes the native-rate MXU path. int8
            # cache values (-128..127) are exactly representable in bf16,
            # so the dequant algebra is unchanged.
            s = jax.lax.dot_general(
                q_ref[0, 0, g], kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [BQ, BK] f32
            if quantized:
                s = s * ks_ref[0, 0, kv][None, :]
            if masked:
                s = jnp.where(mask, s, _NEG)
            return s

        def _attend(g, rows, s):
            m_prev = m_ref[rows, :1]                    # [BQ, 1]
            m_cur = jnp.max(s, axis=1, keepdims=True)   # [BQ, 1]
            m_new = jnp.maximum(m_prev, m_cur)
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(mask, p, 0.0)             # dead rows stay dead

            l_new = l_ref[rows, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = p * v_scale
            # probabilities drop to the query dtype for the PV dot (bf16
            # adds ~0.4% relative rounding — same class as the int8 V
            # scale already applied above); accumulation stays f32
            acc_ref[rows] = acc_ref[rows] * corr + jax.lax.dot_general(
                p.astype(q_ref.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[rows] = jnp.broadcast_to(m_new, (block_q, m_ref.shape[1]))
            l_ref[rows] = jnp.broadcast_to(l_new, (block_q, l_ref.shape[1]))

        for_each_head(_attend, _scores)

    # Each cell does its class's work and no more. Dead cells (nothing to
    # see: above the diagonal, below the window floor, under the row's left
    # pad) run neither body and were never DMA'd — the index_map clamps
    # them onto a live block, see visible_j. An interior cell's mask would
    # be all true, so it builds none; an edge cell runs the masked body.
    @pl.when(interior)
    def _interior():
        _accumulate(masked=False)

    @pl.when(seen & jnp.logical_not(padded) & jnp.logical_not(interior))
    def _edge():
        _accumulate(masked=True)

    @pl.when(j == nj - 1)
    def _finalize():
        def _store(g, rows, _):
            l = jnp.maximum(l_ref[rows, :1], 1e-30)
            o_ref[0, 0, g] = (acc_ref[rows] / l).astype(o_ref.dtype)

        for_each_head(_store)


# A group of more than _UNROLLED_GROUP heads goes through the kernel's head
# loop, _HEADS_LOOPED of them a loop step, on a (1024, 1024) tile; a narrower
# one is a static unroll of the whole group on the tile it had (module
# docstring, "block geometry").
_UNROLLED_GROUP = 4
_HEADS_LOOPED = 2
_LOOPED_BLOCK = 1024
# the most scoped VMEM this kernel asks for, of the core's 128 MiB (what the
# expert product asks for and runs with)
_VMEM_MOST = 64 * 1024 * 1024


def _kernel_of_rows(lidx_ref, pad_ref, win_ref, off_ref, rows_ref, *refs,
                    **geometry):
    """``_kernel`` under a fifth prefetched vector (``cache_rows``), which
    only the index_map reads."""
    del rows_ref
    _kernel(lidx_ref, pad_ref, win_ref, off_ref, *refs, **geometry)


def _heads_per_step(G: int) -> int:
    return G if G <= _UNROLLED_GROUP else _HEADS_LOOPED


def _heads_ahead(G: int) -> int:
    """How many heads a head's score product is written ahead of its softmax
    (for_each_head): one in the static unroll, none in the loop, where it
    loses (module docstring, "the order a cell is written in")."""
    return 1 if G <= _UNROLLED_GROUP else 0


def _vmem_bytes(G: int, hd: int, bq: int, bk: int) -> int:
    """Scoped VMEM a grid step needs at the engine's types (bf16 queries; a
    bf16 cache, which needs 2 MiB more than an int8 one): the q and o tiles
    double-buffered and the acc, m, l scratch, all G * bq rows; the k and v
    blocks double-buffered; and what a cell keeps per score — Mosaic's own
    count, bisected on the compiler at G 2-64, is 11.5-14 bytes with one
    head's [bq, bk] temporaries alive and 2-4 more for a second head's:
    a looped step holds two heads, and the unroll, written one product
    ahead, two score tiles (14 MiB for 13 at G=4, 22 for 20 at G=3). At
    most 30% above what Mosaic needs, never under (PERF.md section 6, PRs
    36 and 45)."""
    rows = G * bq * (2 * 2 * 2 * hd + 4 * (hd + 2 * _LANES))
    blocks = 2 * 2 * bk * hd * 2
    heads = min(_heads_per_step(G), 2)
    return rows + blocks + bq * bk * (12 + 4 * heads)


def _block_geometry(S: int, C: int, G: int, hd: int,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False) -> tuple[int, int]:
    """(bq, bk) of the kernel's grid for S queries over a cache of C slots
    with GQA group G and head size hd — the wrapper's rule, also the
    counter's (prefill_block_classes)."""
    default_bq = 512
    # the key width shrinks with the head size (hd=256 Gemma3: half)
    default_bk = max(512, 2048 * _LANES // max(hd, 1))
    if 1 < G <= _UNROLLED_GROUP:
        # G * bk held to 3 * 2048 since the default limit of 16 MiB (Qwen3
        # and Phi-4's 4:1 groups: bk 1024); (1024, 1024) ties under the
        # unroll's order (module docstring, "block geometry")
        while G * default_bk > 3 * 2048 and default_bk > 512:
            default_bk //= 2
    else:
        # the wide groups' tile, and a lone head's (G = 1: a K/V block's
        # upcast serves one head, so twice the query rows halve the upcasts
        # a score: -20% at Ouro's map dispatches, PR 50)
        default_bk = max(512, _LOOPED_BLOCK * _LANES // max(hd, 1))
        # 1024 query rows while the group's q, o and state rows fit (G <= 16
        # at hd 128), else 512 (1.55 s for 1.44 at G=7)
        if _vmem_bytes(G, hd, _LOOPED_BLOCK, default_bk) <= _VMEM_MOST:
            default_bq = _LOOPED_BLOCK
    bq = min(block_q or default_bq, S)
    # scratch is G-sliced at multiples of bq — keep the slice offsets
    # sublane-aligned when S is small and not 8-divisible
    bq = -(-bq // 8) * 8
    bk = min(block_k or default_bk, C)
    need = _vmem_bytes(G, hd, bq, bk)
    if need > _VMEM_MOST and not interpret:
        # the q and o tiles and the softmax state grow with the group: fail
        # with the numbers, not with a Mosaic error that names none of them
        raise ValueError(
            f"flash prefill geometry exceeds the scoped-VMEM budget: G={G}, "
            f"head_dim={hd}, bq={bq}, bk={bk} need {need} bytes of "
            f"{_VMEM_MOST} — pass a smaller block_q/block_k or drop to the "
            f"dense path"
        )
    return bq, bk


def head_dim_supported(head_dim: int) -> bool:
    """Head sizes the attention kernels take on the chip: whole lane tiles,
    or half of one (64) — two heads a tile where the cache pairs them
    (``heads_per_lane_tile``), else as blocks of the array's own width: the
    DMA moves 64-wide rows and the products contract 64, on tiles whose
    other 64 lanes hold nothing."""
    return head_dim % _LANES == 0 or head_dim == _LANES // 2


def heads_per_lane_tile(n_kv: int, head_dim: int, model_shards: int = 1) -> int:
    """KV heads the cache stores in one 128-lane tile (models.llama.
    init_kv_cache): two where a head is half a tile wide and the KV heads
    pair off — under a mesh, where the pairs still divide over the tensor
    axis' ``model_shards`` — else one. Heads 2p and 2p+1 then share tile p,
    lanes 0-63 and 64-127; the scales stay a head. What reads the cache
    takes the number off its operands (``cache_heads_per_tile``)."""
    pairs = head_dim * 2 == _LANES and n_kv % 2 == 0
    return 2 if pairs and (n_kv // 2) % max(model_shards, 1) == 0 else 1


def cache_heads_per_tile(cache: dict, head_dim: int) -> int:
    """KV heads a lane tile of the stacked ``cache`` holds, for queries of
    ``head_dim``: the cache's last dim over the head's."""
    return cache["k"].shape[-1] // head_dim


def supports_flash(seq_len: int, cache_len: int, head_dim: int) -> bool:
    """Ceil-div grids handle any S/C; only the head dim (whole lane tiles,
    or half of one) is load-bearing on real hardware."""
    return head_dim_supported(head_dim)


@functools.partial(
    jax.jit,
    static_argnames=("q_per_kv", "block_q", "block_k", "interpret"),
)
def flash_prefill_attention(
    q: jax.Array,          # [B, S, H, hd]
    cache: dict,           # stacked {"k","v"[, "ks","vs"]} (llama.init_kv_cache)
    layer_idx: jax.Array,  # scalar int32
    pad_lens: jax.Array,   # [B] int32 — left-pad per sequence
    q_per_kv: int,
    window: jax.Array | None = None,  # scalar int32; 0/None = global
    q_offset: jax.Array | None = None,  # scalar int32; cache slot of query 0
    cache_rows: jax.Array | None = None,  # [B] int32; q's rows of the cache
    *,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns [B, S, H, hd]; semantics match _attention with the prefill
    mask (pad_b <= j <= i over cache slots) on the (dequantized) cache layer
    ``layer_idx``. ``window`` > 0 additionally restricts each query to the
    last ``window`` slots (Gemma sliding layers — the per-layer value is a
    runtime scalar, so one compiled program serves global and local layers).
    ``q_offset`` places the S queries at cache slots
    [q_offset, q_offset + S) — chunk c of a CHUNKED prefill (the engine's
    prefill_chunk_tokens path, which halves/quarters prefill transients so
    bigger decode batches fit); 0/None is the classic whole-prompt prefill.
    ``cache_rows`` names, for each of q's B rows, its batch row of a cache
    that holds more rows than q (a row piece of the engine's prefill): the
    index_map reads that row in place, and no slice of the cache is made;
    None is row b for row b.

    K/V blocks a query block has nothing to see in — strictly above the
    causal diagonal, wholly below the window floor, wholly under the row's
    left pad, or any block at all when the query block itself is all pad —
    are both compute-skipped AND DMA-elided: the index_map clamps their
    block index onto the nearest live block, and Pallas skips the copy when
    consecutive grid steps address the same block. Pad query rows still
    come back as zeros (see _block_class for the cell classes)."""
    k_all, v_all = cache["k"], cache["v"]
    quantized = "ks" in cache
    B, S, H, hd = q.shape
    C = k_all.shape[3]
    if not (head_dim_supported(hd) or interpret):
        raise ValueError(f"unsupported flash head_dim={hd}")
    # KV heads a lane tile of the cache (heads_per_lane_tile): where there
    # are two, the grid still walks the KV heads, a cell is handed its
    # pair's K/V tile (kv_index) and takes its head's half of the lanes
    # (_kernel, ``paired``); queries, outputs and the state stay a head wide
    tile = cache_heads_per_tile(cache, hd)
    KV = k_all.shape[2] * tile
    G = H // KV
    if q_per_kv != G:
        # the group-major grid derives G from the shapes; a mismatched
        # caller value would silently change the head->KV mapping
        raise ValueError(f"q_per_kv={q_per_kv} inconsistent with H/KV={G}")
    bq, bk = _block_geometry(S, C, G, hd, block_q, block_k, interpret)

    # group-major query layout: [B, KV, G, S, hd] — the grid walks KV
    # heads, so one grid cell computes the whole GQA group against each
    # K/V block (DMA'd once, not G times)
    qt = q.transpose(0, 2, 1, 3).reshape(B, KV, G, S, hd)

    def visible_j(b, i, j, pad, win, off):
        q_start = off[0] + i * bq
        # causal: last block any row sees (rows start at off + i*bq)
        j_hi = (q_start + bq - 1) // bk
        # first block with anything to see: the one the row's pad ends in,
        # or with a window the FIRST query row's floor, whichever is later
        lo = jnp.maximum(
            pad[b] // bk,
            jnp.where(
                win[0] > 0, jnp.maximum(q_start - win[0] + 1, 0) // bk, 0
            ),
        )
        # the upper clamp goes last: a Q block wholly in the pad has
        # lo > j_hi and parks every step on j_hi (always in range)
        return jnp.minimum(jnp.maximum(j, lo), j_hi)

    # a fifth prefetched vector, where the cache's rows are named: it
    # steers the index_map alone, and the kernel's body never sees it
    prefetch = 4 if cache_rows is None else 5

    def kv_index(b, kv, i, j, lidx, pad, win, off, *rows):
        row = rows[0][b] if rows else b
        # no division traced at one head a tile: the program it was
        tile_of = kv // tile if tile > 1 else kv
        return (lidx[0], row, tile_of, visible_j(b, i, j, pad, win, off), 0)

    def scale_index(b, kv, i, j, lidx, pad, win, off, *rows):
        row = rows[0][b] if rows else b
        return (lidx[0], row, 0, visible_j(b, i, j, pad, win, off))

    in_specs = [
        pl.BlockSpec(
            (1, 1, G, bq, hd),
            lambda b, kv, i, j, *prefetched: (b, kv, 0, i, 0),
        ),
        pl.BlockSpec((1, 1, 1, bk, hd * tile), kv_index),
        pl.BlockSpec((1, 1, 1, bk, hd * tile), kv_index),
    ]
    operands = [qt, k_all, v_all]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, KV, bk), scale_index),
            pl.BlockSpec((1, 1, KV, bk), scale_index),
        ]
        operands += [cache["ks"], cache["vs"]]

    grid = (B, KV, pl.cdiv(S, bq), pl.cdiv(C, bk))
    kernel = functools.partial(
        _kernel if cache_rows is None else _kernel_of_rows,
        block_q=bq, block_k=bk, seq_len=S, cache_len=C,
        scale=1.0 / (hd ** 0.5), quantized=quantized, q_per_kv=G,
        heads_per_step=_heads_per_step(G), heads_ahead=_heads_ahead(G),
        paired=tile == 2,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prefetch,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, G, bq, hd),
                lambda b, kv, i, j, *prefetched: (b, kv, 0, i, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((G * bq, hd), jnp.float32),
                pltpu.VMEM((G * bq, _LANES), jnp.float32),
                pltpu.VMEM((G * bq, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, S, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the attention kernels' common limit where the geometry fits
            # it, else this kernel's own count
            vmem_limit_bytes=max(VMEM_LIMIT_BYTES, _vmem_bytes(G, hd, bq, bk))
        ),
        interpret=interpret,
        # a contract: the device trace, the ledger and benchmark metrics name
        # this kernel by it, whatever the wrapper is called
        name="flash_prefill_attention",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        pad_lens.astype(jnp.int32),
        jnp.asarray(0 if window is None else window, jnp.int32).reshape(1),
        jnp.asarray(0 if q_offset is None else q_offset, jnp.int32).reshape(1),
        *(() if cache_rows is None else (cache_rows.astype(jnp.int32),)),
        *operands,
    )
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


BLOCK_CLASSES = ("dead_causal", "dead_pad", "interior", "edge")


def prefill_block_class_grid(pad_lens, S: int, C: int, q_offset: int = 0,
                             window: int = 0, G: int = 1, hd: int = _LANES,
                             *, block_q: int | None = None,
                             block_k: int | None = None):
    """Per-cell class of one flash_prefill_attention call, as an int8 numpy
    array [B, ceil(S/bq), ceil(C/bk)] of indices into BLOCK_CLASSES, with
    the geometry (bq, bk). Pure host code: same geometry rule as the
    wrapper, same class rule as the kernel (_block_class)."""
    import numpy as np

    bq, bk = _block_geometry(S, C, G, hd, block_q, block_k, interpret=True)
    pad = np.asarray(pad_lens, np.int64).reshape(-1, 1, 1)
    q_start = q_offset + bq * np.arange(-(-S // bq), dtype=np.int64)
    k_start = bk * np.arange(-(-C // bk), dtype=np.int64)
    seen, padded, interior = _block_class(
        q_start[None, :, None], k_start[None, None, :], pad,
        np.int64(window), q_offset + S, C, bq, bk,
    )
    # dead_causal is what the kernel skipped before it looked at the pad
    grid = np.select([~seen, padded, interior], [0, 1, 2], default=3)
    return grid.astype(np.int8), (bq, bk)


def prefill_block_classes(pad_lens, S: int, C: int, q_offset: int = 0,
                          window: int = 0, G: int = 1, hd: int = _LANES,
                          *, block_q: int | None = None,
                          block_k: int | None = None) -> dict[str, int]:
    """How many grid cells of one flash_prefill_attention call (per layer
    and KV head) fall in each class: ``dead_causal`` (above the diagonal or
    below the window floor), ``dead_pad`` (under the row's left pad),
    ``interior`` (no mask needed) and ``edge`` (the masked body). Only
    interior and edge cells are fetched and computed."""
    import numpy as np

    grid, _ = prefill_block_class_grid(
        pad_lens, S, C, q_offset, window, G, hd,
        block_q=block_q, block_k=block_k,
    )
    counts = np.bincount(grid.ravel(), minlength=len(BLOCK_CLASSES))
    return {name: int(n) for name, n in zip(BLOCK_CLASSES, counts)}
