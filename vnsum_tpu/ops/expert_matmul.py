"""Grouped expert matrix product for sparse-expert layers, with no dropped
token, and the two ends of it: the layout that puts token slots into expert
order and the kernel that brings the experts' rows back to token order.

The rows of ``lhs`` are token slots sorted by expert and laid out in row
tiles of ``tm`` that each belong to ONE expert. ``expert_layout`` makes that
order with one stable sort over the slots AND the rows of padding (each
padding row keyed with the expert whose last tile it fills), carrying what
a slot brings along (its routing weight, its token's int8 scale) to row
order in the same sort; a second sort gives the slots their rows. No
one-hot is summed down the slots, nothing is scattered, no index is looked
up a scalar at a time (5-7 ns each on this chip; PERF.md, PR 40). The
product kernel walks the row tiles that hold a slot and multiplies each by
its expert's weights, picked by a scalar-prefetched ``tile_expert``. The
row dimension of its grid is ``tiles_used`` itself, a bound read when the
kernel starts: no grid step is taken for a tile past it (a step skipped by
``pl.when`` still cost 0.10-0.13 us, and a decode step's 120 slots on 256
experts walked 1,044 steps for 208; PERF.md, PR 42), so the cost follows
the slots that are really there while every shape stays static at the
worst case (every pick of every token on an expert held here): nothing has
a capacity, nothing is dropped.

``expert_grouped_matmul(lhs, w, ...)`` is one product; with ``w_up`` it is
the gated front half ``act(lhs . w) * (lhs . w_up)`` in one pass over
``lhs`` (``act`` ``silu``: SwiGLU, ``relu``: ReGLU); with ``act`` ``relu2``
and no ``w_up`` it is the front half of an expert that has NO gate,
``relu(lhs . w)^2`` (the square in float32 before the product is rounded;
Nemotron-H's two-matrix experts). The weights are the
STACKED leaves of every expert layer, ``[L, E, K, N]``, read in place: the layer arrives by scalar prefetch and only steers
the block index, so no layer's 900 MB of experts is ever copied out of the
stack. int8 weights are ``{"q": [L, E, K, N], "s": [L, E, N]}`` (per expert
and output channel, models/quant.py); with int8 ``lhs`` and its per-row
scale the dot runs s8 x s8 -> s32, otherwise the weight tile is converted
to the row type on the way in. The per-row scale is also where a row's
routing weight goes in (float rows take it as a factor of their own), so
the rows leave expert order already weighted.

The grid is (column tiles, row tiles) with the rows inside: an expert's
weight tile stays resident across its row tiles (prefill), and a decode
step fetches each touched expert's weights once.

``expert_combine`` is the way back for a prefill piece: see the note above
it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def expert_layout(expert_of_slot, n_experts: int, tm: int, carry=()):
    """Where each slot's row goes, and each row's slot: ONE permutation.
    ``expert_of_slot`` [N] int32 holds a local expert id in [0, n_experts)
    or -1 for a slot that has no expert here.

    The rows are the slots in expert order, each group padded from a tile's
    edge to whole tiles of ``tm``: one stable sort of the N slots together
    with the ``M - N`` rows of padding, each padding row given the expert
    whose group it fills. Returns ``row_of_slot`` [N] (the last row, a
    spare, for -1 slots), ``slot_of_row`` [M] (-1 for a row of padding:
    nobody reads its product), ``tile_expert`` [Mt], ``tiles_used`` [1],
    ``group_sizes`` [n_experts], the static row count ``M = Mt * tm`` (the
    most tiles N slots can fill — one each, or their whole tiles and a
    partly filled one an expert, whichever is less — plus the spare tile:
    121 tiles for a decode step's 120 slots on 256 experts) and
    ``carry`` ([N] arrays, a value a slot) moved to row order by the same
    sort, 0 on padding. Nothing is scattered and no index is looked up: a
    second sort brings the slots' rows back to slot order."""
    N = expert_of_slot.shape[0]
    E = n_experts
    # a used tile holds a slot, and an expert leaves at most one tile partly
    # filled: tiles_used <= min(N, N // tm + E) at any N, plus the spare
    Mt = min(N, N // tm + E) + 1
    M = Mt * tm
    i32 = jnp.int32
    experts = jnp.arange(E, dtype=i32)[None, :]
    held = expert_of_slot >= 0
    key = jnp.where(held, expert_of_slot, E).astype(i32)
    group_sizes = jnp.sum(key[:, None] == experts, axis=0, dtype=i32)
    tiles = -(-group_sizes // tm)
    tile_end = jnp.cumsum(tiles)
    tile_expert = jnp.sum(
        jnp.arange(Mt, dtype=i32)[:, None] >= tile_end[None, :], axis=1,
        dtype=i32)
    # padding row j fills expert e's last tile if it is among the first
    # pad_end[e] and not among the first pad_end[e - 1]; the rest (at least
    # a tile) lie past every group, with the slots held nowhere
    pad_end = jnp.cumsum(tiles * tm - group_sizes)
    pad_key = jnp.sum(
        jnp.arange(M - N, dtype=i32)[:, None] >= pad_end[None, :], axis=1,
        dtype=i32)
    # stable: a group keeps the slots' order and its padding comes last
    sorted_key, slot_of_row, *carried = jax.lax.sort(
        (jnp.concatenate([key, pad_key]),
         jnp.concatenate([jnp.arange(N, dtype=i32), jnp.full(M - N, N, i32)]),
         *(jnp.pad(c, (0, M - N)) for c in carry)),
        num_keys=1, is_stable=True)
    _, row = jax.lax.sort(
        (slot_of_row, jnp.arange(M, dtype=i32)), num_keys=1)
    row_of_slot = jnp.where(held, row[:N], M - 1)
    own = (sorted_key < E) & (slot_of_row < N)
    return (row_of_slot, jnp.where(own, slot_of_row, -1),
            jnp.minimum(tile_expert, E - 1), tile_end[-1:].astype(i32),
            group_sizes, M, [jnp.where(own, c, 0) for c in carried])


def _kernel(layer_ref, te_ref, *refs, quantized: bool, int8_lhs: bool,
            scaled: bool, gated: bool, act: str):
    refs = list(refs)
    x_ref = refs.pop(0)
    xs_ref = refs.pop(0) if scaled else None
    w_ref = refs.pop(0)
    ws_ref = refs.pop(0) if quantized else None
    u_ref = refs.pop(0) if gated else None
    us_ref = refs.pop(0) if gated and quantized else None
    o_ref = refs.pop(0)

    x = x_ref[...]

    def product(wr, sr):
        w = wr[0, 0]
        if int8_lhs:
            y = jax.lax.dot_general(
                x, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32)
        else:
            y = jax.lax.dot_general(
                x, w.astype(x.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        if scaled:
            y = y * xs_ref[...]
        if sr is not None:
            y = y * sr[0, 0]
        return y

    y = product(w_ref, ws_ref)
    if gated:
        gate = jnp.maximum(y, 0.0) if act == "relu" else jax.nn.silu(y)
        y = gate * product(u_ref, us_ref)
    elif act == "relu2":
        y = jnp.square(jnp.maximum(y, 0.0))
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "tm", "tn", "out_dtype", "act", "interpret"))
def expert_grouped_matmul(lhs, lhs_scale, w, w_up, layer, tile_expert,
                          tiles_used, *, tm: int, tn: int, out_dtype,
                          act: str = "silu", interpret: bool = False):
    """``out[r] = lhs[r] . w[layer, tile_expert[r // tm]]`` for the rows of
    the first ``tiles_used`` tiles, the only ones the grid takes a step for
    (with none used it takes no step); the other rows of ``out`` are
    unspecified.

    lhs [M, K] (int8 with ``lhs_scale`` [M, 1] float32, or a float type with
    ``lhs_scale`` None or a factor a row, [M, 1] float32, applied to the
    product before it is rounded: a routing weight); ``w`` and the optional ``w_up`` [L, E, K, N] or int8
    ``{"q", "s"}`` leaves; ``layer`` a scalar; ``act`` the gate's
    activation where ``w_up`` is given (``silu`` or ``relu``); without
    ``w_up`` a gate's name leaves the product plain (a down product, as
    ever) and ``relu2`` makes it the single-product front half
    ``relu(lhs . w)^2``; ``tn`` whole lane tiles, or the whole width N
    where N has no such divisor (``models/experts.py::_column_tile``);
    returns [M, N] in ``out_dtype``."""
    M, K = lhs.shape
    quantized = isinstance(w, dict)
    wq = w["q"] if quantized else w
    N = wq.shape[-1]
    int8_lhs = lhs.dtype == jnp.int8
    if int8_lhs and not quantized:
        raise ValueError("int8 rows need int8 weights")
    if int8_lhs and lhs_scale is None:
        raise ValueError("int8 rows need their scales")
    scaled = lhs_scale is not None
    if M % tm or N % tn:
        raise ValueError(f"[{M}, {N}] is not whole tiles of [{tm}, {tn}]")
    gated = w_up is not None
    if act not in (("silu", "relu") if gated else ("silu", "relu", "relu2")):
        raise ValueError(
            f"act={act!r}: a gate is silu or relu, and a single product "
            "plain under those names or relu2")

    def row(n, m, layer, te):
        return (m, 0)

    def weight(n, m, layer, te):
        return (layer[0], te[m], 0, n)

    in_specs = [pl.BlockSpec((tm, K), row)]
    operands = [lhs]
    if scaled:
        in_specs.append(pl.BlockSpec((tm, 1), row))
        operands.append(lhs_scale)
    for leaf in (w, w_up) if gated else (w,):
        in_specs.append(pl.BlockSpec((1, 1, K, tn), weight))
        operands.append(leaf["q"] if quantized else leaf)
        if quantized:
            in_specs.append(pl.BlockSpec((1, 1, 1, tn), weight))
            operands.append(leaf["s"][:, :, None, :])
    kernel = functools.partial(_kernel, quantized=quantized,
                               int8_lhs=int8_lhs, scaled=scaled, gated=gated,
                               act=act)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # the row tiles that hold a slot, and no step for the others
            grid=(N // tn, tiles_used[0]),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, tn), lambda n, m, layer, te: (m, n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        # a contract: the device trace and the benchmark's metrics name this
        # kernel by it
        name="expert_grouped_matmul",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_expert, *operands)


# -- back to token order ------------------------------------------------------
#
# A token's k rows lie anywhere in expert order, and a row gather moves them
# one descriptor a row. But the sort that made the order is stable: the picks
# that a TILE of consecutive tokens sends to one expert are consecutive rows
# of that expert's group. So a token tile needs at most one row range an
# expert, and ``expert_combine`` fetches each range as whole chunks of
# ``_CHUNK`` rows (a descriptor a chunk, ~10x fewer) into VMEM and sums a
# token's picks there with a 0/1 matrix on the MXU: the rows never make a
# second trip through HBM.

_CHUNK = 16          # rows a descriptor: a whole packed tile of bf16
_COMBINE_BLOCK = 512  # rows of the buffer one product takes
_COMBINE_VMEM = 40 * 1024 * 1024   # both halves of the row buffer


def _combine_chunks(tt: int, k: int, n_experts: int) -> int:
    """The most chunks a tile of ``tt`` tokens can need: its picks' rows, and
    for each expert a chunk the range starts inside and one it ends inside."""
    return -(-tt * k // _CHUNK) + 2 * n_experts


def _combine_rows(tt: int, k: int, n_experts: int) -> int:
    """Rows of one row buffer: those chunks, in whole blocks of a product."""
    rows = _combine_chunks(tt, k, n_experts) * _CHUNK
    return -(-rows // _COMBINE_BLOCK) * _COMBINE_BLOCK


def _combine_geometry(k: int, n_experts: int, D: int,
                      itemsize: int) -> tuple[int, int]:
    """(tokens a grid step sums, columns of the rows it fetches): as many
    tokens as keep both row buffers in VMEM at the rows' whole width; where
    no tile does — many experts: a tile may need two chunks for each of
    them, 256 experts 3,072 wide are 50 MB a buffer before any pick —
    the widest whole-lane share of the columns at which one does, and a
    grid step sums one such share of a token tile."""
    for columns in range(1, max(D // 128, 1) + 1):
        if columns > 1 and D % (columns * 128):
            continue
        for tt in (256, 128, 64, 32):
            if (2 * _combine_rows(tt, k, n_experts) * (D // columns)
                    * itemsize <= _COMBINE_VMEM):
                return tt, D // columns
    raise ValueError(
        f"expert_combine: no tile of tokens fits {_COMBINE_VMEM} bytes of "
        f"VMEM at k={k}, {n_experts} experts, {D} columns")


def _combine_plan(expert_of_slot, row_of_slot, n_experts: int, k: int,
                  tt: int):
    """What the kernel is told. For token tile i: ``chunk_row`` [nt, C] the
    first row of each chunk to fetch, ``n_chunks`` [nt], and for every pick
    ``pos`` [nt * tt, k], where its row lands in the tile's buffer (-1 for a
    pick not held)."""
    i32 = jnp.int32
    E = n_experts
    nt = expert_of_slot.shape[0] // (tt * k)
    C = _combine_chunks(tt, k, E)
    e = expert_of_slot.reshape(nt, tt * k)
    row = row_of_slot.reshape(nt, tt * k)
    hit = e[:, :, None] == jnp.arange(E, dtype=i32)[None, None, :]
    count = jnp.sum(hit, axis=1, dtype=i32)                      # [nt, E]
    start = jnp.min(jnp.where(hit, row[:, :, None], jnp.iinfo(i32).max),
                    axis=1)
    start = jnp.where(count > 0, start // _CHUNK * _CHUNK, 0)    # aligned
    end = jnp.max(jnp.where(hit, row[:, :, None] + 1, 0), axis=1)
    chunks = jnp.where(count > 0, -(-(end - start) // _CHUNK), 0)
    chunk_end = jnp.cumsum(chunks, axis=1)
    chunk_base = chunk_end - chunks
    # a pick's row r of expert e lands at (chunk_base[e] * CHUNK + r - start[e])
    shift = chunk_base * _CHUNK - start
    pos = jnp.where(
        e >= 0, row + jnp.sum(jnp.where(hit, shift[:, None, :], 0), axis=2),
        -1)
    c = jnp.arange(C, dtype=i32)[None, :, None]                  # [1, C, 1]
    mine = (c >= chunk_base[:, None, :]) & (c < chunk_end[:, None, :])
    chunk_row = jnp.sum(jnp.where(
        mine, start[:, None, :] + (c - chunk_base[:, None, :]) * _CHUNK, 0),
        axis=2)
    return (chunk_row.reshape(-1).astype(i32), chunk_end[:, -1].astype(i32),
            pos.reshape(nt * tt, k).astype(i32), C)


def _combine_kernel(chunk_row_ref, n_chunks_ref, pos_ref, y_ref, o_ref,
                    buf, acc, sem, *, C: int, k: int, exact: bool,
                    columns: int):
    # grid step i sums column share i % columns of token tile i // columns
    # (one share: the whole rows of tile i)
    i = pl.program_id(0)
    tt, dc = o_ref.shape

    def tile_of(step):
        return step // columns if columns > 1 else step

    def chunk(step, half, c):
        r = pl.multiple_of(chunk_row_ref[tile_of(step) * C + c], _CHUNK)
        rows = y_ref.at[pl.ds(r, _CHUNK)]
        if columns > 1:
            rows = y_ref.at[pl.ds(r, _CHUNK), pl.ds(
                pl.multiple_of(step % columns * dc, 128), dc)]
        return pltpu.make_async_copy(
            rows,
            buf.at[half, pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)],
            sem.at[half])

    def chunks_of(step):
        return n_chunks_ref[tile_of(step)]

    def fetch(step, half):
        def start(c, carry):
            chunk(step, half, c).start()
            return carry
        jax.lax.fori_loop(0, chunks_of(step), start, 0)

    @pl.when(i == 0)
    def _first():
        # what a product meets in the buffer beside the rows it asked for
        # has to be a number: zeros now, rows of earlier tiles later
        buf[...] = jnp.zeros_like(buf)
        fetch(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():
        fetch(i + 1, (i + 1) % 2)

    half = i % 2

    def wait(c, carry):
        chunk(i, half, c).wait()
        return carry
    jax.lax.fori_loop(0, chunks_of(i), wait, 0)

    acc[...] = jnp.zeros_like(acc)
    B = _COMBINE_BLOCK

    def block(b, carry):
        lane = jax.lax.broadcasted_iota(jnp.int32, (tt, B), 1) + b * B
        picks = jnp.zeros((tt, B), jnp.float32)
        for j in range(k):
            picks = picks + (pos_ref[:, j:j + 1] == lane).astype(jnp.float32)
        rows = buf[half, pl.ds(pl.multiple_of(b * B, B), B), :]
        acc[...] += jax.lax.dot_general(
            picks.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST if exact else None)
        return carry
    jax.lax.fori_loop(0, -(-chunks_of(i) * _CHUNK // B), block, 0)
    o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_experts", "k", "interpret"))
def expert_combine(y, expert_of_slot, row_of_slot, *, n_experts: int, k: int,
                   interpret: bool = False):
    """``out[t] = sum over t's held picks of y[row_of_slot[t * k + j]]`` in
    float32, [T, D] in y's type: the way back from expert order.

    y [M, D] the down product's rows (the routing weight already in them);
    ``expert_of_slot`` [T * k] (-1: not held, adds nothing) and
    ``row_of_slot`` [T * k] as ``expert_layout`` gave them — the picks of a
    run of tokens to one expert must be consecutive rows (a stable sort's).
    Rows outside the chunks that hold a pick are never read."""
    D = y.shape[1]
    T = expert_of_slot.shape[0] // k
    tt, dc = _combine_geometry(k, n_experts, D, y.dtype.itemsize)
    columns = D // dc
    pad = -T % tt
    if pad:
        expert_of_slot = jnp.pad(expert_of_slot, (0, pad * k),
                                 constant_values=-1)
        row_of_slot = jnp.pad(row_of_slot, (0, pad * k))
    chunk_row, n_chunks, pos, C = _combine_plan(
        expert_of_slot, row_of_slot, n_experts, k, tt)
    R = _combine_rows(tt, k, n_experts)
    kernel = functools.partial(_combine_kernel, C=C, k=k,
                               exact=y.dtype == jnp.float32, columns=columns)
    if columns == 1:
        pick_block = out_block = lambda i, cr, nc: (i, 0)   # noqa: E731
    else:
        pick_block = lambda i, cr, nc: (i // columns, 0)    # noqa: E731
        out_block = lambda i, cr, nc: (i // columns, i % columns)  # noqa: E731
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=((T + pad) // tt * columns,),
            in_specs=[pl.BlockSpec((tt, k), pick_block),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tt, dc), out_block),
            scratch_shapes=[pltpu.VMEM((2, R, dc), y.dtype),
                            pltpu.VMEM((tt, dc), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((T + pad, D), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="expert_combine",
    )(chunk_row, n_chunks, pos, y)
    return out[:T]
