"""The Mamba-2 recurrence for the one-shot program: a chunked
state-space-duality scan for prefill and a one-token state update for
decode, each as its XLA form and as a Pallas TPU kernel.

The recurrence, per row, head ``h`` (``P`` channels, a state of ``N``):

    H_t = exp(dt_t[h] * A[h]) * H_{t-1} + dt_t[h] * X_t[h] (x) B_t[g(h)]
    Y_t[h] = H_t C_t[g(h)] + D[h] * X_t[h]

with ``dt`` already through its softplus, ``A`` negative, and ``B``, ``C``
``[G, N]`` a token: ``G`` groups, head ``h`` reading group ``g(h) = h //
(H / G)`` (one group: every head the same ``B`` and ``C``, Granite-4.0-H;
eight groups of eight heads, Nemotron-H). A position whose ``X`` is zero adds
nothing to the state, so a state that is zero stays zero through a row's
left pad whatever ``dt`` reads there (``models/granite_hybrid.py`` zeroes
``X``, ``B`` and ``C`` under the pad).

**The state's layout** is ``[L, B, N, H * P]``: the state's own dim on the
sublanes and (head, channel) on the lanes, transposed from the
``[H, P, N]`` of the equations. Everything a step scales the state by — a
head's decay, ``dt * X`` — varies along (head, channel), so it is a lane
row of the ``[B, H * P]`` arrays the layer already has; ``B_t`` alone
varies along the sublanes and is a ``[N, 1]`` column a group, which the
group's heads — a run of ``H * P / G`` lanes — share. The decode update is
then element-wise products and one sublane sum, in float32 with no matrix
product and no transpose, and every matrix product of the prefill kernel
is a plain ``[m, k] @ [k, n]``. Both kernels take the whole stacked state
with the layer's index as a prefetched scalar and write the layer's block
back **in place** (``input_output_aliases``): the decode loop's carry does
not copy it. The prefill kernel also takes a ROW PIECE: ``rows`` names, for
each row of its inputs, the batch row of the state it continues (a third
prefetched vector, which steers the state's index_map alone), and the
state's other rows stay as they are.

**The chunked form** (``ssd_prefill_scan``; ``chunk`` = the config's
``mamba_chunk_size``) is how the recurrence is computed, not another model.
With ``cum_i`` the running sum of ``dt * A`` inside a chunk, for one head:

    Y  = ((C B^T) * L) (dt * X) + exp(cum) * (C H_in)   L_ij = exp(cum_i - cum_j), i >= j
    H_out = exp(cum_last) H_in + B^T (exp(cum_last - cum) * dt * X)

Grid (rows, chunks), chunks in sequence with the row's state — all heads —
in VMEM scratch; the heads go ``128 / P`` at a time (a lane tile: two
heads of 64), so every product is 128 lanes wide: the tile's heads share
the operand ``dt * X`` and each takes its own lanes of the result (a tile
never spans two groups: it holds ``min(128 / P, H / G)`` heads). The
decays, ``C B^T`` (once a chunk and GROUP, shared by the group's heads: by
all of them with one group, by eight heads = four lane tiles at Nemotron-H's
widths), the masked product
and the state update never leave VMEM. ``cum`` is summed outside, in
float32 by XLA (a product on the MXU would round it), and handed in twice,
by rows and by columns. A chunk wholly under the row's left pad is neither
fetched nor computed: its ``Y`` is written as zeros and the state passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# x and y blocks of [chunk, H * P] double-buffered, the state's block in,
# out and in scratch, and a handful of [chunk, chunk] float32 temporaries:
# ~22 MiB at 64 heads of 64, chunk 256, state 128
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _whole_chunks(chunk: int, *arrays):
    """[B, S, ...] arrays padded at the END of S to whole chunks (with
    ``dt`` = 0 a position neither decays nor adds)."""
    tail = -arrays[0].shape[1] % chunk
    if not tail:
        return arrays
    return tuple(jnp.pad(a, ((0, 0), (0, tail)) + ((0, 0),) * (a.ndim - 2))
                 for a in arrays)


# -- XLA forms ----------------------------------------------------------------


def _by_group(a: jax.Array, ndim: int) -> jax.Array:
    """B or C with its group dim: ``[..., N]`` of one group (``ndim - 1``
    dims) becomes ``[..., 1, N]``."""
    return a if a.ndim == ndim else a[..., None, :]


def ssd_chunked_xla(x, dt, A, Bm, Cm, D, state, chunk: int, rows=None):
    """The chunked scan in plain XLA: x [B, S, H, P], dt [B, S, H] float32
    (through its softplus), A, D [H], Bm, Cm [B, S, G, N] (or [B, S, N]: one
    group), state [B, N, H * P] float32 -> (y [B, S, H, P] in x's type, the
    state after the S tokens). S is padded at its END to whole chunks.
    ``rows`` [B] int32: x's rows are a piece of a ``state`` that holds more
    of them, row b of x continuing ``state[rows[b]]``; those rows of the
    state are returned rewritten, its others as they came."""
    if rows is not None:
        y, piece = ssd_chunked_xla(x, dt, A, Bm, Cm, D, state[rows], chunk)
        return y, state.at[rows].set(piece)
    Bt, S, H, P = x.shape
    Bm, Cm = _by_group(Bm, 4), _by_group(Cm, 4)
    G, N = Bm.shape[-2:]
    R = H // G                                             # heads a group
    x, dt, Bm, Cm = _whole_chunks(chunk, x, dt, Bm, Cm)
    nc = x.shape[1] // chunk
    f32 = jnp.float32
    xc = x.reshape(Bt, nc, chunk, G, R, P)
    dtc = dt.astype(f32).reshape(Bt, nc, chunk, H)
    Bc = Bm.reshape(Bt, nc, chunk, G, N)
    Cc = Cm.reshape(Bt, nc, chunk, G, N)
    cum = jnp.cumsum(dtc * A.astype(f32), axis=2)          # [B, nc, Q, H]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [B, nc, i, j, H]
    decay = jnp.exp(jnp.where(tri[None, None, :, :, None], diff, -jnp.inf)
                    ).reshape(Bt, nc, chunk, chunk, G, R)
    CB = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                    preferred_element_type=f32)
    xd = xc.astype(f32) * dtc.reshape(Bt, nc, chunk, G, R)[..., None]
    y = jnp.einsum("bcgij,bcijgr,bcjgrp->bcigrp", CB, decay, xd)
    # what each chunk adds to the state, and the state entering each chunk
    to_end = jnp.exp(cum[:, :, -1:, :] - cum).reshape(Bt, nc, chunk, G, R)
    adds = jnp.einsum("bcjgn,bcjgr,bcjgrp->bcngrp", Bc.astype(f32), to_end,
                      xd)
    total = jnp.exp(cum[:, :, -1, :]).reshape(Bt, nc, G, R)

    def step(h, xs):
        add, tot = xs
        return h * tot[:, None, :, :, None] + add, h

    h0 = state.reshape(Bt, N, G, R, P)
    h_end, h_in = jax.lax.scan(
        step, h0, (adds.swapaxes(0, 1), total.swapaxes(0, 1)))
    h_in = h_in.swapaxes(0, 1)                          # [B, nc, N, G, R, P]
    y = y + jnp.einsum("bcign,bcngrp->bcigrp", Cc.astype(f32), h_in) \
        * jnp.exp(cum).reshape(Bt, nc, chunk, G, R)[..., None]
    y = y + D.astype(f32).reshape(G, R)[..., None] * xc.astype(f32)
    y = y.reshape(Bt, nc * chunk, H, P)[:, :S]
    return y.astype(x.dtype), h_end.reshape(Bt, N, H * P)


def ssm_step_xla(x, dt, A, Bv, Cv, D, state):
    """One token of the recurrence: x [B, H, P], dt [B, H] float32, Bv, Cv
    [B, G, N] (or [B, N]: one group), state [B, N, H * P] float32 ->
    (y [B, H, P] float32, state)."""
    Bt, H, P = x.shape
    Bv, Cv = _by_group(Bv, 3), _by_group(Cv, 3)
    G, N = Bv.shape[-2:]
    f32 = jnp.float32
    decay = jnp.repeat(jnp.exp(dt * A.astype(f32)), P, axis=-1)   # [B, HP]
    dtx = (dt[..., None] * x.astype(f32)).reshape(Bt, H * P)
    # a group's heads are a run of H * P / G lanes
    lanes = lambda a: a.reshape(a.shape[:-1] + (G, H * P // G))  # noqa: E731
    state = (lanes(state) * lanes(decay)[:, None]
             + Bv.astype(f32).swapaxes(1, 2)[..., None] * lanes(dtx)[:, None])
    y = jnp.einsum("bngk,bgn->bgk", state, Cv.astype(f32),
                   precision=jax.lax.Precision.HIGHEST)
    y = y.reshape(Bt, H, P) + D.astype(f32)[:, None] * x.astype(f32)
    return y, state.reshape(Bt, N, H * P)


# -- the prefill kernel -------------------------------------------------------


def _heads_per_tile(H: int, P: int, G: int = 1) -> int:
    """Heads a lane tile holds: 128 / P of them, two at P = 64 (all of them
    where the heads together are narrower than a tile), and never heads of
    two groups."""
    hpt = max(1, min(H, _LANES // P)) if P <= _LANES else 1
    return min(hpt, H // G)


def _prefill_kernel(lidx_ref, pad_ref, x_ref, dtc_ref, cumc_ref, cumr_ref,
                    bt_ref, c_ref, d_ref, hin_ref, y_ref, hout_ref, h_scr, *,
                    chunk: int, n_heads: int, head_dim: int, hpt: int,
                    n_groups: int):
    b = pl.program_id(0)
    c = pl.program_id(1)
    nc = pl.num_programs(1)
    Q, P, W = chunk, head_dim, hpt * head_dim
    N = h_scr.shape[0]
    tiles_a_group = n_heads // n_groups // hpt
    f32 = jnp.float32

    @pl.when(c == 0)
    def _load():
        h_scr[...] = hin_ref[0, 0]

    # a chunk wholly under the row's left pad: nothing enters the state
    live = (c + 1) * Q > pad_ref[b]

    @pl.when(jnp.logical_not(live))
    def _skip():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live)
    def _chunk():
        dtype = x_ref.dtype
        tri = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
               >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
        head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_heads), 1)
        tile_lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // P
        dtc, cumc = dtc_ref[0], cumc_ref[0]                  # [Q, H]

        def column(block, h):
            """Head h's values down the chunk, [Q, 1]: its lane of the
            [Q, H] block (a masked lane sum; a lane cannot be sliced by a
            traced index)."""
            return jnp.sum(jnp.where(head_lane == h, block, 0.0), axis=1,
                           keepdims=True)

        def group(g, _):
            # the group's B^T [N, Q] and C [Q, N]: its rows and its lanes of
            # the blocks (all of them with one group)
            if n_groups == 1:
                Bt, Cc = bt_ref[0], c_ref[0]
            else:
                Bt = bt_ref[0, pl.ds(pl.multiple_of(g * N, N), N), :]
                Cc = c_ref[0, :, pl.ds(pl.multiple_of(g * N, N), N)]
            # C B^T, once a chunk and group: the group's heads share it
            CB = jnp.dot(Cc, Bt, preferred_element_type=f32)     # [Q, Q]

            def tile(t, _):
                t = g * tiles_a_group + t
                lanes = pl.ds(pl.multiple_of(t * W, W), W)
                xp = x_ref[0, :, lanes].astype(f32)              # [Q, W]
                # per head of the tile: its columns, then one row or column
                # of the tile's own lanes picked from them
                cols = [(column(dtc, t * hpt + u), column(cumc, t * hpt + u))
                        for u in range(hpt)]

                def by_head(values):
                    out = values[0]
                    for u in range(1, hpt):
                        out = jnp.where(tile_lane == u, values[u], out)
                    return out

                last = [cum[Q - 1:Q, :] for _, cum in cols]      # [1, 1] each
                xd = xp * by_head([dt for dt, _ in cols])        # dt * X
                xd_in = xd.astype(dtype)
                y = None
                for u in range(hpt):
                    cum = cols[u][1]
                    row = cumr_ref[0, pl.ds(t * hpt + u, 1), :]  # [1, Q]
                    decay = jnp.exp(jnp.where(tri, cum - row, -jnp.inf))
                    yu = jnp.dot((CB * decay).astype(dtype), xd_in,
                                 preferred_element_type=f32)     # [Q, W]
                    y = yu if y is None else jnp.where(tile_lane == u, yu, y)
                hp = h_scr[:, lanes]                             # [N, W] f32
                y = y + jnp.dot(Cc, hp.astype(dtype),
                                preferred_element_type=f32) * by_head(
                                    [jnp.exp(cum) for _, cum in cols])
                y = y + d_ref[:, lanes] * xp
                y_ref[0, :, lanes] = y.astype(y_ref.dtype)
                xw = xd * by_head(
                    [jnp.exp(end - cum) for end, (_, cum) in zip(last, cols)])
                h_scr[:, lanes] = hp * by_head(
                    [jnp.exp(end) for end in last]) \
                    + jnp.dot(Bt, xw.astype(dtype),
                              preferred_element_type=f32)

            jax.lax.fori_loop(0, tiles_a_group, tile, None)

        if n_groups == 1:
            group(0, None)
        else:
            jax.lax.fori_loop(0, n_groups, group, None)

    @pl.when(c == nc - 1)
    def _store():
        hout_ref[0, 0] = h_scr[...]


def _prefill_kernel_of_rows(lidx_ref, pad_ref, rows_ref, *refs, **geometry):
    """``_prefill_kernel`` under a third prefetched vector (``rows``), which
    only the state's index_map reads."""
    del rows_ref
    _prefill_kernel(lidx_ref, pad_ref, *refs, **geometry)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_prefill_scan(x, dt, A, Bm, Cm, D, state, layer_idx, pad_lens,
                     rows=None, *, chunk: int, interpret: bool = False):
    """The chunked scan over S tokens from layer ``layer_idx``'s state of
    the stacked ``state`` [L, B, N, H * P] float32; x [B, S, H, P], dt
    [B, S, H] float32, Bm, Cm [B, S, G, N] (or [B, S, N]: one group),
    ``pad_lens`` [B] the left-pad slots among these S (whole chunks of them
    are skipped). Returns (y [B, S, H, P], the stacked state with the
    layer's block overwritten in place). Semantics: ``ssd_chunked_xla``.

    ``rows`` [B] int32 (distinct): x's rows are a piece of a state that
    holds more of them, and row b continues — and overwrites, in place —
    the state's batch row ``rows[b]``; no other row of the state is
    fetched or written."""
    Bt, S, H, P = x.shape
    Bm, Cm = _by_group(Bm, 4), _by_group(Cm, 4)
    G, N = Bm.shape[-2:]
    HP = H * P
    if H % G:
        raise ValueError(f"{G} groups do not divide {H} heads")
    hpt = _heads_per_tile(H, P, G)
    if (H // G) % hpt:
        raise ValueError(
            f"a group's {H // G} heads do not fill lane tiles of {hpt}")
    x, dt, Bm, Cm = _whole_chunks(chunk, x, dt, Bm, Cm)
    Sp = x.shape[1]
    nc = Sp // chunk
    f32 = jnp.float32
    dt = dt.astype(f32)
    # the running sum of dt * A inside each chunk, in float32, by columns
    # [B, S, H] and by rows [B, H, S]
    cum = jnp.cumsum(
        (dt * A.astype(f32)).reshape(Bt, nc, chunk, H), axis=2
    ).reshape(Bt, Sp, H)
    cum_rows = cum.transpose(0, 2, 1)
    # a group's B^T its N rows of [B, G * N, S], its C its N lanes
    Bm, Cm = Bm.reshape(Bt, Sp, G * N), Cm.reshape(Bt, Sp, G * N)
    Bt_rows = Bm.transpose(0, 2, 1)
    d_lanes = jnp.repeat(D.astype(f32), P)[None, :]          # [1, HP]

    # a third prefetched vector, where the state's rows are named: it steers
    # the state's index_map alone, and the kernel's body never sees it
    prefetch = 2 if rows is None else 3

    def first_live(b, c, pad):
        # a pad chunk parks on the row's first live one: no fetch of its own
        return jnp.minimum(jnp.maximum(c, pad[b] // chunk), nc - 1)

    seq_block = lambda width: pl.BlockSpec(  # noqa: E731
        (1, chunk, width),
        lambda b, c, lidx, pad, *rows: (b, first_live(b, c, pad), 0))
    row_block = lambda height: pl.BlockSpec(  # noqa: E731
        (1, height, chunk),
        lambda b, c, lidx, pad, *rows: (b, 0, first_live(b, c, pad)))
    state_block = pl.BlockSpec(
        (1, 1, N, HP), lambda b, c, lidx, pad, *rows: (
            lidx[0], rows[0][b] if rows else b, 0, 0))
    kernel = functools.partial(
        _prefill_kernel if rows is None else _prefill_kernel_of_rows,
        chunk=chunk, n_heads=H, head_dim=P, hpt=hpt, n_groups=G)
    y, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prefetch,
            grid=(Bt, nc),
            in_specs=[
                seq_block(HP),      # x
                seq_block(H),       # dt by columns
                seq_block(H),       # cum by columns
                row_block(H),       # cum by rows
                row_block(G * N),   # B^T, group by group
                seq_block(G * N),   # C, group by group
                pl.BlockSpec((1, HP), lambda b, c, *prefetched: (0, 0)),
                state_block,
            ],
            out_specs=[
                pl.BlockSpec((1, chunk, HP),
                             lambda b, c, *prefetched: (b, c, 0)),
                state_block,
            ],
            scratch_shapes=[pltpu.VMEM((N, HP), f32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bt, Sp, HP), x.dtype),
            jax.ShapeDtypeStruct(state.shape, f32),
        ],
        # the call's last operand, after the prefetched scalars and the seven
        # blocks before it, is the state
        input_output_aliases={prefetch + 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        # a contract: the device trace and the benchmark's metrics name this
        # kernel by it
        name="ssd_prefill_scan",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        pad_lens.astype(jnp.int32),
        *(() if rows is None else (rows.astype(jnp.int32),)),
        x.reshape(Bt, Sp, HP), dt, cum, cum_rows, Bt_rows, Cm, d_lanes,
        state,
    )
    return y[:, :S].reshape(Bt, S, H, P), state


def scan_tokens_computed(pad_lens, S: int, chunk: int) -> int:
    """Tokens of the chunks ``ssd_prefill_scan`` does not skip, summed over
    rows, for one call over S tokens with ``pad_lens`` left-pad slots among
    them. Host arithmetic, the kernel's rule."""
    import numpy as np

    pads = np.minimum(np.asarray(pad_lens, np.int64), S)
    chunks = -(-S // chunk)
    return int(((chunks - pads // chunk) * chunk).sum())


# -- the decode kernel --------------------------------------------------------


def _decode_kernel(lidx_ref, decay_ref, dtx_ref, bcol_ref, ccol_ref,
                   hin_ref, y_ref, hout_ref, *, n_groups: int):
    # a group's heads are a run of H * P / G lanes; each run takes its
    # group's columns of B and C
    W = hin_ref.shape[-1] // n_groups
    for g in range(n_groups):
        lanes = slice(g * W, (g + 1) * W)
        h = (hin_ref[0, 0, :, lanes] * decay_ref[0, :, lanes]    # [N, W]
             + bcol_ref[0, g] * dtx_ref[0, :, lanes])        # [N, 1] * [1, W]
        hout_ref[0, 0, :, lanes] = h
        y_ref[0, :, lanes] = jnp.sum(h * ccol_ref[0, g], axis=0,
                                     keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_update(x, dt, A, Bv, Cv, D, state, layer_idx, *,
                      interpret: bool = False):
    """One token for every row: x [B, H, P], dt [B, H] float32, Bv, Cv
    [B, G, N] (or [B, N]: one group), the stacked ``state``
    [L, B, N, H * P] float32, whose layer ``layer_idx`` is read and
    overwritten in place. Returns (y [B, H, P] float32, the stacked state).
    Semantics: ``ssm_step_xla``."""
    Bt, H, P = x.shape
    Bv, Cv = _by_group(Bv, 3), _by_group(Cv, 3)
    G, N = Bv.shape[-2:]
    HP = H * P
    if HP % G:
        raise ValueError(f"{G} groups do not divide {H} heads")
    f32 = jnp.float32
    xf = x.astype(f32)
    decay = jnp.repeat(jnp.exp(dt * A.astype(f32)), P, axis=-1)
    dtx = (dt[..., None] * xf).reshape(Bt, HP)
    row = pl.BlockSpec((1, 1, HP), lambda b, lidx: (b, 0, 0))
    col = pl.BlockSpec((1, G, N, 1), lambda b, lidx: (b, 0, 0, 0))
    state_block = pl.BlockSpec(
        (1, 1, N, HP), lambda b, lidx: (lidx[0], b, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, n_groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Bt,),
            in_specs=[row, row, col, col, state_block],
            out_specs=[row, state_block],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bt, 1, HP), f32),
            jax.ShapeDtypeStruct(state.shape, f32),
        ],
        # operand 5 of the call (the prefetched scalar first) is the state
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ssm_decode_update",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        decay[:, None, :], dtx[:, None, :],
        Bv.astype(f32)[..., None], Cv.astype(f32)[..., None], state,
    )
    y = y.reshape(Bt, H, P) + D.astype(f32)[:, None] * xf
    return y, state
