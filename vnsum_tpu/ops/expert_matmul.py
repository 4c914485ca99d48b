"""Grouped expert matrix product for sparse-expert layers, with no dropped
token.

The rows of ``lhs`` are token slots already sorted by expert and laid out in
row tiles of ``tm`` that each belong to ONE expert (``expert_layout`` pads a
group to whole tiles). The kernel walks the row tiles and multiplies each
by its expert's weights, picked by a scalar-prefetched ``tile_expert``;
tiles past ``tiles_used`` are neither fetched nor computed, so the cost
follows the slots that are really there while every shape stays static at
the worst case (every pick of every token on an expert held here): nothing
has a capacity, nothing is dropped.

``expert_grouped_matmul(lhs, w, ...)`` is one product; with ``w_up`` it is
the gated front half ``act(lhs . w) * (lhs . w_up)`` in one pass over
``lhs`` (``act`` ``silu``: SwiGLU, ``relu``: ReGLU). The weights are the
STACKED leaves of every expert layer, ``[L, E, K, N]``, read in place: the layer arrives by scalar prefetch and only steers
the block index, so no layer's 900 MB of experts is ever copied out of the
stack. int8 weights are ``{"q": [L, E, K, N], "s": [L, E, N]}`` (per expert
and output channel, models/quant.py); with int8 ``lhs`` and its per-row
scale the dot runs s8 x s8 -> s32, otherwise the weight tile is converted
to the row type on the way in.

The grid is (column tiles, row tiles) with the rows inside: an expert's
weight tile stays resident across its row tiles (prefill), and a decode
step fetches each touched expert's weights once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def expert_layout(expert_of_slot, n_experts: int, tm: int):
    """Where each slot's row goes. ``expert_of_slot`` [N] int32 holds a local
    expert id in [0, n_experts) or -1 for a slot that has no expert here.

    Returns ``row_of_slot`` [N] (the last row, a spare, for -1 slots),
    ``tile_expert`` [Mt], ``tiles_used`` [1], ``group_sizes`` [n_experts]
    and the static row count ``M = Mt * tm``: every slot plus up to a tile
    of padding an expert, plus the spare tile."""
    N = expert_of_slot.shape[0]
    Mt = -(-N // tm) + n_experts + 1
    held = expert_of_slot >= 0
    onehot = (expert_of_slot[:, None] == jnp.arange(n_experts)[None, :])
    rank = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1   # [N, E]
    group_sizes = jnp.sum(onehot.astype(jnp.int32), axis=0)
    tiles = -(-group_sizes // tm)
    tile_end = jnp.cumsum(tiles)
    group_start = (tile_end - tiles) * tm
    e = jnp.maximum(expert_of_slot, 0)
    row = group_start[e] + jnp.take_along_axis(rank, e[:, None], axis=1)[:, 0]
    row_of_slot = jnp.where(held, row, Mt * tm - 1).astype(jnp.int32)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(Mt), side="right"),
        n_experts - 1).astype(jnp.int32)
    return (row_of_slot, tile_expert, tile_end[-1:].astype(jnp.int32),
            group_sizes, Mt * tm)


def _kernel(layer_ref, te_ref, used_ref, *refs, quantized: bool,
            int8_lhs: bool, gated: bool, act: str):
    refs = list(refs)
    x_ref = refs.pop(0)
    xs_ref = refs.pop(0) if int8_lhs else None
    w_ref = refs.pop(0)
    ws_ref = refs.pop(0) if quantized else None
    u_ref = refs.pop(0) if gated else None
    us_ref = refs.pop(0) if gated and quantized else None
    o_ref = refs.pop(0)

    @pl.when(pl.program_id(1) < used_ref[0])
    def _compute():
        x = x_ref[...]

        def product(wr, sr):
            w = wr[0, 0]
            if int8_lhs:
                y = jax.lax.dot_general(
                    x, w, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32).astype(jnp.float32)
                y = y * xs_ref[...]
            else:
                y = jax.lax.dot_general(
                    x, w.astype(x.dtype), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            if sr is not None:
                y = y * sr[0, 0]
            return y

        y = product(w_ref, ws_ref)
        if gated:
            gate = jnp.maximum(y, 0.0) if act == "relu" else jax.nn.silu(y)
            y = gate * product(u_ref, us_ref)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "tm", "tn", "out_dtype", "act", "interpret"))
def expert_grouped_matmul(lhs, lhs_scale, w, w_up, layer, tile_expert,
                          tiles_used, *, tm: int, tn: int, out_dtype,
                          act: str = "silu", interpret: bool = False):
    """``out[r] = lhs[r] . w[layer, tile_expert[r // tm]]`` for the rows of
    the first ``tiles_used`` tiles; the other rows of ``out`` are
    unspecified.

    lhs [M, K] (int8 with ``lhs_scale`` [M, 1] float32, or a float type with
    ``lhs_scale`` None); ``w`` and the optional ``w_up`` [L, E, K, N] or int8
    ``{"q", "s"}`` leaves; ``layer`` a scalar; ``act`` the gate's
    activation where ``w_up`` is given (``silu`` or ``relu``); returns
    [M, N] in ``out_dtype``."""
    M, K = lhs.shape
    quantized = isinstance(w, dict)
    wq = w["q"] if quantized else w
    N = wq.shape[-1]
    int8_lhs = lhs.dtype == jnp.int8
    if int8_lhs and not quantized:
        raise ValueError("int8 rows need int8 weights")
    if M % tm or N % tn:
        raise ValueError(f"[{M}, {N}] is not whole tiles of [{tm}, {tn}]")
    gated = w_up is not None
    if act not in ("silu", "relu"):
        raise ValueError(f"act={act!r}: the gate is silu or relu")

    def tile(m, used):
        # a tile past the last one used repeats its index: nothing is
        # fetched for it, and its (skipped) output block is not written out
        return jnp.minimum(m, jnp.maximum(used[0] - 1, 0))

    def row(n, m, layer, te, used):
        return (tile(m, used), 0)

    def weight(n, m, layer, te, used):
        return (layer[0], te[tile(m, used)], 0, n)

    in_specs = [pl.BlockSpec((tm, K), row)]
    operands = [lhs]
    if int8_lhs:
        in_specs.append(pl.BlockSpec((tm, 1), row))
        operands.append(lhs_scale)
    for leaf in (w, w_up) if gated else (w,):
        in_specs.append(pl.BlockSpec((1, 1, K, tn), weight))
        operands.append(leaf["q"] if quantized else leaf)
        if quantized:
            in_specs.append(pl.BlockSpec((1, 1, 1, tn), weight))
            operands.append(leaf["s"][:, :, None, :])
    kernel = functools.partial(_kernel, quantized=quantized,
                               int8_lhs=int8_lhs, gated=gated, act=act)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, M // tm),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (tm, tn),
                lambda n, m, layer, te, used: (tile(m, used), n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        # a contract: the device trace and the benchmark's metrics name this
        # kernel by it
        name="expert_grouped_matmul",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_expert, tiles_used,
      *operands)
