"""Power retention (degree 2) for the one-shot program: a chunked prefill
scan and a one-token state update, each as its XLA form and as a Pallas TPU
kernel.

The layer, per row, KV head ``h`` and each of its ``G`` query heads ``a``
(``d`` channels a head, ``s`` the score's scale, ``gamma_t <= 0`` the KV
head's log-decay at token ``t``):

    w_tj  = (s q_t^a . k_j^h)^2 * exp(gamma_{j+1} + ... + gamma_t)       j <= t
    o_t^a = sum_j w_tj v_j^h / (sum_j w_tj + eps)

which is attention with the softmax's exponential replaced by a square — and
a square is a dot product of expanded vectors, ``(a . b)^2 = phi(a) .
phi(b)``, so the same sums are a recurrence over a state of FIXED size:

    S_t = exp(gamma_t) S_{t-1} + phi(k_t) v_t^T       z_t alike, with 1 for v_t
    o_t^a = phi(s q_t^a)^T S_t / (phi(s q_t^a)^T z_t + eps)

**phi's layout** (``phi_tiles``). ``phi`` has the ``d (d + 1) / 2`` = 8,256
distinct products ``x_i x_j`` (at ``d`` = 128). Built here as ``T = d / 2 +
1`` = 65 TILES of ``d`` lanes: tile ``r`` is ``x * roll(x, r)``, lane ``i``
holding ``x_i x_{i-r}`` (indices mod ``d``) — one lane rotation and one
product a tile, nothing gathered. Tile 0 is the squares; tiles 1 .. d/2 - 1
hold every pair at circular distance ``r`` once; tile d/2 holds each of its
64 pairs twice: 8,320 lanes for 8,256 products. The weights that make the
dot product exact — 1, 2, ..., 2, 1 by tile (a pair ``i != j`` appears
twice in ``(a . b)^2``; tile d/2 already has it twice) — sit on the QUERY
side alone, with ``s^2``: ``phi_q(x) = w_r s^2 x_i x_{i-r}``, ``phi_k(x) =
x_i x_{i-r}``, and ``phi_q(a) . phi_k(b) = (s a . b)^2`` exactly
(``tests/test_ops_power_retention.py``). The state holds ``phi_k`` sums and
carries no weight.

**The state's layout** is ``S [L, B, KV, T, d_v, d]`` float32 — tile, value
channel on the sublanes, ``phi``'s lane on the lanes — and the normaliser
UNPACKED, ``Z [L, B, KV, d, d]`` float32 with ``Z = sum_j decay k_j k_j^T``:
``z`` in ``phi``'s layout is ``Z``'s entries at ``(i, i - r)`` and ``phi_q(q)
. z = s^2 q^T Z q`` exactly, so the denominator's read of the state is one
``[tokens, d] x [d, d]`` product and a lane sum where the packed form
would be a 129th value channel (a second pass of a 128-wide matrix unit
over every tile) — and 64 KB a head where the packed one is 33. A tile
``S[r]`` is a ``[d_v, d]`` matrix whose lanes line up with ``phi``'s tile
``r`` as the kernel makes it from a ``[tokens, d]`` block of q or k: the
read ``phi_q S[r]^T`` contracts the lanes of both, the write ``(v *
decay)^T phi_k`` is a plain product, tile by tile, and ``phi`` of a
dispatch (13 GB a layer at (12, 8192)) exists only as one ``[tokens, d]``
tile at a time in VMEM. Both kernels take the whole stacked state with the
layer's index as a prefetched scalar and write the layer's block back **in
place** (``input_output_aliases``). The prefill kernel also takes a ROW
PIECE: ``rows`` names, for each row of its inputs, the batch row of the
state it continues (a third prefetched vector, which steers the state's
index_maps alone).

**The chunked form** (``retention_prefill_scan``, ``retention_chunked_xla``;
``chunk`` = the config's ``retention_chunk_size``) is how the recurrence is
computed, not another model. With ``b_t`` the running sum of ``gamma``
inside a chunk and ``S_0, Z_0`` the state entering it:

    inside   w_tj = (s q_t . k_j)^2 exp(b_t - b_j), j <= t   (the attention form)
    before   exp(b_t) phi_q(q_t)^T S_0      and      exp(b_t) s^2 q_t^T Z_0 q_t
    o_t   = (inside's sum_j w_tj v_j + before's) / (sum_j w_tj + before's + eps)
    S_C   = exp(b_C) S_0 + sum_j exp(b_C - b_j) phi_k(k_j) v_j^T ,  Z_C alike

Every exponent is a difference ``<= 0``: nothing overflows. ``b`` is summed
outside the kernel in float32 additions (``[B, S, KV]``: 3 MB a dispatch; a
product with a triangle on the matrix unit would round it) and handed in
twice, by columns and by rows, as ``ops/ssd_scan.py`` does. The matrix
products run in the inputs' type with float32 sums — the scores before
their square, the weights against v, ``phi_q`` against the state's tile
rounded to the inputs' type for the product alone —, the squares, decays,
sums and the one division in float32. Grid (rows, KV heads, chunks), the
chunks in sequence with the KV head's state and normaliser in VMEM scratch;
a KV head's ``G`` query heads are ``G`` lane tiles of the ``[B, S, H * d]``
array the projection leaves, stacked one under the other in VMEM so that
every product with a state tile serves all of them. A position under a
row's left pad has ``k = v = 0`` (``models/brumby.py`` zeroes them), so
state and normaliser stay exactly zero through a pad and its output is
``0 / (0 + eps)``; a chunk wholly under the pad is neither fetched nor
computed: zeros are written and the state passes
(``retention_tokens_computed`` is the same rule on the host).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# [B, S, ...] arrays padded with zeros at the END of S to whole chunks: a
# position with k, v and gamma zero neither decays nor writes
from .ssd_scan import _whole_chunks

# a KV head's state in scratch and its block in and out, each
# double-buffered (5 x 4.3 MB at d = 128), beside the chunk's blocks and a
# few [G * chunk, chunk] float32 temporaries
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_HIGHEST = jax.lax.Precision.HIGHEST
# tiles of phi one product of the prefill kernel takes side by side (on the
# chip, one row of 2,048 tokens at chunks of 256: 1, 3, 7, 9, 21 tiles take
# 4.63, 3.82, 3.59, 3.56, 3.54 ms; the chunk's size moves nothing)
_GROUP_TILES = 7
# ... and of the decode kernel, whose tiles are unrolled
_DECODE_GROUP_TILES = 13


def n_tiles(d: int) -> int:
    """Tiles of ``phi`` over ``d`` channels."""
    if d % 2:
        raise ValueError(f"phi's tiles pair channel i with i - r: {d} is odd")
    return d // 2 + 1


def tile_weights(d: int) -> jax.Array:
    """[T] float32: what a tile's products count in ``(a . b)^2``."""
    T = n_tiles(d)
    return jnp.full((T,), 2.0, jnp.float32).at[0].set(1.0).at[T - 1].set(1.0)


def phi_tiles(x: jax.Array, scale: float | None = None) -> jax.Array:
    """``phi`` of x [..., d] as float32 [..., T, d]: tile r is ``x * roll(x,
    r)``. With ``scale`` the QUERY side, ``w_r scale^2`` folded in; without,
    the key side: ``sum(phi_tiles(a, s) * phi_tiles(b)) == (s a . b)^2``."""
    x = x.astype(jnp.float32)
    d = x.shape[-1]
    tiles = jnp.stack(
        [x * jnp.roll(x, r, axis=-1) for r in range(n_tiles(d))], axis=-2)
    if scale is None:
        return tiles
    return tiles * (tile_weights(d) * scale * scale)[:, None]


# -- XLA forms ----------------------------------------------------------------


def retention_step_xla(q, k, v, gamma, S, Z, *, scale: float, eps: float):
    """One token of the recurrence: q [B, H, d], k, v [B, KV, d], gamma
    [B, KV] float32, S [B, KV, T, dv, d] and Z [B, KV, d, d] float32 ->
    (o [B, H, dv] float32, S, Z). Sums, not products on the matrix unit:
    float32 whatever the platform's default precision."""
    f32 = jnp.float32
    Bt, H, d = q.shape
    KV = k.shape[1]
    q = q.astype(f32).reshape(Bt, KV, H // KV, d)
    k, v = k.astype(f32), v.astype(f32)
    g = jnp.exp(gamma.astype(f32))
    S = S * g[:, :, None, None, None] \
        + phi_tiles(k)[:, :, :, None, :] * v[:, :, None, :, None]
    Z = Z * g[:, :, None, None] + k[..., :, None] * k[..., None, :]
    pq = phi_tiles(q, scale)                               # [B, KV, G, T, d]
    num = jnp.sum(pq[:, :, :, :, None, :] * S[:, :, None], axis=(3, 5))
    den = scale * scale * jnp.sum(
        q[..., :, None] * Z[:, :, None] * q[..., None, :], axis=(-1, -2))
    return (num / (den[..., None] + eps)).reshape(Bt, H, -1), S, Z


def retention_recurrent_xla(q, k, v, gamma, S, Z, *, scale: float,
                            eps: float):
    """The recurrence token by token (``retention_step_xla`` under a scan):
    q [B, S, H, d], k, v [B, S, KV, d], gamma [B, S, KV] -> (o [B, S, H, dv]
    float32, S, Z). What the chunked forms compute."""
    def step(carry, xs):
        o, S, Z = retention_step_xla(*xs, *carry, scale=scale, eps=eps)
        return (S, Z), o

    (S, Z), o = jax.lax.scan(
        step, (S, Z), tuple(a.swapaxes(0, 1) for a in (q, k, v, gamma)))
    return o.swapaxes(0, 1), S, Z


def retention_chunked_xla(q, k, v, gamma, S, Z, chunk: int, rows=None, *,
                          scale: float, eps: float):
    """The chunked scan in plain XLA: q [B, S, H, d], k, v [B, S, KV, d],
    gamma [B, S, KV] float32, S [B, KV, T, dv, d] and Z [B, KV, d, d]
    float32 -> (o [B, S, H, dv] in v's type, S and Z after the S tokens). S
    is padded at its END to whole chunks. ``rows`` [B] int32: the rows are
    a piece of a state that holds more of them, row b continuing
    ``S[rows[b]]``, ``Z[rows[b]]``; those rows are returned rewritten, the
    others as they came."""
    if rows is not None:
        o, Sp, Zp = retention_chunked_xla(
            q, k, v, gamma, S[rows], Z[rows], chunk, scale=scale, eps=eps)
        return o, S.at[rows].set(Sp), Z.at[rows].set(Zp)
    Bt, Sq, H, d = q.shape
    KV = k.shape[2]
    G = H // KV
    f32 = jnp.float32
    q, k, v, gamma = _whole_chunks(
        chunk, q.astype(f32), k.astype(f32), v.astype(f32),
        gamma.astype(f32))
    nc = q.shape[1] // chunk

    def chunks(a):   # [B, nc * C, ...] -> [nc, B, C, ...]
        return jnp.moveaxis(
            a.reshape((Bt, nc, chunk) + a.shape[2:]), 1, 0)

    qc = chunks(q.reshape(Bt, -1, KV, G, d))
    kc, vc = chunks(k), chunks(v)
    cum = jnp.cumsum(chunks(gamma), axis=2)                # [nc, B, C, KV]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    s2 = scale * scale

    def step(carry, xs):
        S0, Z0 = carry
        q, k, v, b = xs
        bh = b.swapaxes(1, 2)                              # [B, KV, C]
        decay = jnp.exp(jnp.where(
            tri, bh[:, :, :, None] - bh[:, :, None, :], -jnp.inf))
        sc = jnp.einsum("bthgd,bjhd->bhgtj", q, k, precision=_HIGHEST)
        w = sc * sc * s2 * decay[:, :, None]               # [B, KV, G, t, j]
        num = jnp.einsum("bhgtj,bjhc->bthgc", w, v, precision=_HIGHEST)
        den = jnp.sum(w, axis=-1).transpose(0, 3, 1, 2)    # [B, t, KV, G]
        before = jnp.exp(b)[..., None]                     # [B, t, KV, 1]
        num = num + before[..., None] * jnp.einsum(
            "bthgri,bhrci->bthgc", phi_tiles(q, scale), S0,
            precision=_HIGHEST)
        den = den + before * s2 * jnp.einsum(
            "bthgi,bhij,bthgj->bthg", q, Z0, q, precision=_HIGHEST)
        last = b[:, -1:, :]                                # [B, 1, KV]
        kd = k * jnp.exp(last - b)[..., None]
        total = jnp.exp(last[:, 0])                        # [B, KV]
        S1 = S0 * total[:, :, None, None, None] + jnp.einsum(
            "bjhri,bjhc->bhrci", phi_tiles(k), v * jnp.exp(last - b)[..., None],
            precision=_HIGHEST)
        Z1 = Z0 * total[:, :, None, None] + jnp.einsum(
            "bjhi,bjhl->bhil", kd, k, precision=_HIGHEST)
        return (S1, Z1), num / (den[..., None] + eps)

    (S, Z), o = jax.lax.scan(step, (S.astype(f32), Z.astype(f32)),
                             (qc, kc, vc, cum))
    # [nc, B, C, KV, G, dv] -> [B, S, H, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(Bt, nc * chunk, H, -1)
    return o[:, :Sq].astype(v.dtype), S, Z


# -- the prefill kernel -------------------------------------------------------


def _prefill_kernel(lidx_ref, pad_ref, q_ref, k_ref, v_ref, cumc_ref,
                    cumr_ref, sin_ref, zin_ref, o_ref, sout_ref, zout_ref,
                    s_scr, z_scr, *, chunk: int, group: int, group_tiles: int,
                    scale: float, eps: float):
    # q/o [1, C, G * d] (the KV head's query heads, a lane tile each), k/v
    # [1, C, d]; the running sum of gamma inside the chunk by columns
    # [1, C, KV] and by rows [1, KV, C], float32; the state [1, 1, 1, T, dv,
    # d] and the normaliser [1, 1, 1, d, d]
    b, h, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nc = pl.num_programs(2)
    C, G = chunk, group
    T, dv, d = s_scr.shape
    f32, dtype = jnp.float32, q_ref.dtype

    @pl.when(c == 0)
    def _load():
        s_scr[...] = sin_ref[0, 0, 0]
        z_scr[...] = zin_ref[0, 0, 0]

    # a chunk wholly under the row's left pad: nothing enters the state
    live = (c + 1) * C > pad_ref[b]

    @pl.when(jnp.logical_not(live))
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)

    def nt(a, b):     # a [m, d] . b [n, d]^T: the lanes of both
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)

    @pl.when(live)
    def _chunk():
        head_lane = jax.lax.broadcasted_iota(
            jnp.int32, (1, cumc_ref.shape[-1]), 1)
        # the head's column of the running sum (a masked lane sum: a lane
        # cannot be sliced by a traced index), and its row
        cum = jnp.sum(jnp.where(head_lane == h, cumc_ref[0], 0.0), axis=1,
                      keepdims=True)                             # [C, 1]
        row = cumr_ref[0, pl.ds(h, 1), :]                        # [1, C]
        last = cum[C - 1:C, :]                                   # [1, 1]
        total = jnp.exp(last)
        tri = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
               >= jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
        decay = jnp.exp(jnp.where(tri, cum - row, -jnp.inf))     # [C, C]
        k, v = k_ref[0], v_ref[0]
        kf = k.astype(f32)
        to_end = jnp.exp(last - cum)                             # [C, 1]
        vdT = (v.astype(f32) * to_end).T.astype(dtype)           # [dv, C]
        kdT = (kf * to_end).T.astype(dtype)                      # [d, C]
        # the G query heads one under the other: [G * C, d]
        Q = jnp.concatenate(
            [q_ref[0, :, a * d:(a + 1) * d] for a in range(G)], axis=0)
        Qf = Q.astype(f32)
        under = lambda a: jnp.concatenate([a] * G, axis=0)  # noqa: E731
        # inside the chunk: the attention form
        sc = nt(Q, k)                                            # [G C, C]
        w = sc * sc * (scale * scale) * under(decay)
        num = jnp.dot(w.astype(dtype), v, preferred_element_type=f32)
        den = jnp.sum(w, axis=1, keepdims=True)
        # from before it: the normaliser, q^T Z_0 q
        z0 = z_scr[...]
        den0 = (scale * scale) * jnp.sum(
            jnp.dot(Q, z0.astype(dtype), preferred_element_type=f32) * Qf,
            axis=1, keepdims=True)
        # ... and the state, tile by tile: phi_q's tile against S_0's, then
        # the tile's own update, which nothing of this chunk reads again.
        # sqrt(2) s on each factor is the tiles' weight 2 and s^2; the first
        # and the last tile count half of that
        Qs = Qf * (scale * 2.0 ** 0.5)

        def tiles(first, n: int, acc, half: bool = False):
            """Tiles [first, first + n): their phi_q side by side against
            the state's tiles side by side, ONE product over n d lanes (the
            float32 sum comes back once for the n of them), and each tile's
            own update."""
            pqs, olds = [], []
            for j in range(n):
                r = first + j
                pq = Qs * pltpu.roll(Qs, r, 1)
                if half:
                    pq = pq * 0.5
                s0 = s_scr[r]                                    # [dv, d]
                pqs.append(pq.astype(dtype))
                olds.append(s0.astype(dtype))
                pk = (kf * pltpu.roll(kf, r, 1)).astype(dtype)   # [C, d]
                s_scr[r] = s0 * total + jnp.dot(
                    vdT, pk, preferred_element_type=f32)
            side = lambda xs: (xs[0] if n == 1  # noqa: E731
                               else jnp.concatenate(xs, axis=1))
            return acc + nt(side(pqs), side(olds))

        acc = tiles(0, 1, jnp.zeros((G * C, dv), f32), True)
        acc = jax.lax.fori_loop(
            0, (T - 2) // group_tiles,
            lambda i, acc: tiles(1 + i * group_tiles, group_tiles, acc), acc)
        acc = tiles(T - 1, 1, acc, True)
        before = under(jnp.exp(cum))                             # [G C, 1]
        o = (num + before * acc) / (den + before * den0 + eps)
        for a in range(G):
            o_ref[0, :, a * dv:(a + 1) * dv] = o[a * C:(a + 1) * C].astype(
                o_ref.dtype)
        z_scr[...] = z0 * total + jnp.dot(kdT, k, preferred_element_type=f32)

    @pl.when(c == nc - 1)
    def _store():
        sout_ref[0, 0, 0] = s_scr[...]
        zout_ref[0, 0, 0] = z_scr[...]


def _prefill_kernel_of_rows(lidx_ref, pad_ref, rows_ref, *refs, **geometry):
    """``_prefill_kernel`` under a third prefetched vector (``rows``), which
    only the state's index_maps read."""
    del rows_ref
    _prefill_kernel(lidx_ref, pad_ref, *refs, **geometry)


def _tiles_a_product(T: int, most: int) -> int:
    """How many of the T - 2 whole-weight tiles one product of the prefill
    kernel takes: the largest divisor of their count within ``most``."""
    return max(n for n in range(1, max(most, 1) + 1) if (T - 2) % n == 0)


@functools.partial(jax.jit, static_argnames=(
    "chunk", "scale", "eps", "interpret"))
def retention_prefill_scan(q, k, v, gamma, S, Z, layer_idx, pad_lens,
                           rows=None, *, chunk: int, scale: float,
                           eps: float, interpret: bool = False):
    """The chunked scan over S tokens from layer ``layer_idx`` of the
    stacked state ``S`` [L, B, KV, T, dv, d] and normaliser ``Z`` [L, B, KV,
    d, d] (float32): q [B, S, H, d], k, v [B, S, KV, d] as the projections
    leave them, gamma [B, S, KV] float32. ``pad_lens`` [B]: the left-pad
    slots among these S (whole chunks of them are skipped). Returns (o
    [B, S, H, dv] in v's type, S, Z with the layer's blocks overwritten in
    place). Semantics: ``retention_chunked_xla``.

    ``rows`` [B] int32 (distinct): the rows are a piece of a state that
    holds more of them, and row b continues — and overwrites, in place —
    the state's batch row ``rows[b]``; no other row is fetched or written."""
    Bt, Sq, H, d = q.shape
    KV, dv = k.shape[2], v.shape[-1]
    G = H // KV
    f32 = jnp.float32
    q, k, v, gamma = _whole_chunks(
        chunk, q.reshape(Bt, Sq, H * d), k.reshape(Bt, Sq, KV * d),
        v.reshape(Bt, Sq, KV * dv), gamma.astype(f32))
    Sp = q.shape[1]
    nc = Sp // chunk
    # the running sum of gamma inside each chunk, float32 additions, by
    # columns [B, S, KV] and by rows [B, KV, S]
    cum = jnp.cumsum(gamma.reshape(Bt, nc, chunk, KV), axis=2).reshape(
        Bt, Sp, KV)
    cum_rows = cum.transpose(0, 2, 1)
    prefetch = 2 if rows is None else 3

    def first_live(b, c, pad):
        # a pad chunk parks on the row's first live one: no fetch of its own
        return jnp.minimum(jnp.maximum(c, pad[b] // chunk), nc - 1)

    head_block = lambda width: pl.BlockSpec(  # noqa: E731
        (1, chunk, width),
        lambda b, h, c, lidx, pad, *rows: (b, first_live(b, c, pad), h))
    state_block = lambda *tail: pl.BlockSpec(  # noqa: E731
        (1, 1, 1) + tail, lambda b, h, c, lidx, pad, *rows: (
            lidx[0], rows[0][b] if rows else b, h) + (0,) * len(tail))
    kernel = functools.partial(
        _prefill_kernel if rows is None else _prefill_kernel_of_rows,
        chunk=chunk, group=G, scale=scale, eps=eps,
        group_tiles=_tiles_a_product(S.shape[3], _GROUP_TILES))
    o, S, Z = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prefetch,
            grid=(Bt, KV, nc),
            in_specs=[
                head_block(G * d), head_block(d), head_block(dv),  # q, k, v
                pl.BlockSpec((1, chunk, KV), lambda b, h, c, lidx, pad, *rows:
                             (b, first_live(b, c, pad), 0)),
                pl.BlockSpec((1, KV, chunk), lambda b, h, c, lidx, pad, *rows:
                             (b, 0, first_live(b, c, pad))),
                state_block(*S.shape[3:]), state_block(*Z.shape[3:]),
            ],
            out_specs=[
                pl.BlockSpec((1, chunk, G * dv),
                             lambda b, h, c, *prefetched: (b, c, h)),
                state_block(*S.shape[3:]), state_block(*Z.shape[3:]),
            ],
            scratch_shapes=[pltpu.VMEM(S.shape[3:], f32),
                            pltpu.VMEM(Z.shape[3:], f32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bt, Sp, H * dv), v.dtype),
            jax.ShapeDtypeStruct(S.shape, f32),
            jax.ShapeDtypeStruct(Z.shape, f32),
        ],
        # the call's last two operands, after the prefetched scalars and the
        # five blocks before them, are the state and the normaliser
        input_output_aliases={prefetch + 5: 1, prefetch + 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        # a contract: the device trace and the benchmark's metrics name this
        # kernel by it
        name="retention_prefill_scan",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        pad_lens.astype(jnp.int32),
        *(() if rows is None else (rows.astype(jnp.int32),)),
        q, k, v, cum, cum_rows, S, Z,
    )
    return o[:, :Sq].reshape(Bt, Sq, H, dv), S, Z


def retention_tokens_computed(pad_lens, S: int, chunk: int) -> int:
    """Tokens of the chunks ``retention_prefill_scan`` does not skip, summed
    over rows, for one call over S tokens with ``pad_lens`` left-pad slots
    among them. Host arithmetic, the kernel's rule."""
    import numpy as np

    pads = np.minimum(np.asarray(pad_lens, np.int64), S)
    chunks = -(-S // chunk)
    return int(((chunks - pads // chunk) * chunk).sum())


# -- the decode kernel --------------------------------------------------------


def _decode_kernel(lidx_ref, q_ref, qcol_ref, k_ref, kcol_ref, vcol_ref,
                   g_ref, s_hbm, zin_ref, o_ref, s_out, zout_ref, held,
                   read_sem, write_sem, *, steps: int, group: int,
                   scale: float, eps: float, dtype):
    # q [1, 1, Gp, d] float32 rows (the KV head's G query heads, padded to
    # whole sublanes) and [1, 1, d, Gp] columns; k [1, 1, 1, d] a row and,
    # as v, [1, 1, d, 1] a column; g [1, 1, 1, 1] the decay; the normaliser
    # [1, 1, 1, d, d]; the output [1, 1, Gp, dv], a head a row. The stacked
    # state stays in HBM (``s_hbm`` and ``s_out`` are one buffer) and a grid
    # step's [T, dv, d] block of it stands in ``held`` [2, T, dv, d].
    # ``dtype``: the inputs' type, in which the read's products run
    # (float32 sums), as the prefill kernel's
    _, T, dv, d = held.shape
    f32 = jnp.float32
    KV = pl.num_programs(1)
    step = pl.program_id(0) * KV + pl.program_id(1)
    layer = lidx_ref[0]
    me = step % 2

    # The kernel moves the state itself, and NEVER a read beside a write: on
    # this chip a read alone runs at 743 GB/s and a write alone at 645, but
    # a block read while another is written — what the pipeline of
    # BlockSpecs does, at any block size, depth or order — moves 658 GB/s
    # of the two together: 13.1 us a block where the two in turn take 5.8 +
    # 6.7 (``PERF.md`` section 6, PR 61). So the transfers of a call are
    # one sequence, read(0) read(1) write(0) read(2) write(1) ..., each
    # started when the one before has ended; a step's arithmetic runs
    # beside the write of the step before it.
    def read(n):
        return pltpu.make_async_copy(
            s_hbm.at[layer, n // KV, n % KV], held.at[n % 2],
            read_sem.at[n % 2])

    def write(n):
        return pltpu.make_async_copy(
            held.at[n % 2], s_out.at[layer, n // KV, n % KV],
            write_sem.at[n % 2])

    @pl.when(step == 0)
    def _first():
        for n in range(min(2, steps)):   # no write to wait for yet
            read(n).start()

    read(step).wait()

    @pl.when(step >= 1)
    def _write_the_last():
        write(step - 1).start()

    g = g_ref[0, 0]                                              # [1, 1]
    vcol = vcol_ref[0, 0]                                        # [dv, 1]
    rows = q_ref.shape[2]
    K = jnp.broadcast_to(k_ref[0, 0], (rows, d))
    Qs = q_ref[0, 0] * (scale * 2.0 ** 0.5)

    vfull = jnp.broadcast_to(vcol, (dv, d))
    # every tile unrolled, ``_DECODE_GROUP_TILES`` of them a product: decay
    # and rank-one write of each tile on the vector unit, float32, where it
    # stands; then the query heads' read of the NEW tiles, all heads and
    # the group's tiles ONE product — phi_q's tiles [Gp, n d] side by side
    # against the tiles' lanes side by side. (A product a tile made the
    # loop wait out the matrix unit's latency 65 times a grid step: three
    # times the state's transfer.)
    num = jnp.zeros((rows, dv), f32)
    for first in range(0, T, _DECODE_GROUP_TILES):
        pqs, news = [], []
        for r in range(first, min(first + _DECODE_GROUP_TILES, T)):
            pk = (K * pltpu.roll(K, r, 1))[0:1, :] if r else K[0:1] * K[0:1]
            new = held[me, r] * g + vfull * pk                   # [dv, d]
            held[me, r] = new
            pq = Qs * (pltpu.roll(Qs, r, 1) if r else Qs)
            if r in (0, T - 1):
                pq = pq * 0.5
            pqs.append(pq.astype(dtype))
            news.append(new.astype(dtype))
        num = num + jax.lax.dot_general(
            jnp.concatenate(pqs, axis=1), jnp.concatenate(news, axis=1),
            (((1,), (1,)), ((), ())), preferred_element_type=f32)
    z = zin_ref[0, 0, 0] * g + kcol_ref[0, 0] * k_ref[0, 0]
    zout_ref[0, 0, 0] = z
    den = jnp.concatenate([
        jnp.sum(jnp.sum(z * qcol_ref[0, 0, :, a:a + 1] * q_ref[0, 0, a:a + 1],
                        axis=1, keepdims=True), axis=0, keepdims=True)
        for a in range(group)] + [jnp.ones((rows - group, 1), f32)], axis=0)
    o_ref[0, 0] = num / ((scale * scale) * den + eps)

    @pl.when(step >= 1)
    def _read_the_next():
        write(step - 1).wait()

        @pl.when(step + 1 < steps)
        def _():
            read(step + 1).start()       # into the block just written

    @pl.when(step == steps - 1)
    def _write_mine():
        write(step).start()
        write(step).wait()


@functools.partial(jax.jit, static_argnames=("scale", "eps", "interpret"))
def retention_decode_update(q, k, v, gamma, S, Z, layer_idx, *, scale: float,
                            eps: float, interpret: bool = False):
    """One token for every row: q [B, H, d], k, v [B, KV, d], gamma [B, KV]
    float32, the stacked ``S`` [L, B, KV, T, dv, d] and ``Z`` [L, B, KV, d,
    d] float32, whose layer ``layer_idx`` is read and overwritten in place,
    once, by the kernel's own copies (a row's and KV head's block a grid
    step, two blocks in VMEM): decay and the rank-one write in float32 on
    the vector unit, then the KV head's query heads' read of the new state,
    once, tile by tile on the matrix unit in the inputs' type with float32
    sums. Returns (o [B, H, dv] float32, S, Z). Semantics:
    ``retention_step_xla``."""
    Bt, H, d = q.shape
    KV, dv = k.shape[1], v.shape[-1]
    G = H // KV
    Gp = -(-G // 8) * 8
    f32, dtype = jnp.float32, q.dtype
    q = jnp.pad(q.astype(f32).reshape(Bt, KV, G, d),
                ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    k = k.astype(f32)
    at = lambda *tail: pl.BlockSpec(  # noqa: E731
        (1, 1) + tail, lambda b, h, lidx: (b, h) + (0,) * len(tail))
    stays_in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    norm_block = pl.BlockSpec(
        (1, 1, 1) + Z.shape[3:], lambda b, h, lidx: (lidx[0], b, h, 0, 0))
    o, S, Z = pl.pallas_call(
        functools.partial(_decode_kernel, steps=Bt * KV, group=G,
                          scale=scale, eps=eps, dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Bt, KV),
            in_specs=[at(Gp, d), at(d, Gp), at(1, d), at(d, 1), at(dv, 1),
                      at(1, 1), stays_in_hbm, norm_block],
            out_specs=[at(Gp, dv), stays_in_hbm, norm_block],
            scratch_shapes=[pltpu.VMEM((2,) + S.shape[3:], f32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bt, KV, Gp, dv), f32),
            jax.ShapeDtypeStruct(S.shape, f32),
            jax.ShapeDtypeStruct(Z.shape, f32),
        ],
        # operands 7 and 8 of the call (the prefetched scalar first) are the
        # state and the normaliser
        input_output_aliases={7: 1, 8: 2},
        # the transfers are one sequence over the steps: no step may run
        # beside another
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="retention_decode_update",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        q, q.swapaxes(2, 3), k[:, :, None, :], k[..., None],
        v.astype(f32)[..., None], jnp.exp(gamma.astype(f32))[..., None, None],
        S, Z,
    )
    return o[:, :, :G].reshape(Bt, H, dv), S, Z
