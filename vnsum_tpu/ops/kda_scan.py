"""The gated delta rule of Kimi Delta Attention (KDA) for the one-shot
program: a chunked prefill scan and a one-token state update, each as its XLA
form and as a Pallas TPU kernel.

The recurrence, per row and head (``d_k`` key channels, ``d_v`` value
channels, a MATRIX state ``S [d_k, d_v]`` in float32):

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``g_t [d_k] <= 0`` a log-decay a key CHANNEL (not a scalar a head: what
``ops/ssd_scan.py`` scans), ``beta_t`` in (0, 1) a head, ``k_t`` of unit
length and ``q_t`` scaled by the caller. Every token the state is decayed
channel by channel, the value it would read at ``k_t`` is erased by
``beta_t`` and ``beta_t v_t`` written there: the delta rule. With ``u_t =
beta_t (v_t - (diag(exp(g_t)) S_{t-1})^T k_t)`` the same step reads ``S_t =
diag(exp(g_t)) S_{t-1} + k_t u_t^T``. A position whose ``k``, ``v`` and
``beta`` are zero leaves a zero state zero whatever ``g`` reads there, so a
state stays exactly zero through a row's left pad (``models/ling.py`` zeroes
them under the pad).

**The state's layout** is ``[L, B, H, d_v, d_k]`` — ``S`` TRANSPOSED, the
value channel on the sublanes and the key channel on the lanes. Everything a
step scales the state by (the decay, ``k``, ``beta k``, ``q``) varies along
the key channel, so it is a lane row of the ``[B, H, d_k]`` arrays the layer
already has; ``beta v`` alone varies along the sublanes and comes in as a
``[d_v, 1]`` column, and a head's output leaves as one. The decode update is
then element-wise products and two lane sums a head, in float32 with no
matrix product, and the prefill kernel's state products are the plain forms
the matrix unit takes (``x S`` contracts the lanes of both; the update
``U^T K`` the sublanes of both). Both kernels take the whole stacked state
with the layer's index as a prefetched scalar and write the layer's block
back **in place** (``input_output_aliases``): the decode loop's carry does
not copy it. The prefill kernel also takes a ROW PIECE: ``rows`` names, for
each row of its inputs, the batch row of the state it continues (a third
prefetched vector, which steers the state's index_map alone).

**The chunked form** (``kda_prefill_scan``, ``kda_chunked_xla``; ``chunk`` =
the config's ``kda_chunk_size``) is how the recurrence is computed, not
another model. With ``G_t`` the running sum of ``g`` inside a chunk
(``Gamma_t = exp(G_t)``), ``S_0`` the state entering it, and row ``t`` of
``Q, K, V, U`` the token's vectors:

    A_ti = sum_c beta_t k_t[c] k_i[c] exp(G_t[c] - G_i[c])      i <  t
    B_ti = sum_c      q_t[c] k_i[c] exp(G_t[c] - G_i[c])        i <= t
    T    = (I + A)^-1                    unit lower triangular (the WY form)
    U    = T (beta V) - T (beta K * Gamma) S_0
    O    = (Q * Gamma) S_0 + B U
    S_C  = diag(Gamma_C) S_0 + (K * exp(G_C - G))^T U

**Decays only ever as differences.** At the gate's bound of -5 a token,
``exp(-G)`` alone overflows float32 within 18 tokens, so no factor
``exp(-G_i)`` is ever formed: the kernel splits a chunk into sub-blocks of
16 tokens and, for the rows of sub-block I, measures every exponent from
``G`` at the sub-block's first row — ``exp(G_t - ref_I) <= 1`` on the row
side, ``exp(ref_I - G_i)`` on the column side, which is ``<= 1`` for every
earlier sub-block and at most ``exp(75)`` inside the sub-block itself
(columns after the row's own are masked, their exponent clamped). ``T`` is
built from the sub-blocks' own inverses (a product of ``I + N^(2^j)``, ``N``
nilpotent of order 16) merged pair by pair (``[[P, 0], [C, Q]]^-1 = [[P^-1,
0], [-Q^-1 C P^-1, Q^-1]]``), in float32 at the highest precision: it is an
inverse, and rounding it to the inputs' type would be rounding every token's
erase. The other products run in the inputs' type with float32 sums. The
XLA form computes the same sums directly (every difference before its
exponential, a triangular solve).

Grid (rows, heads, token blocks), the blocks in sequence with the head's
state in VMEM scratch; a head is one lane tile of the ``[B, S, H * 128]``
arrays the layer has, so nothing is transposed for the kernel — and
nothing is computed for it either: the kernel takes ``q, k, v`` and the
gate's projection ``a`` as the projections and the convolution leave them
(the inputs' type), ``beta`` as ``[B, S, H]`` float32 (a block holds every
head's column and the kernel picks its own) and a head's ``exp(A_log)`` and
``dt_bias`` as a ``[2, 128]`` block, and makes in VMEM, a group of chunks at
a time, what XLA once wrote to HBM as whole ``[B, S, H * 128]`` arrays: the
log-decay ``g = lower_bound * sigmoid(exp(A_log) * (a + dt_bias))``
(``kda_gate``) in float32, its running sum ``G`` inside each chunk in
float32 ADDITIONS (a product with a triangle on the matrix unit would round
it: ``log2(chunk)`` steps of rows rolled down the sublanes by 1, 2, 4, ...
and added where the shift stays inside the chunk; the sum nearest the
float64 one of the three orders tried), and ``beta k``, ``beta v`` as
float32 products rounded to the inputs' type. A grid step runs its block in
two phases. The first reads no state: those, and ``A``, ``B``, ``T``, ``T
(beta V)``, ``T (beta K * Gamma)``, ``Q * Gamma`` and ``K * exp(G_C - G)``
hang on ``q, k, v, beta, a`` alone, so ``_GROUP_CHUNKS`` chunks are computed
at a time, as the diagonal blocks of one operand — a chunk is one more
level of the masks the inverse already has — and left in VMEM scratch
(with each chunk's last row of ``G`` for the state's decay). The inverse's
factors are block-diagonal
(sub-blocks, then pairs of them): as a LEFT factor such a matrix is folded
to one block's rows, every block in lanes of its own, so a product pushes 16
or 32 rows through the matrix unit where the operand has 256 — the same
sums, the zeros left out. The second phase is the recurrence, chunk by
chunk: the four products with the state (``U``, ``O``'s two, ``S_C``).
A token block wholly under the row's left pad is not fetched, a group of
chunks wholly under it not computed, and a chunk wholly under it enters
nothing into the state: its output is written as zeros and the state passes
(``kda_tokens_computed`` counts the groups computed, on the host).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# [B, S, ...] arrays padded with zeros at the END of S to whole chunks: a
# position with k, v, beta and g zero neither decays nor writes
from .ssd_scan import _whole_chunks

VMEM_LIMIT_BYTES = 48 * 1024 * 1024
# tokens of a sub-block: exponents inside one reach 15 tokens x 5 = 75, and
# exp(75) = 3.7e32 is a float32 (and a bfloat16)
_SUB = 16
# what a masked column's exponent is clamped to (exp(80) = 5.5e34: 128 of
# them still sum inside float32)
_CAP = 80.0
# tokens one grid step of the prefill kernel holds (whole chunks)
_BLOCK_TOKENS = 1024
# chunks whose state-free half the prefill kernel computes as ONE
# block-diagonal operand, and skips together (on the chip at chunks of 64, a
# call of 4 x 2,048 tokens x 32 heads: 2, 4, 8 a group take 5.0, 4.2, 4.9 ms)
_GROUP_CHUNKS = 4
_HIGHEST = jax.lax.Precision.HIGHEST


def _sub_block(chunk: int) -> int:
    sub = min(_SUB, chunk)
    n = chunk // sub
    if chunk % sub or n & (n - 1) or sub & (sub - 1):
        raise ValueError(
            f"a chunk of {chunk} tokens is no power-of-two count of "
            f"sub-blocks of {sub} (itself a power of two)")
    return sub


# -- XLA forms ----------------------------------------------------------------


def kda_step_xla(q, k, v, g, beta, state):
    """One token of the recurrence: q, k, g [B, H, dk] (g float32), v
    [B, H, dv], beta [B, H], state [B, H, dv, dk] float32 (S transposed) ->
    (o [B, H, dv] float32, state). Sums, not products on the matrix unit:
    float32 whatever the platform's default precision."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    beta = beta.astype(f32)[..., None]
    decayed = state * jnp.exp(g.astype(f32))[:, :, None, :]
    u = beta * v - jnp.sum(decayed * (beta * k)[:, :, None, :], axis=-1)
    state = decayed + u[..., None] * k[:, :, None, :]
    return jnp.sum(state * q[:, :, None, :], axis=-1), state


def kda_recurrent_xla(q, k, v, g, beta, state):
    """The recurrence token by token (``kda_step_xla`` under a scan): q, k,
    g [B, S, H, dk], v [B, S, H, dv], beta [B, S, H], state [B, H, dv, dk]
    -> (o [B, S, H, dv] float32, state). What the chunked forms compute."""
    def step(state, xs):
        o, state = kda_step_xla(*xs, state)
        return state, o

    state, o = jax.lax.scan(
        step, state, tuple(a.swapaxes(0, 1) for a in (q, k, v, g, beta)))
    return o.swapaxes(0, 1), state


def kda_chunked_xla(q, k, v, g, beta, state, chunk: int, rows=None):
    """The chunked scan in plain XLA: q, k [B, S, H, dk], v [B, S, H, dv],
    g [B, S, H, dk] float32, beta [B, S, H], state [B, H, dv, dk] float32 ->
    (o [B, S, H, dv] in v's type, the state after the S tokens). S is
    padded at its END to whole chunks. ``rows`` [B] int32: the rows are a
    piece of a ``state`` that holds more of them, row b continuing
    ``state[rows[b]]``; those rows of the state are returned rewritten, its
    others as they came."""
    if rows is not None:
        o, piece = kda_chunked_xla(q, k, v, g, beta, state[rows], chunk)
        return o, state.at[rows].set(piece)
    Bt, S, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    q, k, v, g, beta = _whole_chunks(
        chunk, q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
        beta.astype(f32))
    nc = q.shape[1] // chunk

    def chunks(a):   # [B, nc * C, H, ...] -> [nc, B, H, C, ...]
        a = a.reshape((Bt, nc, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    qc, kc, vc, gc = chunks(q), chunks(k), chunks(v), chunks(g)
    bc = chunks(beta[..., None])
    G = jnp.cumsum(gc, axis=3)                             # [nc, B, H, C, dk]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    eye = jnp.eye(chunk, dtype=f32)

    def step(St, xs):
        q, k, v, G, beta = xs                              # [B, H, C, *]
        kb, vb = k * beta, v * beta
        # every difference before its exponential: [B, H, t, i, dk]
        decay = jnp.exp(jnp.where(
            tri[:, :, None], G[:, :, :, None, :] - G[:, :, None, :, :],
            -jnp.inf))
        A = jnp.sum(kb[:, :, :, None, :] * k[:, :, None, :, :] * decay, -1)
        Bm = jnp.sum(q[:, :, :, None, :] * k[:, :, None, :, :] * decay, -1)
        A = jnp.where(jnp.tril(tri, -1), A, 0.0)
        gam = jnp.exp(G)
        S0 = St.swapaxes(-1, -2)                           # [B, H, dk, dv]
        rhs = vb - jnp.einsum("bhtc,bhcv->bhtv", kb * gam, S0,
                              precision=_HIGHEST)
        U = jax.scipy.linalg.solve_triangular(
            eye + A, rhs, lower=True, unit_diagonal=True)
        o = jnp.einsum("bhtc,bhcv->bhtv", q * gam, S0, precision=_HIGHEST) \
            + jnp.einsum("bhti,bhiv->bhtv", Bm, U, precision=_HIGHEST)
        last = G[:, :, -1:, :]
        St = St * jnp.exp(last) + jnp.einsum(
            "bhtv,bhtc->bhvc", U, k * jnp.exp(last - G), precision=_HIGHEST)
        return St, o

    state, o = jax.lax.scan(step, state.astype(f32), (qc, kc, vc, G, bc))
    # [nc, B, H, C, dv] -> [B, S, H, dv]
    o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(Bt, nc * chunk, H, dv)
    return o[:, :S].astype(v.dtype), state


# -- the prefill kernel -------------------------------------------------------


def _prefill_kernel(lidx_ref, pad_ref, q_ref, k_ref, v_ref, a_ref, beta_ref,
                    gate_ref, sin_ref, o_ref, sout_ref, s_scr, u_scr, w_scr,
                    b_scr, qg_scr, ke_scr, gc_scr, *, chunk: int, sub: int,
                    block: int, seq: int, lower_bound: float):
    # q/k/v/a/o [1, block, d] (one head's lanes; a the gate's projection),
    # beta [1, block, H] float32 (a column a head), gate [2, dk] float32
    # (the head's exp(A_log) on every lane, over its dt_bias); the state
    # [1, 1, 1, dv, dk]; the scratch between the phases, a row a token of
    # the block: U0 = T (beta V) [block, dv] float32, and in the inputs'
    # type W = T (beta K * Gamma) and Q * Gamma and K * exp(G_C - G)
    # [block, dk], B [block, chunk]; and a row a chunk, G_C [.., dk] float32
    b, h, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nt = pl.num_programs(2)
    C, per = chunk, chunk // sub
    f32, dtype = jnp.float32, q_ref.dtype
    dk, dv = q_ref.shape[-1], v_ref.shape[-1]

    @pl.when(t == 0)
    def _load():
        s_scr[...] = sin_ref[0, 0, 0]

    def rhs_t(a, b):     # a [m, d] . b [n, d]^T
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)

    def stacked(blocks):  # rows, one block under the other
        return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, 0)

    def exact(a, b):     # float32 a @ b, not rounded to the inputs' type
        return jnp.dot(a, b, preferred_element_type=f32, precision=_HIGHEST)

    def live(first, size):
        # chunks [first, first + size) wholly under the row's left pad:
        # nothing of them enters the state
        return t * block + (first + size) * C > pad_ref[b]

    def free_of_state(first, size: int):
        """What chunks [first, first + size) need of q, k, v, beta and the
        gate alone, into scratch. The chunks are the diagonal blocks of ONE
        operand of ``n = size * C`` rows: a chunk is one more level of
        ``same``, and each product below is one for all of them."""
        n = size * C
        at = pl.ds(pl.multiple_of(first * C, C), n)
        row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)

        def same(width: int):
            return (row // width) == (col // width)

        def of_each(rows, height: int):  # a [1, dk] row a block of `height`
            return stacked([jnp.broadcast_to(r, (height, r.shape[1]))
                            for r in rows])

        def folded(full, height: int, every: int = 1):
            # a block-diagonal [n, n] as [height, n]: the sum of its row
            # slabs of `height` rows (with `every` 2, each pair's second
            # alone), whose non-zeros lie in lanes of their own. As a left
            # factor it gives in `height` rows what the n rows would, their
            # zeros aside
            return functools.reduce(jnp.add, [
                full[i * height:(i + 1) * height]
                for i in range(every - 1, n // height, every)])

        def unfolded(rows, keep):
            # ... and back to [n, n]: a copy a row slab, under the mask of
            # the blocks it came from
            return jnp.where(
                keep, stacked([rows] * (n // rows.shape[0])), 0.0)

        token = jax.lax.broadcasted_iota(jnp.int32, (n, dk), 0)
        # the log-decay, in float32: lower_bound sigmoid(exp(A_log) (a +
        # dt_bias)), and none at the zeros a ragged call ends in (they must
        # not decay the state)
        g = lower_bound * jax.nn.sigmoid(gate_ref[0:1, :] * (
            a_ref[0, at, :].astype(f32) + gate_ref[1:2, :]))
        if seq % C:
            g = jnp.where(t * block + first * C + token < seq, g, 0.0)
        # G, its running sum inside each chunk: float32 additions (a product
        # with a triangle on the matrix unit would round them), log2(C)
        # steps of the rows shifted down by 1, 2, 4, ... and added where the
        # shift stays inside the chunk
        G, shift = g, 1
        while shift < C:
            G = G + jnp.where(token % C >= shift, pltpu.roll(G, shift, 0),
                              0.0)
            shift *= 2
        q = q_ref[0, at, :].astype(f32)
        k = k_ref[0, at, :].astype(f32)
        # the head's column of beta, then beta k and beta v as the layer's
        # XLA made them: a float32 product rounded to the inputs' type
        lane = jax.lax.broadcasted_iota(jnp.int32, (n, beta_ref.shape[-1]), 1)
        beta = jnp.sum(jnp.where(lane == h, beta_ref[0, at, :], 0.0),
                       axis=1, keepdims=True)                        # [n, 1]
        kb = (k * beta).astype(dtype).astype(f32)
        vb = (v_ref[0, at, :].astype(f32) * beta).astype(dtype)
        firsts = [G[i * sub:i * sub + 1] for i in range(size * per)]
        to_ref = jnp.exp(G - of_each(firsts, sub))               # <= 1
        kb_rows = (kb * to_ref).astype(dtype)
        q_rows = (q * to_ref).astype(dtype)
        a_rows = [None] * (size * per)
        b_rows = [None] * (size * per)
        for i in range(per):
            # sub-block i of EVERY chunk: each chunk's columns as its own
            # sub-block i's rows see them (exponents from that sub-block's
            # first row), under those sub-blocks' beta k and q rows together
            mine = [j * per + i for j in range(size)]
            cols = (k * jnp.exp(jnp.minimum(
                of_each([firsts[s] for s in mine], C) - G, _CAP))
                    ).astype(dtype)
            left = stacked([x[s * sub:(s + 1) * sub] for s in mine
                            for x in (kb_rows, q_rows)])
            both = rhs_t(left, cols)                     # [size * 2 sub, n]
            for j, s in enumerate(mine):
                a_rows[s] = both[2 * j * sub:(2 * j + 1) * sub]
                b_rows[s] = both[(2 * j + 1) * sub:(2 * j + 2) * sub]
        A = jnp.where(same(C) & (row > col), stacked(a_rows), 0.0)
        Bm = jnp.where(same(C) & (row >= col), stacked(b_rows), 0.0)
        # T = (I + A)^-1: the sub-blocks' own inverses, (I + N)(I + N^2)
        # (I + N^4) ... with N = -A inside a sub-block, nilpotent. P and T
        # are block-diagonal, so each is folded to `sub` rows as a left
        # factor; P^2 and T P have the same right factor and are one product
        blocks = same(sub)
        N = -jnp.where(blocks, A, 0.0)
        P = folded(N, sub)
        T = folded((row == col).astype(f32), sub) + P
        steps = max(sub.bit_length() - 2, 0)
        for step in range(steps):
            if step == 0:
                P = exact(P, N)
            if step < steps - 1:
                both = exact(stacked([T, P]), unfolded(P, blocks))
                T, P = T + both[:sub], both[sub:]
            else:
                T = T + exact(T, unfolded(P, blocks))
        T = unfolded(T, blocks)
        # ... merged pair by pair, up to a chunk: [[P, 0], [C, Q]]^-1 has
        # -Q^-1 C P^-1 in its corner, whose rows are the pairs' second
        # blocks' alone
        width = sub
        while width < C:
            corner = same(2 * width) & ~same(width) & (row > col)
            T = T - unfolded(exact(exact(
                folded(T, width, 2), jnp.where(corner, A, 0.0)), T), corner)
            width *= 2
        gam = jnp.exp(G)
        UW = jnp.dot(T.astype(dtype), jnp.concatenate(
            [vb, (kb * gam).astype(dtype)], axis=1),
            preferred_element_type=f32)                     # [n, dv + dk]
        u_scr[at, :] = UW[:, :dv]
        w_scr[at, :] = UW[:, dv:].astype(dtype)
        for j in range(size):
            mine = slice(j * C, (j + 1) * C)
            b_scr[pl.ds(pl.multiple_of((first + j) * C, C), C), :] = \
                Bm[mine, mine].astype(dtype)
        qg_scr[at, :] = (q * gam).astype(dtype)
        lasts = [G[(j + 1) * C - 1:(j + 1) * C] for j in range(size)]
        ke_scr[at, :] = (k * jnp.exp(of_each(lasts, C) - G)).astype(dtype)
        for j in range(size):
            gc_scr[pl.ds(first + j, 1), :] = lasts[j]

    def one_group(first, size: int):
        pl.when(live(first, size))(lambda: free_of_state(first, size))

    def with_state(c, _):
        at = pl.ds(pl.multiple_of(c * C, C), C)
        enters = live(c, 1)

        @pl.when(jnp.logical_not(enters))
        def _skip():
            o_ref[0, at, :] = jnp.zeros((C, o_ref.shape[-1]), o_ref.dtype)

        @pl.when(enters)
        def _compute():
            St = s_scr[...]                                      # [dv, dk]
            Sd = St.astype(dtype)
            U = u_scr[at, :] - rhs_t(w_scr[at, :], Sd)           # [C, dv]
            Ud = U.astype(dtype)
            o = rhs_t(qg_scr[at, :], Sd) + jnp.dot(
                b_scr[at, :], Ud, preferred_element_type=f32)
            o_ref[0, at, :] = o.astype(o_ref.dtype)
            last = gc_scr[pl.ds(c, 1), :]                        # [1, dk]
            s_scr[...] = St * jnp.exp(last) + jax.lax.dot_general(
                Ud, ke_scr[at, :], (((0,), (0,)), ((), ())),
                preferred_element_type=f32)

    # phase 1, which reads no state: a group of chunks a step (the last
    # group of a block what the block has left)
    n_chunks = block // C
    group = min(_GROUP_CHUNKS, n_chunks)
    jax.lax.fori_loop(0, n_chunks // group,
                      lambda i, _: one_group(i * group, group), None)
    if n_chunks % group:
        one_group(n_chunks - n_chunks % group, n_chunks % group)
    # phase 2, the recurrence: four products with the state a chunk
    jax.lax.fori_loop(0, n_chunks, with_state, None)

    @pl.when(t == nt - 1)
    def _store():
        sout_ref[0, 0, 0] = s_scr[...]


def _prefill_kernel_of_rows(lidx_ref, pad_ref, rows_ref, *refs, **geometry):
    """``_prefill_kernel`` under a third prefetched vector (``rows``), which
    only the state's index_map reads."""
    del rows_ref
    _prefill_kernel(lidx_ref, pad_ref, *refs, **geometry)


def _block_tokens(n_chunks: int, chunk: int) -> int:
    """Tokens a grid step holds: as many whole chunks as divide the call's
    and stay within ``_BLOCK_TOKENS``."""
    m = max(d for d in range(1, n_chunks + 1)
            if n_chunks % d == 0 and d * chunk <= max(_BLOCK_TOKENS, chunk))
    return m * chunk


def kda_gate(a, A_log, dt_bias, lower_bound: float):
    """The log-decay ``g`` [..., H, dk] float32 of the gate's projection
    ``a`` [..., H, dk], a head's ``A_log`` [H] and a channel's ``dt_bias``
    [H, dk]: ``lower_bound * sigmoid(exp(A_log) * (a + dt_bias))``, in
    (lower_bound, 0). The XLA form of what ``kda_prefill_scan`` computes in
    its kernel."""
    f32 = jnp.float32
    return lower_bound * jax.nn.sigmoid(
        jnp.exp(A_log.astype(f32))[:, None] * (a.astype(f32) + dt_bias))


@functools.partial(jax.jit, static_argnames=(
    "lower_bound", "chunk", "interpret"))
def kda_prefill_scan(q, k, v, a, beta, state, layer_idx, pad_lens, rows=None,
                     *, A_log, dt_bias, lower_bound: float, chunk: int,
                     interpret: bool = False):
    """The chunked scan over S tokens from layer ``layer_idx``'s state of
    the stacked ``state`` [L, B, H, dv, dk] float32, on what the layer's
    projections and convolution hand over: q, k [B, S, H, dk], v [B, S, H,
    dv], beta [B, S, H], and in ``g``'s place the gate's projection ``a``
    [B, S, H, dk] (in the inputs' type) with ``A_log`` [H], ``dt_bias`` [H,
    dk] and the bound — ``g = kda_gate(a, A_log, dt_bias, lower_bound)``,
    which the kernel computes a head's tile at a time in float32, as it
    does ``g``'s running sum inside each chunk (float32 additions), ``beta
    k`` and ``beta v`` (float32 products rounded to the inputs' type).
    Around the kernel the arrays are reshaped and padded to whole chunks:
    nothing of their size is computed or written outside it. ``pad_lens``
    [B]: the left-pad slots among these S (whole chunks of them are
    skipped). Returns (o [B, S, H, dv] in v's type, the stacked state with
    the layer's block overwritten in place). Semantics: ``kda_chunked_xla``
    on ``kda_gate``'s ``g``.

    ``rows`` [B] int32 (distinct): the rows are a piece of a state that
    holds more of them, and row b continues — and overwrites, in place —
    the state's batch row ``rows[b]``; no other row is fetched or written."""
    Bt, S, H, dk = q.shape
    dv = v.shape[-1]
    sub = _sub_block(chunk)
    f32 = jnp.float32
    q, k, v, a, beta = _whole_chunks(
        chunk, q.reshape(Bt, S, H * dk), k.reshape(Bt, S, H * dk),
        v.reshape(Bt, S, H * dv), a.reshape(Bt, S, H * dk), beta.astype(f32))
    Sp = q.shape[1]
    block = _block_tokens(Sp // chunk, chunk)
    nt = Sp // block
    # exp(A_log) a head on its channels' lanes, over dt_bias: [2, H * dk]
    gate = jnp.stack([jnp.repeat(jnp.exp(A_log.astype(f32)), dk),
                      dt_bias.astype(f32).reshape(H * dk)])
    prefetch = 2 if rows is None else 3

    def first_live(b, t, pad):
        # a pad block parks on the row's first live one: no fetch of its own
        return jnp.minimum(jnp.maximum(t, pad[b] // block), nt - 1)

    head_block = lambda width: pl.BlockSpec(  # noqa: E731
        (1, block, width),
        lambda b, h, t, lidx, pad, *rows: (b, first_live(b, t, pad), h))
    state_block = pl.BlockSpec(
        (1, 1, 1, dv, dk), lambda b, h, t, lidx, pad, *rows: (
            lidx[0], rows[0][b] if rows else b, h, 0, 0))
    kernel = functools.partial(
        _prefill_kernel if rows is None else _prefill_kernel_of_rows,
        chunk=chunk, sub=sub, block=block, seq=S, lower_bound=lower_bound)
    o, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prefetch,
            grid=(Bt, H, nt),
            in_specs=[
                head_block(dk), head_block(dk), head_block(dv),   # q, k, v
                head_block(dk),                                   # a
                # beta: every head's column of the block (a head's alone
                # would be a block one lane wide)
                pl.BlockSpec((1, block, H), lambda b, h, t, lidx, pad, *rows:
                             (b, first_live(b, t, pad), 0)),
                pl.BlockSpec((2, dk), lambda b, h, t, *prefetched: (0, h)),
                state_block,
            ],
            out_specs=[
                pl.BlockSpec((1, block, dv),
                             lambda b, h, t, *prefetched: (b, t, h)),
                state_block,
            ],
            scratch_shapes=[
                pltpu.VMEM((dv, dk), f32),          # the head's state
                pltpu.VMEM((block, dv), f32),       # T (beta V)
                pltpu.VMEM((block, dk), q.dtype),   # T (beta K * Gamma)
                pltpu.VMEM((block, chunk), q.dtype),  # B
                pltpu.VMEM((block, dk), q.dtype),   # Q * Gamma
                pltpu.VMEM((block, dk), q.dtype),   # K * exp(G_C - G)
                pltpu.VMEM((block // chunk, dk), f32),  # G_C
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bt, Sp, H * dv), v.dtype),
            jax.ShapeDtypeStruct(state.shape, f32),
        ],
        # the call's last operand, after the prefetched scalars and the six
        # blocks before it, is the state
        input_output_aliases={prefetch + 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        # a contract: the device trace and the benchmark's metrics name this
        # kernel by it
        name="kda_prefill_scan",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        pad_lens.astype(jnp.int32),
        *(() if rows is None else (rows.astype(jnp.int32),)),
        q, k, v, a, beta, gate, state,
    )
    return o[:, :S].reshape(Bt, S, H, dv), state


def kda_tokens_computed(pad_lens, S: int, chunk: int) -> int:
    """Tokens of the chunks whose state-free half ``kda_prefill_scan`` does
    not skip, summed over rows, for one call over S tokens with ``pad_lens``
    left-pad slots among them: the groups of ``_GROUP_CHUNKS`` chunks (a
    block's last group what the block has left) that do not lie wholly
    under a row's pad. Host arithmetic, the kernel's rule."""
    import numpy as np

    pads = np.minimum(np.asarray(pad_lens, np.int64), S)
    chunks = -(-S // chunk)
    per_block = _block_tokens(chunks, chunk) // chunk
    group = min(_GROUP_CHUNKS, per_block)
    # the chunk each group ends before: a block's, then the call's
    ends = np.minimum(np.arange(group, per_block + group, group), per_block)
    ends = (np.arange(0, chunks, per_block)[:, None] + ends).ravel()
    computed = ends[None, :] * chunk > pads[:, None]
    return int((computed * np.diff(ends, prepend=0)).sum()) * chunk


# -- the decode kernel --------------------------------------------------------


def _decode_kernel(lidx_ref, q_ref, k_ref, kb_ref, decay_ref, vbt_ref,
                   sin_ref, ot_ref, sout_ref, *, n_heads: int):
    # q/k/kb/decay [1, H, dk] float32 rows; vbt/ot [1, dv, H]: a head's
    # beta v and its output as columns; the state [1, 1, H, dv, dk]
    for h in range(n_heads):
        row = slice(h, h + 1)
        decayed = sin_ref[0, 0, h] * decay_ref[0, row, :]        # [dv, dk]
        u = vbt_ref[0, :, row] - jnp.sum(
            decayed * kb_ref[0, row, :], axis=1, keepdims=True)  # [dv, 1]
        new = decayed + u * k_ref[0, row, :]
        sout_ref[0, 0, h] = new
        ot_ref[0, :, row] = jnp.sum(new * q_ref[0, row, :], axis=1,
                                    keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_update(q, k, v, g, beta, state, layer_idx, *,
                      interpret: bool = False):
    """One token for every row: q, k, g [B, H, dk], v [B, H, dv], beta
    [B, H], the stacked ``state`` [L, B, H, dv, dk] float32, whose layer
    ``layer_idx`` is read and overwritten in place. Returns (o [B, H, dv]
    float32, the stacked state). Semantics: ``kda_step_xla``."""
    Bt, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    beta = beta.astype(f32)[..., None]
    k = k.astype(f32)
    row = pl.BlockSpec((1, H, dk), lambda b, lidx: (b, 0, 0))
    col = pl.BlockSpec((1, dv, H), lambda b, lidx: (b, 0, 0))
    state_block = pl.BlockSpec(
        (1, 1, H, dv, dk), lambda b, lidx: (lidx[0], b, 0, 0, 0))
    ot, state = pl.pallas_call(
        functools.partial(_decode_kernel, n_heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Bt,),
            in_specs=[row, row, row, row, col, state_block],
            out_specs=[col, state_block],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Bt, dv, H), f32),
            jax.ShapeDtypeStruct(state.shape, f32),
        ],
        # operand 6 of the call (the prefetched scalar first) is the state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="kda_decode_update",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        q.astype(f32), k, k * beta, jnp.exp(g.astype(f32)),
        (v.astype(f32) * beta).swapaxes(1, 2), state,
    )
    return ot.swapaxes(1, 2), state
