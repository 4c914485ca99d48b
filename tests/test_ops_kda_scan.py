"""ops/kda_scan.py on the CPU: the chunked forms (XLA, and the kernel
interpreted) against the token-by-token recurrence, the one-token update,
gates down to the bound, pads, row pieces and the host's count. The kernel
takes what the layer hands it — the gate's projection ``a`` with ``A_log``,
``dt_bias`` and the bound, ``k``, ``v`` and ``beta`` — and makes the
log-decay, its running sum, ``beta k`` and ``beta v`` itself; the XLA forms
take ``g``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vnsum_tpu.ops import kda_scan


BOUND = -5.0


def _draw(seed, B, S, H, dk, dv, spread=3.0, dtype=jnp.float32,
          heads_differ=False):
    """q scaled and k of unit length a head, the gate's projection ``a``
    with the log-decay it gives in (BOUND, 0), beta in (0, 1), a state to
    continue. ``A_log`` and ``dt_bias`` are zero (``g = BOUND sigmoid(a)``)
    unless ``heads_differ``: then a head's ``A_log`` and a channel's
    ``dt_bias`` are drawn, as a layer's are."""
    ks = jax.random.split(jax.random.key(seed), 8)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, dv))
    a = (jax.random.normal(ks[3], (B, S, H, dk)) * spread).astype(dtype)
    A_log, dt_bias = jnp.zeros((H,)), jnp.zeros((H, dk))
    if heads_differ:
        A_log = jnp.log(jax.random.uniform(ks[6], (H,), minval=0.5,
                                           maxval=2.0))
        dt_bias = jax.random.uniform(ks[7], (H, dk), minval=-3.0, maxval=1.0)
    gate = dict(A_log=A_log, dt_bias=dt_bias, lower_bound=BOUND)
    g = kda_scan.kda_gate(a, **gate)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    state = jax.random.normal(ks[5], (B, H, dv, dk))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
            state, a, gate)


def _kernel(q, k, v, a, gate, beta, state, layer, pads, rows=None, *, chunk,
            jitted=True):
    """``kda_prefill_scan`` interpreted, on what the layer hands it: the
    gate's projection and its parameters, never ``g``."""
    fn = kda_scan.kda_prefill_scan if jitted \
        else kda_scan.kda_prefill_scan.__wrapped__
    return fn(q, k, v, a, beta, state, layer, jnp.asarray(pads, jnp.int32),
              rows, **gate, chunk=chunk, interpret=True)


def _under_pads(q, k, v, beta, st, pads, S):
    """A row's left pad as ``models/ling.py`` hands it over: q, k, v and
    beta zero under it, and a padded row's state zero when it starts."""
    real = jnp.arange(S)[None, :] >= pads[:, None]
    q, k, v = (a * real[:, :, None, None] for a in (q, k, v))
    return (q, k, v, beta * real[:, :, None],
            st * (pads == 0)[:, None, None, None])


def _err(a, b) -> float:
    return float(jnp.abs(jnp.asarray(a, jnp.float32)
                         - jnp.asarray(b, jnp.float32)).max())


SHAPES = [  # B, S, H, dk, dv, chunk
    (2, 64, 2, 16, 16, 32),    # two sub-blocks a chunk, two chunks
    (1, 96, 2, 32, 16, 64),    # four sub-blocks, a ragged last chunk
    (2, 40, 1, 16, 8, 16),     # one sub-block a chunk
    (1, 24, 2, 8, 8, 8),       # sub-blocks of 8
    (1, 12, 1, 8, 8, 4),       # ... of 4
    (2, 80, 3, 16, 16, 16),    # three heads: a head's beta is ITS column
]


@pytest.mark.parametrize("shape", SHAPES)
def test_chunked_xla_is_the_recurrence(shape):
    B, S, H, dk, dv, chunk = shape
    q, k, v, g, beta, st, _, _ = _draw(0, B, S, H, dk, dv,
                                       heads_differ=True)
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    oc, sc = kda_scan.kda_chunked_xla(q, k, v, g, beta, st, chunk)
    assert float(jnp.abs(o).max()) > 0.1
    assert _err(oc, o) < 5e-6 and _err(sc, s) < 5e-6


@pytest.mark.parametrize("shape", SHAPES)
def test_prefill_kernel_is_its_xla_form_and_the_recurrence(shape):
    """Interpreted, over the stacked state in place at a layer's index."""
    B, S, H, dk, dv, chunk = shape
    q, k, v, g, beta, st, a, gate = _draw(1, B, S, H, dk, dv,
                                          heads_differ=True)
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    oc, sc = kda_scan.kda_chunked_xla(q, k, v, g, beta, st, chunk)
    ok, sk = _kernel(q, k, v, a, gate, beta,
                     jnp.stack([jnp.ones_like(st), st]), 1, [0] * B,
                     chunk=chunk)
    assert _err(ok, oc) < 5e-6 and _err(sk[1], sc) < 5e-6
    assert _err(ok, o) < 5e-6 and _err(sk[1], s) < 5e-6
    assert (np.asarray(sk[0]) == 1.0).all()      # the other layer untouched


def test_prefill_kernel_hands_its_state_from_block_to_block(monkeypatch):
    """Several token blocks a call (the grid's third axis): the state stays
    in scratch between them."""
    monkeypatch.setattr(kda_scan, "_BLOCK_TOKENS", 32)
    q, k, v, g, beta, st, a, gate = _draw(2, 2, 128, 2, 16, 16)
    assert kda_scan._block_tokens(8, 16) == 32
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    ok, sk = _kernel(q, k, v, a, gate, beta, st[None], 0, [0, 0], chunk=16)
    assert _err(ok, o) < 5e-6 and _err(sk[0], s) < 5e-6


GROUPED = {  # S, chunk, tokens a block, pads, rows of a state of five
    # two blocks of five chunks a call: a group of four and one of one a
    # block, as a piece of a larger state
    "odd-chunks-a-block": (160, 16, 80, [0, 90], [3, 1]),
    # row 1's pad ends inside the second chunk: the first chunk of the
    # group is dead, the others live
    "pad-ends-inside-a-group": (64, 16, 1024, [0, 20], [4, 0]),
    # a first group wholly under row 1's pad, half of the second (computed,
    # and nothing of it enters the state)
    "a-group-under-the-pad": (128, 16, 1024, [0, 100], None),
    # a chunk a block, three blocks: every group is one chunk
    "a-chunk-a-block": (48, 16, 16, [0, 17], [2, 4]),
    # chunks of four sub-blocks, five of them: 256 rows an operand
    "five-chunks-of-64": (320, 64, 1024, [70, 0], [1, 0]),
    # row 1's first two blocks lie wholly under its pad (parked on its
    # third, not fetched), the pad ending inside that block's first group
    "whole-pad-blocks": (128, 16, 32, [0, 70], [1, 3]),
    # a ragged last chunk behind a pad: the zeros at the END of the call
    # must not decay the state, whatever gate the kernel makes of them
    "ragged-last-chunk": (72, 16, 1024, [0, 20], [2, 0]),
}


@pytest.mark.parametrize("case", GROUPED)
def test_prefill_kernel_groups_its_chunks_as_the_block_has_them(
        case, monkeypatch):
    """The state-free phase takes the chunks of a block a group at a time:
    a group the block does not fill, a group a row's left pad ends inside,
    with and without ``rows`` — against the recurrence and the XLA form."""
    S, chunk, block, pads, rows = GROUPED[case]
    monkeypatch.setattr(kda_scan, "_BLOCK_TOKENS", block)
    B, H, dk, dv = 2, 2, 16, 16
    q, k, v, g, beta, st, a, gate = _draw(10, B, S, H, dk, dv,
                                          heads_differ=True)
    pads = jnp.array(pads)
    q, k, v, beta, st = _under_pads(q, k, v, beta, st, pads, S)
    if rows is None:
        big, mine = jnp.stack([st, st + 1.0]), slice(None)
        ok, sk = _kernel(q, k, v, a, gate, beta, big, 0, pads, chunk=chunk)
    else:
        mine = jnp.array(rows)
        big = jnp.full((2, 5, H, dv, dk), 7.0).at[0, mine].set(st)
        ok, sk = _kernel(q, k, v, a, gate, beta, big, 0, pads, mine,
                         chunk=chunk)
        others = jnp.array(sorted(set(range(5)) - set(rows)))
        assert (np.asarray(sk[0][others]) == 7.0).all()
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    oc, sc = kda_scan.kda_chunked_xla(q, k, v, g, beta, st, chunk)
    assert float(jnp.abs(o).max()) > 0.1
    # the state to 1e-5: the kernel's running sum takes its float32
    # additions in another order than either form (over 64 tokens the three
    # sit 2e-5 to 8e-5 from the sum in float64, the kernel's the nearest)
    assert _err(ok, o) < 5e-6 and _err(sk[0][mine], s) < 1e-5
    assert _err(ok, oc) < 5e-6 and _err(sk[0][mine], sc) < 1e-5
    assert _err(sk[1], big[1]) == 0.0            # the other layer untouched
    for b, pad in enumerate(np.asarray(pads)):   # whole pad chunks: zeros
        assert not np.asarray(ok[b, :pad // chunk * chunk]).any()


@pytest.mark.parametrize("group", [1, 2, 3, 8])
def test_the_size_of_a_group_changes_no_sum(group, monkeypatch):
    """However many chunks share an operand, the result is the
    recurrence's: seven chunks under a pad that ends inside the third."""
    monkeypatch.setattr(kda_scan, "_GROUP_CHUNKS", group)
    q, k, v, g, beta, st, a, gate = _draw(12, 2, 112, 1, 16, 16)
    pads = jnp.array([0, 37])
    q, k, v, beta, st = _under_pads(q, k, v, beta, st, pads, 112)
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    # not the jitted entry: jit caches by arguments, not by the constant
    ok, sk = _kernel(q, k, v, a, gate, beta, st[None], 0, pads, chunk=16,
                     jitted=False)
    assert _err(ok, o) < 5e-6 and _err(sk[0], s) < 5e-6
    assert not np.asarray(ok[1, :32]).any()
    assert kda_scan.kda_tokens_computed(pads, 112, 16) == {
        1: 7 + 5, 2: 7 + 5, 3: 7 + 7, 8: 7 + 7}[group] * 16


def test_float32_inputs_give_what_the_one_phase_kernel_gave():
    """The same sums in another order: outputs and state of the kernel as
    PR 56 left it (a chunk at a time, every product inside the state's
    loop), interpreted at float32 on this draw, to 1e-5. Three chunks of
    64, one group, row 1's pad ending inside the second."""
    q, k, v, g, beta, st, a, gate = _draw(11, 2, 192, 2, 32, 32)
    pads = jnp.array([0, 70])
    q, k, v, beta, st = _under_pads(q, k, v, beta, st, pads, 192)
    o, s = _kernel(q, k, v, a, gate, beta, st[None], 0, pads, chunk=64)
    o, s = np.asarray(o), np.asarray(s[0])
    was_o0 = [[0.12740950, -0.01662224, -0.03305814],      # tokens 0, 63,
              [-0.00143864, 0.00904303, 0.00151281],       # 64, 191 of row
              [-0.01936263, -0.01867227, -0.00054826],     # 0, head 1
              [0.01797232, -0.00652484, -0.01111810]]
    was_o1 = [[0.0, 0.0, 0.0],                             # tokens 69, 70,
              [-0.00681758, -0.01911932, -0.03725274],     # 127, 128, 191
              [-0.00607630, -0.00490933, -0.00834571],     # of row 1, head 0
              [0.01151496, 0.00416203, -0.01943647],
              [0.02050495, 0.01968795, -0.01952249]]
    was_s = [[[0.02519188, 0.05876053], [-0.01498567, -0.03466559]],
             [[-0.13889401, -0.08171223], [0.01867666, 0.04982152]]]
    assert np.abs(o[0, [0, 63, 64, 191], 1, :3] - was_o0).max() < 1e-5
    assert np.abs(o[1, [69, 70, 127, 128, 191], 0, :3] - was_o1).max() < 1e-5
    assert np.abs(s[:, :, 5, [0, 31]] - was_s).max() < 1e-5
    assert not o[1, :64].any()


@pytest.mark.parametrize("form", ["kernel", "xla"])
@pytest.mark.parametrize("bound", [-5.0, -4.0])
def test_gates_at_the_bound_over_whole_chunks_stay_finite(form, bound):
    """Every gate AT the bound for two chunks of 64: exp(-G) alone would
    overflow within 18 tokens; by differences inside a sub-block nothing
    does, and the result is the recurrence's."""
    q, k, v, g, beta, st, a, gate = _draw(3, 1, 128, 2, 16, 16)
    # sigmoid(40) is 1 in float32: the gate the kernel makes IS the bound
    a, gate = jnp.full_like(a, 40.0), dict(gate, lower_bound=bound)
    g = kda_scan.kda_gate(a, **gate)
    assert (np.asarray(g) == bound).all()
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    if form == "kernel":
        oc, sc = _kernel(q, k, v, a, gate, beta, st[None], 0, [0], chunk=64)
        sc = sc[0]
    else:
        oc, sc = kda_scan.kda_chunked_xla(q, k, v, g, beta, st, 64)
    assert bool(jnp.isfinite(oc).all()) and bool(jnp.isfinite(sc).all())
    assert _err(oc, o) < 5e-6 and _err(sc, s) < 5e-6


def test_gates_drawn_down_to_the_bound_are_bounded_in_error():
    """Gates spread over the whole of (-5, 0), many of them at either end,
    channel by channel: fast and slow channels side by side in one head."""
    q, k, v, g, beta, st, a, gate = _draw(4, 2, 192, 2, 32, 32, spread=8.0)
    assert float(g.min()) < -4.99 and float(g.max()) > -0.01
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    ok, sk = _kernel(q, k, v, a, gate, beta, st[None], 0, [0, 0], chunk=64)
    assert bool(jnp.isfinite(ok).all())
    assert _err(ok, o) < 1e-5 and _err(sk[0], s) < 1e-5


def test_bfloat16_inputs_keep_a_float32_state_close():
    q, k, v, g, beta, st, a, gate = _draw(5, 1, 128, 2, 32, 32,
                                          dtype=jnp.bfloat16)
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    ok, sk = _kernel(q, k, v, a, gate, beta, st[None], 0, [0], chunk=64)
    assert a.dtype == ok.dtype == jnp.bfloat16 and sk.dtype == jnp.float32
    rel = float(jnp.linalg.norm(sk[0] - s) / jnp.linalg.norm(s))
    assert rel < 2e-2, rel


def test_pads_are_skipped_and_a_row_piece_lives_in_place():
    """Three rows under pads 0, 17 and 40 of 64 tokens, chunk 16, as a
    piece at rows 4, 0, 2 of a state of five: whole pad chunks give zeros
    and pass a zero state, the other rows of the state stay as they were."""
    B, S, H, dk, dv, chunk = 3, 64, 2, 16, 16, 16
    q, k, v, g, beta, st, a, gate = _draw(6, B, S, H, dk, dv)
    pads = jnp.array([0, 17, 40])
    real = jnp.arange(S)[None, :] >= pads[:, None]
    q, k, v = (x * real[:, :, None, None] for x in (q, k, v))
    beta = beta * real[:, :, None]
    big = jnp.full((2, 5, H, dv, dk), 7.0).at[1].set(0.0)
    rows = jnp.array([4, 0, 2])
    ok, sk = _kernel(q, k, v, a, gate, beta, big, 1, pads, rows, chunk=chunk)
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, jnp.zeros_like(st))
    assert _err(ok, o) < 5e-6 and _err(sk[1][rows], s) < 5e-6
    assert not np.asarray(sk[1][jnp.array([1, 3])]).any()
    assert (np.asarray(sk[0]) == 7.0).all()
    assert not np.asarray(ok[2, :32]).any()       # two whole pad chunks
    oc, sc = kda_scan.kda_chunked_xla(q, k, v, g, beta, big[1], chunk, rows)
    assert _err(oc, o) < 5e-6 and _err(sc[rows], s) < 5e-6
    assert not np.asarray(sc[jnp.array([1, 3])]).any()


def test_a_zero_state_stays_exactly_zero_under_zeroed_inputs():
    q, k, v, g, beta, st, a, gate = _draw(7, 2, 48, 2, 16, 16)
    zero = jnp.zeros_like
    for fn in (
            lambda: kda_scan.kda_chunked_xla(
                zero(q), zero(k), zero(v), g, zero(beta), zero(st), 16),
            lambda: _kernel(
                zero(q), zero(k), zero(v), a, gate, zero(beta),
                zero(st)[None], 0, [0, 0], chunk=16)):
        o, s = fn()
        assert not np.asarray(o).any() and not np.asarray(s).any()


def test_only_pads_and_reshapes_lie_between_the_layer_and_the_kernel():
    """Outside its ``pallas_call`` the jaxpr of ``kda_prefill_scan`` makes no
    array of a ``[B, S, H * dk]`` array's size but by a reshape, the pad to
    whole chunks and the output's slice: no running sum, no product with
    beta, no gate in float32 (a ragged call, so the pad is there)."""
    B, S, H, dk, dv = 2, 40, 2, 16, 16
    q, k, v, _, beta, st, a, gate = _draw(13, B, S, H, dk, dv)
    jaxpr = jax.make_jaxpr(lambda *x: kda_scan.kda_prefill_scan(
        *x, jnp.zeros((B,), jnp.int32), jnp.array([1, 0]), **gate,
        chunk=16))(q, k, v, a, beta, st[None], 0)
    made, seen = {}, set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            seen.add(eqn.primitive.name)
            if eqn.primitive.name == "pallas_call":
                continue
            inner = list(jax.core.jaxprs_in_params(eqn.params))
            for sub in inner:
                walk(sub)
            if not inner and any(out.aval.size >= B * S * H * dk
                                 for out in eqn.outvars):
                made[eqn.primitive.name] = eqn.outvars[0].aval.shape

    walk(jaxpr.jaxpr)
    assert "pallas_call" in seen
    assert not seen & {"cumsum", "reduce_window_sum", "reduce_window",
                       "logistic", "dot_general"}
    assert set(made) <= {"reshape", "pad", "slice"}, made
    assert "pad" in made


@pytest.mark.parametrize("B,H,dk,dv", [(3, 4, 16, 8), (2, 2, 32, 32)])
def test_decode_kernel_is_the_one_token_step(B, H, dk, dv):
    q, k, v, g, beta, st, _, _ = _draw(8, B, 1, H, dk, dv)
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    o, s = kda_scan.kda_step_xla(*args, st)
    ok, sk = kda_scan.kda_decode_update(
        *args, jnp.stack([st, st + 1.0]), 1, interpret=True)
    _, s1 = kda_scan.kda_step_xla(*args, st + 1.0)
    assert _err(sk[1], s1) < 5e-6 and _err(sk[0], st) == 0.0
    ok, sk = kda_scan.kda_decode_update(*args, st[None], 0, interpret=True)
    assert _err(ok, o) < 5e-6 and _err(sk[0], s) < 5e-6
    # the step IS the equation: S (I - b k k^T) diag(e^g) ... transposed
    S = np.asarray(st, np.float64).swapaxes(-1, -2)              # [dk, dv]
    kk = np.asarray(k[:, 0], np.float64)
    b = np.asarray(beta[:, 0], np.float64)[..., None, None]
    D = np.exp(np.asarray(g[:, 0], np.float64))[..., None] * S
    new = (D - b * kk[..., :, None] * np.einsum("bhk,bhkv->bhv", kk, D)[
        :, :, None, :]
        + b * kk[..., :, None] * np.asarray(v[:, 0], np.float64)[:, :, None])
    assert np.abs(np.asarray(s).swapaxes(-1, -2) - new).max() < 1e-5


def test_tokens_computed_by_hand(monkeypatch):
    # chunk 64 over 512 tokens, groups of four chunks: pads 0, 255, 256,
    # 300 and 512 skip 0, 0, 1, 1 and 2 groups
    assert kda_scan.kda_tokens_computed([0, 255, 256, 300, 512], 512, 64) \
        == (2 + 2 + 1 + 1 + 0) * 256
    assert kda_scan.kda_tokens_computed([10], 100, 64) == 128
    # blocks of five chunks: a group of four and one of one a block; the
    # pad of 330 ends inside the second block's first chunk
    monkeypatch.setattr(kda_scan, "_BLOCK_TOKENS", 320)
    assert kda_scan.kda_tokens_computed([0, 256, 330], 640, 64) \
        == (10 + 6 + 5) * 64


@pytest.mark.parametrize("chunk", [48, 24])
def test_a_chunk_that_is_no_power_of_two_of_sub_blocks_is_refused(chunk):
    q, k, v, g, beta, st, a, gate = _draw(9, 1, 48, 1, 8, 8)
    with pytest.raises(ValueError, match="sub-blocks"):
        _kernel(q, k, v, a, gate, beta, st[None], 0, [0], chunk=chunk)
