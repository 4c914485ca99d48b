"""ops/kda_scan.py on the CPU: the chunked forms (XLA, and the kernel
interpreted) against the token-by-token recurrence, the one-token update,
gates down to the bound, pads, row pieces and the host's count."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vnsum_tpu.ops import kda_scan


def _draw(seed, B, S, H, dk, dv, bound=-5.0, spread=3.0, dtype=jnp.float32):
    """q scaled and k of unit length a head, gates in (bound, 0), beta in
    (0, 1), a state to continue."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = bound * jax.nn.sigmoid(
        jax.random.normal(ks[3], (B, S, H, dk)) * spread)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    state = jax.random.normal(ks[5], (B, H, dv, dk))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
            state)


def _err(a, b) -> float:
    return float(jnp.abs(jnp.asarray(a, jnp.float32)
                         - jnp.asarray(b, jnp.float32)).max())


SHAPES = [  # B, S, H, dk, dv, chunk
    (2, 64, 2, 16, 16, 32),    # two sub-blocks a chunk, two chunks
    (1, 96, 2, 32, 16, 64),    # four sub-blocks, a ragged last chunk
    (2, 40, 1, 16, 8, 16),     # one sub-block a chunk
    (1, 24, 2, 8, 8, 8),       # sub-blocks of 8
    (1, 12, 1, 8, 8, 4),       # ... of 4
]


@pytest.mark.parametrize("shape", SHAPES)
def test_chunked_xla_is_the_recurrence(shape):
    B, S, H, dk, dv, chunk = shape
    q, k, v, g, beta, st = _draw(0, B, S, H, dk, dv)
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    oc, sc = kda_scan.kda_chunked_xla(q, k, v, g, beta, st, chunk)
    assert float(jnp.abs(o).max()) > 0.1
    assert _err(oc, o) < 5e-6 and _err(sc, s) < 5e-6


@pytest.mark.parametrize("shape", SHAPES)
def test_prefill_kernel_is_its_xla_form_and_the_recurrence(shape):
    """Interpreted, over the stacked state in place at a layer's index."""
    B, S, H, dk, dv, chunk = shape
    q, k, v, g, beta, st = _draw(1, B, S, H, dk, dv)
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    oc, sc = kda_scan.kda_chunked_xla(q, k, v, g, beta, st, chunk)
    ok, sk = kda_scan.kda_prefill_scan(
        q, k, v, g, beta, jnp.stack([jnp.ones_like(st), st]), 1,
        jnp.zeros((B,), jnp.int32), chunk=chunk, interpret=True)
    assert _err(ok, oc) < 5e-6 and _err(sk[1], sc) < 5e-6
    assert _err(ok, o) < 5e-6 and _err(sk[1], s) < 5e-6
    assert (np.asarray(sk[0]) == 1.0).all()      # the other layer untouched


def test_prefill_kernel_hands_its_state_from_block_to_block(monkeypatch):
    """Several token blocks a call (the grid's third axis): the state stays
    in scratch between them."""
    monkeypatch.setattr(kda_scan, "_BLOCK_TOKENS", 32)
    q, k, v, g, beta, st = _draw(2, 2, 128, 2, 16, 16)
    assert kda_scan._block_tokens(8, 16) == 32
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    ok, sk = kda_scan.kda_prefill_scan(
        q, k, v, g, beta, st[None], 0, jnp.zeros((2,), jnp.int32), chunk=16,
        interpret=True)
    assert _err(ok, o) < 5e-6 and _err(sk[0], s) < 5e-6


@pytest.mark.parametrize("form", ["kernel", "xla"])
@pytest.mark.parametrize("bound", [-5.0, -4.0])
def test_gates_at_the_bound_over_whole_chunks_stay_finite(form, bound):
    """Every gate AT the bound for two chunks of 64: exp(-G) alone would
    overflow within 18 tokens; by differences inside a sub-block nothing
    does, and the result is the recurrence's."""
    q, k, v, g, beta, st = _draw(3, 1, 128, 2, 16, 16)
    g = jnp.full_like(g, bound)
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    if form == "kernel":
        oc, sc = kda_scan.kda_prefill_scan(
            q, k, v, g, beta, st[None], 0, jnp.zeros((1,), jnp.int32),
            chunk=64, interpret=True)
        sc = sc[0]
    else:
        oc, sc = kda_scan.kda_chunked_xla(q, k, v, g, beta, st, 64)
    assert bool(jnp.isfinite(oc).all()) and bool(jnp.isfinite(sc).all())
    assert _err(oc, o) < 5e-6 and _err(sc, s) < 5e-6


def test_gates_drawn_down_to_the_bound_are_bounded_in_error():
    """Gates spread over the whole of (-5, 0), many of them at either end,
    channel by channel: fast and slow channels side by side in one head."""
    q, k, v, g, beta, st = _draw(4, 2, 192, 2, 32, 32, spread=8.0)
    assert float(g.min()) < -4.99 and float(g.max()) > -0.01
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    ok, sk = kda_scan.kda_prefill_scan(
        q, k, v, g, beta, st[None], 0, jnp.zeros((2,), jnp.int32), chunk=64,
        interpret=True)
    assert bool(jnp.isfinite(ok).all())
    assert _err(ok, o) < 1e-5 and _err(sk[0], s) < 1e-5


def test_bfloat16_inputs_keep_a_float32_state_close():
    q, k, v, g, beta, st = _draw(5, 1, 128, 2, 32, 32, dtype=jnp.bfloat16)
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, st)
    ok, sk = kda_scan.kda_prefill_scan(
        q, k, v, g, beta, st[None], 0, jnp.zeros((1,), jnp.int32), chunk=64,
        interpret=True)
    assert ok.dtype == jnp.bfloat16 and sk.dtype == jnp.float32
    rel = float(jnp.linalg.norm(sk[0] - s) / jnp.linalg.norm(s))
    assert rel < 2e-2, rel


def test_pads_are_skipped_and_a_row_piece_lives_in_place():
    """Three rows under pads 0, 17 and 40 of 64 tokens, chunk 16, as a
    piece at rows 4, 0, 2 of a state of five: whole pad chunks give zeros
    and pass a zero state, the other rows of the state stay as they were."""
    B, S, H, dk, dv, chunk = 3, 64, 2, 16, 16, 16
    q, k, v, g, beta, st = _draw(6, B, S, H, dk, dv)
    pads = jnp.array([0, 17, 40])
    real = jnp.arange(S)[None, :] >= pads[:, None]
    q, k, v = (a * real[:, :, None, None] for a in (q, k, v))
    beta = beta * real[:, :, None]
    big = jnp.full((2, 5, H, dv, dk), 7.0).at[1].set(0.0)
    rows = jnp.array([4, 0, 2])
    ok, sk = kda_scan.kda_prefill_scan(q, k, v, g, beta, big, 1, pads, rows,
                                       chunk=chunk, interpret=True)
    o, s = kda_scan.kda_recurrent_xla(q, k, v, g, beta, jnp.zeros_like(st))
    assert _err(ok, o) < 5e-6 and _err(sk[1][rows], s) < 5e-6
    assert not np.asarray(sk[1][jnp.array([1, 3])]).any()
    assert (np.asarray(sk[0]) == 7.0).all()
    assert not np.asarray(ok[2, :32]).any()       # two whole pad chunks
    oc, sc = kda_scan.kda_chunked_xla(q, k, v, g, beta, big[1], chunk, rows)
    assert _err(oc, o) < 5e-6 and _err(sc[rows], s) < 5e-6
    assert not np.asarray(sc[jnp.array([1, 3])]).any()


def test_a_zero_state_stays_exactly_zero_under_zeroed_inputs():
    q, k, v, g, beta, st = _draw(7, 2, 48, 2, 16, 16)
    zero = jnp.zeros_like
    for fn in (
            lambda: kda_scan.kda_chunked_xla(
                zero(q), zero(k), zero(v), g, zero(beta), zero(st), 16),
            lambda: kda_scan.kda_prefill_scan(
                zero(q), zero(k), zero(v), g, zero(beta), zero(st)[None], 0,
                jnp.zeros((2,), jnp.int32), chunk=16, interpret=True)):
        o, s = fn()
        assert not np.asarray(o).any() and not np.asarray(s).any()


@pytest.mark.parametrize("B,H,dk,dv", [(3, 4, 16, 8), (2, 2, 32, 32)])
def test_decode_kernel_is_the_one_token_step(B, H, dk, dv):
    q, k, v, g, beta, st = _draw(8, B, 1, H, dk, dv)
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


def test_tokens_computed_by_hand():
    # chunk 64 over 256 tokens: pads 0, 63, 64, 200, 256 skip 0, 0, 1, 3, 4
    assert kda_scan.kda_tokens_computed([0, 63, 64, 200, 256], 256, 64) \
        == (4 + 4 + 3 + 1 + 0) * 64
    assert kda_scan.kda_tokens_computed([10], 100, 64) == 128


@pytest.mark.parametrize("chunk", [48, 24])
def test_a_chunk_that_is_no_power_of_two_of_sub_blocks_is_refused(chunk):
    q, k, v, g, beta, st = _draw(9, 1, 48, 1, 8, 8)
    with pytest.raises(ValueError, match="sub-blocks"):
        kda_scan.kda_prefill_scan(
            q, k, v, g, beta, st[None], 0, jnp.zeros((1,), jnp.int32),
            chunk=chunk, interpret=True)
