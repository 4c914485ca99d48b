"""The order the prefill kernel writes a cell's heads in (ops/flash_attention
.py, for_each_head): whatever stands next to what, a head's arithmetic is its
own. The kernel interpreted at tiny shapes, in the fast tier — test_ops_flash
.py, which holds the kernel's other cases, is one of conftest's slow modules
and its tests do not count there."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vnsum_tpu.ops import flash_attention
from vnsum_tpu.ops.flash_attention import flash_prefill_attention

# 2 rows (one left-padded), 45 queries at cache slot 32, 4 KV heads over 80
# slots: block_k 32 leaves a partial last key block, block_q 16 a partial
# last query block
_B, _S, _OFF, _C, _KV, _HD, _BQ, _BK = 2, 45, 32, 80, 4, 128, 16, 32
_PADS = [0, 37]


def _case(G: int, quantized: bool):
    kq, kk, kv, ks, vs = jax.random.split(jax.random.key(100 + G), 5)
    q = jax.random.normal(kq, (_B, _S, G * _KV, _HD), jnp.float32)
    shape = (1, _B, _KV, _C, _HD)
    if quantized:
        cache = {
            "k": jax.random.randint(kk, shape, -127, 128, jnp.int8),
            "v": jax.random.randint(kv, shape, -127, 128, jnp.int8),
            "ks": jax.random.uniform(ks, shape[:-1], jnp.float32, 0.01, 0.02),
            "vs": jax.random.uniform(vs, shape[:-1], jnp.float32, 0.01, 0.02),
        }
    else:
        cache = {"k": jax.random.normal(kk, shape, jnp.float32),
                 "v": jax.random.normal(kv, shape, jnp.float32)}
    return q, cache


def _call(q, cache, G, window):
    return np.asarray(flash_prefill_attention(
        q, cache, 0, jnp.asarray(_PADS, jnp.int32), G, jnp.int32(window),
        jnp.int32(_OFF), block_q=_BQ, block_k=_BK, interpret=True))


def _heads_alone(q, cache, G, window):
    """(a group's output for head g, head g's alone) for every g: the G=1
    call takes query head g of every KV head over the same cache and tile."""
    group = _call(q, cache, G, window)
    assert np.isfinite(group).all()
    for g in range(G):
        yield g, group[:, :, g::G], _call(q[:, :, g::G], cache, 1, window)


@pytest.mark.parametrize("window", [0, 24], ids=["global", "window"])
@pytest.mark.parametrize("G", [2, 3, 4, 6, 7, 9])
def test_a_groups_output_is_its_heads_run_one_at_a_time(G, window):
    """A group of G heads against each of its heads alone, bit for bit: the
    static unroll written one product ahead (G <= 4), the loop's steps, its
    last step and the odd head out (7, 9), under no window and under one
    smaller than C, with a left pad, a chunk offset and a partial last key
    block."""
    q, cache = _case(G, quantized=False)
    for g, grouped, alone in _heads_alone(q, cache, G, window):
        np.testing.assert_array_equal(grouped, alone, err_msg=str(g))


@pytest.mark.parametrize("G", [4, 7])
def test_a_groups_output_is_its_heads_over_an_int8_cache(G):
    """The same over the engine's cache (int8 values, a scale a slot and KV
    head), whose scale rows the heads of a step share. To a last bit only:
    XLA's CPU backend, which runs the interpreted body, contracts the two
    scale multiplies into their neighbours one way in a group's program and
    another in a single head's (3.6e-07 relative; the float32 cache above,
    which has no such multiply, is equal bit for bit, and on the chip the
    outputs of every order were compared with the parent's bit for bit:
    PERF.md section 6, PR 45)."""
    q, cache = _case(G, quantized=True)
    for g, grouped, alone in _heads_alone(q, cache, G, 24):
        np.testing.assert_allclose(grouped, alone, rtol=2e-6, atol=1e-7,
                                   err_msg=str(g))


@pytest.mark.parametrize("G,want", [
    (6, "c58887160d3b"), (7, "be1289d10f32"), (9, "a40bab81fc83"),
    (16, "596252c76f9f"),
])
def test_a_looped_groups_kernel_is_the_one_it_was(G, want):
    """What PR 45 did NOT move: in the loop a product written ahead loses
    (4.73 for 4.60 ns per 1,024 scores at G=7 on the v5e), so a group wider
    than four keeps its order and its tile, and its call at the cells'
    shapes (SmallThinker's 7, Laguna's 6 and 9; int8 cache, a 2,048-query
    chunk over 8,448 slots) traces to the jaxpr it traced to on PR 44's
    tree, where these hashes were taken."""
    import hashlib

    assert flash_attention._heads_ahead(G) == 0
    KV, S, C = 2, 2048, 8448
    sds = jax.ShapeDtypeStruct
    cache = {"k": sds((1, _B, KV, C, _HD), jnp.int8),
             "v": sds((1, _B, KV, C, _HD), jnp.int8),
             "ks": sds((1, _B, KV, C), jnp.float32),
             "vs": sds((1, _B, KV, C), jnp.float32)}
    text = str(jax.make_jaxpr(
        lambda q, cache, pad, win, off: flash_prefill_attention(
            q, cache, 0, pad, G, win, off, interpret=True)
    )(sds((_B, S, G * KV, _HD), jnp.bfloat16), cache, sds((_B,), jnp.int32),
      sds((), jnp.int32), sds((), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest()[:12] == want
