"""The DeepSeek-V2 family's three kernels compiled for the chip at the
published widths, with no chip: the TPU's compiler is installed here and
compiles for a described v5e. Interpret mode cannot show what this does: a
slice not aligned to the tiling, too much VMEM, an int8 product Mosaic
refuses. Nothing runs and nothing is timed.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every xdist worker imports this file
(on-chip-measurement guide, section 2). Keep such tests in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be read
    # back without a chip: keep it out
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiled(fn, one_chip, *shapes):
    args = [jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip), a,
        is_leaf=lambda x: isinstance(x, tuple)) for a in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("S,offset", [
    (1024, 7168), (1024, 0), (2048, 2048),
    (1024, 1024),   # the reduce's second chunk: 2,048 keys
    (1024, 3072),   # parity's last chunk: 4,096 keys
])
def test_prefill_kernel_compiles_at_the_published_widths(one_chip, S, offset):
    """With the geometry the wrapper chooses (a group of heads a step, key
    blocks wider than query blocks): one that overflows scoped VMEM fails
    here and not on the chip."""
    from vnsum_tpu.ops.mla_attention import mla_prefill_attention

    R, H, T = 1, 128, offset + S
    c = _compiled(
        lambda qn, qr, kn, kr, v, p: mla_prefill_attention(
            qn, qr, kn, kr, v, p, scale=0.1147, q_offset=offset),
        one_chip, ((R, H, S, 128), BF16), ((R, H, S, 64), BF16),
        ((R, H, T, 128), BF16), ((R, T, 64), BF16), ((R, H, T, 128), BF16),
        ((R,), I32))
    assert "tpu_custom_call" in c.as_text()


def test_absorbed_decode_kernel_compiles_over_the_576_wide_cache(one_chip):
    """C = 8448 leaves a partial last block of 256; the block's lane slices
    at 512 (latent | rope) have to be ones Mosaic takes."""
    from vnsum_tpu.ops.mla_attention import mla_decode_attention

    c = _compiled(
        lambda ql, qr, cache, p: mla_decode_attention(
            ql, qr, cache, 3, p, 8200, scale=0.1147, rank=512),
        one_chip, ((24, 128, 512), BF16), ((24, 128, 64), BF16),
        ((8, 24, 8448, 576), BF16), ((24,), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("tm,tiles", [(256, 233), (32, 46)])
def test_grouped_expert_product_compiles_on_int8_rows(one_chip, tm, tiles):
    """Both products of an expert at the prefill's and the decode's row
    tile: s8 x s8 -> s32 over K = 5120 and K = 1536, weights read from the
    stack of 7 layers x 40 experts in place."""
    from vnsum_tpu.models.deepseek import _column_tile
    from vnsum_tpu.ops.expert_matmul import expert_grouped_matmul

    L, E, D, F, M = 7, 40, 5120, 1536, tm * tiles
    up = {"q": ((L, E, D, F), I8), "s": ((L, E, F), F32)}
    down = {"q": ((L, E, F, D), I8), "s": ((L, E, D), F32)}
    sched = (((tiles,), I32), ((1,), I32))
    c = _compiled(
        lambda x, xs, w, u, te, nu: expert_grouped_matmul(
            x, xs, w, u, 2, te, nu, tm=tm, tn=_column_tile(D, F),
            out_dtype=BF16),
        one_chip, ((M, D), I8), ((M, 1), F32), up, up, *sched)
    assert "tpu_custom_call" in c.as_text()
    c = _compiled(
        lambda x, xs, w, te, nu: expert_grouped_matmul(
            x, xs, w, None, 2, te, nu, tm=tm, tn=_column_tile(F, D),
            out_dtype=BF16),
        one_chip, ((M, F), I8), ((M, 1), F32), down, *sched)
    assert "tpu_custom_call" in c.as_text()
    assert (_column_tile(D, F), _column_tile(F, D)) == (512, 1280)
