"""The DeepSeek-V2 family's three kernels, the GQA kernels and the
expert product at the SmallThinker family's geometry, the Granite-4.0-H
family's two scan kernels and the GQA kernels at its 64-wide heads, and the
Nemotron-H family's (the scan kernels at eight groups, the single-product
expert form at experts stored 1,920 wide for 1,856, the GQA kernels at 16
query heads on 2 KV heads), and the LFM2-MoE family's (the GQA kernels at
32/8 heads of 64, two KV heads a lane tile of the cache as ``init_kv_cache``
lays it out, over 6 layers with a row piece of four rows, the expert
product at 32 experts of 2048 x 1792), and the Brumby family's two
power-retention kernels and its whole (12, 8192, 256) program, and the Keye
family's sparse-attention kernels (selection and masked attention, prefill
and decode, over 16,640 slots) and its whole (8, 16384, 256) program, compiled
for the chip at the published widths, with no chip: the TPU's compiler is installed here and
compiles for a described v5e. Interpret mode cannot show what this does: a
slice not aligned to the tiling, too much VMEM, an int8 product Mosaic
refuses. Nothing runs and nothing is timed.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every xdist worker imports this file
(on-chip-measurement guide, section 2). Keep such tests in this one file.
"""
from __future__ import annotations

import math
import os
import re

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
    """With the geometry the wrapper chooses (a group of heads a step, their
    blocks of ``W_kb`` / ``W_vb`` beside a latent block whose lanes are
    sliced at 512): one that overflows scoped VMEM fails here and not on
    the chip."""
    from vnsum_tpu.ops.mla_attention import mla_prefill_attention

    R, H, T = 4, 128, offset + S
    c = _compiled(
        lambda qn, qr, lat, wk, wv, p: mla_prefill_attention(
            qn, qr, lat, wk, wv, p, scale=0.1147, q_offset=offset),
        one_chip, ((R, H, S, 128), BF16), ((R, H, S, 64), BF16),
        ((R, T, 576), BF16), ((H, 512, 128), BF16), ((H, 512, 128), BF16),
        ((R,), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("R,S,offset", [
    (4, 1024, 7168),   # the map dispatch's last chunk, a piece of four rows
    (4, 1024, 0),      # its first
    (1, 1024, 3072),   # parity's last chunk
])
def test_prefill_kernel_compiles_over_the_stacked_cache(one_chip, R, S,
                                                        offset):
    """As the model calls it since PR 53: the cell's latent cache whole, its
    layer and the piece's first batch row by scalar prefetch."""
    from vnsum_tpu.ops.mla_attention import mla_prefill_attention

    H = 128
    c = _compiled(
        lambda qn, qr, cache, wk, wv, p, layer, row: mla_prefill_attention(
            qn, qr, cache, wk, wv, p, scale=0.1147, q_offset=offset,
            layer_idx=layer, row_offset=row),
        one_chip, ((R, H, S, 128), BF16), ((R, H, S, 64), BF16),
        ((8, 24, 8448, 576), BF16), ((H, 512, 128), BF16),
        ((H, 512, 128), BF16), ((R,), I32), ((), I32), ((), I32))
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


# (layers, experts held, hidden, expert width, gate, column tiles)
_DEEPSEEK = (7, 40, 5120, 1536, "silu", (512, 1280))
_SMALLTHINKER = (16, 64, 2560, 768, "relu", (768, 2560))
_LAGUNA = (4, 256, 3072, 1024, "silu", (512, 1536))
# LFM2-8B-A1B whole: 22 sparse layers of 32 gated experts of 2048 x 1792
_LFM2 = (22, 32, 2048, 1792, "silu", (896, 1024))


@pytest.mark.parametrize("widths,tm,tiles", [
    (_DEEPSEEK, 256, 233), (_DEEPSEEK, 32, 45),
    # a piece of 8,192 tokens x 6 picks over 64 held experts; a decode step
    # of 24 rows
    (_SMALLTHINKER, 256, 257), (_SMALLTHINKER, 32, 69),
    # a piece of 8,192 tokens x 10 picks over 256 held experts; a decode
    # step of 12 rows: no more tiles than its 120 slots
    (_LAGUNA, 256, 577), (_LAGUNA, 32, 121),
    # a piece of 8,192 tokens x 4 picks over 32 held experts (~1,024 rows
    # an expert); a decode step of 24 rows
    (_LFM2, 256, 161), (_LFM2, 32, 36),
])
def test_grouped_expert_product_compiles_on_int8_rows(one_chip, widths, tm,
                                                      tiles):
    """Both products of an expert at the prefill's and the decode's row
    tile: s8 x s8 -> s32 over K = hidden and K = the expert's width, weights
    read from the stack of layers x held experts in place; DeepSeek-V2's
    SwiGLU experts of 5120 x 1536 and SmallThinker's ReGLU ones of 2560 x
    768. The grid's row bound is the traced ``tiles_used``: a bound Mosaic
    refuses fails here."""
    from vnsum_tpu.models.experts import _column_tile
    from vnsum_tpu.ops.expert_matmul import expert_grouped_matmul

    L, E, D, F, act, column_tiles = widths
    M = tm * tiles
    up = {"q": ((L, E, D, F), I8), "s": ((L, E, F), F32)}
    down = {"q": ((L, E, F, D), I8), "s": ((L, E, D), F32)}
    sched = (((tiles,), I32), ((1,), I32))
    c = _compiled(
        lambda x, xs, w, u, te, nu: expert_grouped_matmul(
            x, xs, w, u, 2, te, nu, tm=tm, tn=_column_tile(D, F),
            out_dtype=BF16, act=act),
        one_chip, ((M, D), I8), ((M, 1), F32), up, up, *sched)
    assert "tpu_custom_call" in c.as_text()
    c = _compiled(
        lambda x, xs, w, te, nu: expert_grouped_matmul(
            x, xs, w, None, 2, te, nu, tm=tm, tn=_column_tile(F, D),
            out_dtype=BF16),
        one_chip, ((M, F), I8), ((M, 1), F32), down, *sched)
    assert "tpu_custom_call" in c.as_text()
    assert (_column_tile(D, F), _column_tile(F, D)) == column_tiles


@pytest.mark.parametrize("widths,k,rows,tiles", [
    # a map step's rows and the reduce's 4, each cell's picks a token
    (_DEEPSEEK, 6, 24, 45), (_DEEPSEEK, 6, 4, 25),
    (_SMALLTHINKER, 6, 24, 69), (_SMALLTHINKER, 6, 4, 25),
    (_LAGUNA, 10, 12, 121), (_LAGUNA, 10, 4, 41),
    (_LFM2, 4, 24, 36), (_LFM2, 4, 4, 17),
])
def test_a_decode_steps_expert_layer_compiles_with_its_dynamic_grid(
        one_chip, widths, k, rows, tiles):
    """A whole decode step of ``grouped_experts`` at the cells' widths: the
    layout by sort over ``min(N, N // tm + E) + 1`` row tiles, the rows by a
    0/1 product, both products under a grid bounded by the layout's own
    ``tiles_used`` (a scalar the program computes, not a constant), one
    gather back."""
    import types

    from vnsum_tpu.models.experts import grouped_experts
    from vnsum_tpu.ops.expert_matmul import expert_layout

    L, E, D, F, act, _tiles = widths
    cfg = types.SimpleNamespace(n_held=E, moe_intermediate=F, act=act,
                                w8a8_prefill=True)
    up = {"q": ((L, E, D, F), I8), "s": ((L, E, F), F32)}
    down = {"q": ((L, E, F, D), I8), "s": ((L, E, D), F32)}
    c = _compiled(
        lambda x, local, weights, g, u, d: grouped_experts(
            x, local, weights, {"we_gate": g, "we_up": u, "we_down": d}, 1,
            cfg, interpret=False),
        one_chip, ((rows, D), BF16), ((rows, k), I32), ((rows, k), F32),
        up, up, down)
    assert c.as_text().count("tpu_custom_call") >= 2
    M = jax.eval_shape(
        lambda e: expert_layout(e, E, 32)[1],
        jax.ShapeDtypeStruct((rows * k,), I32)).shape[0]
    assert M == tiles * 32


@pytest.mark.parametrize("widths,k,tokens,columns", [
    (_DEEPSEEK, 6, 128, 5120), (_SMALLTHINKER, 6, 256, 2560),
    (_LAGUNA, 10, 128, 1024)])
def test_expert_combine_compiles_at_a_piece_of_the_cells(one_chip, widths, k,
                                                         tokens, columns):
    """The rows' way back at a piece of 8,192 tokens x k picks: chunks of 16
    rows by DMA into two row buffers that fit the scoped VMEM at every
    cell's widths (5,120 wide a token tile is 128, not 256; with 256
    experts a tile may need 512 chunks before any pick, and a grid step
    takes a third of the 3,072 columns), the picks summed by a 0/1
    product."""
    from vnsum_tpu.ops.expert_matmul import _combine_geometry, expert_combine

    _L, E, D, _F, _act, _tiles = widths
    N = 8192 * k
    M = (N // 256 + E + 1) * 256
    assert _combine_geometry(k, E, D, 2) == (tokens, columns)
    c = _compiled(
        lambda y, e, r: expert_combine(y, e, r, n_experts=E, k=k),
        one_chip, ((M, D), BF16), ((N,), I32), ((N,), I32))
    assert "expert_combine" in c.as_text()


def _int8_cache(L, B, KV, C, hd, tile=None):
    """``init_kv_cache``'s shapes: ``tile`` KV heads a lane tile (None: the
    rule's — two of 64, one of 128), the scales a head."""
    from vnsum_tpu.ops.flash_attention import heads_per_lane_tile

    tile = tile or heads_per_lane_tile(KV, hd)
    kv = ((L, B, KV // tile, C, hd * tile), I8)
    return {"k": kv, "v": kv,
            "ks": ((L, B, KV, C), F32), "vs": ((L, B, KV, C), F32)}


@pytest.mark.parametrize("offset", [0, 2048, 6144])
def test_gqa_prefill_kernel_compiles_at_g7_under_a_window(one_chip, offset):
    """28/4 heads of 128 (a group of 7: its heads looped two a step and one
    after, bq 1024 / bk 1024, 38.5 MiB of scoped VMEM asked for), a
    2,048-query chunk of the S=8192 bucket over the int8 cache of 8,448
    slots, the layer's window a traced scalar."""
    from vnsum_tpu.ops.flash_attention import flash_prefill_attention

    c = _compiled(
        lambda q, cache, pads, win: flash_prefill_attention(
            q, cache, 3, pads, 7, win, offset),
        one_chip, ((2, 2048, 28, 128), BF16), _int8_cache(4, 2, 4, 8448, 128),
        ((2,), I32), ((), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("G,KV,hd,geometry", [
    (7, 4, 128, (1024, 1024)),
    (16, 2, 128, (1024, 1024)),   # 61 MiB counted: the widest at 1024 rows
    (32, 1, 128, (512, 1024)),
    (8, 2, 256, (1024, 512)),
])
def test_gqa_prefill_kernel_compiles_at_wide_groups(one_chip, G, KV, hd,
                                                    geometry):
    """Groups wider than four at the geometry the wrapper gives them, over a
    bf16 cache (2 MiB more of VMEM than an int8 one): the limit the kernel
    asks for from its own count (_vmem_bytes) is one Mosaic accepts."""
    from vnsum_tpu.ops import flash_attention

    assert flash_attention._block_geometry(2048, 8448, G, hd) == geometry
    cache = {"k": ((2, 2, KV, 8448, hd), BF16), "v": ((2, 2, KV, 8448, hd), BF16)}
    c = _compiled(
        lambda q, cache, pads, win: flash_attention.flash_prefill_attention(
            q, cache, 1, pads, G, win, 6144),
        one_chip, ((2, 2048, G * KV, hd), BF16), cache, ((2,), I32), ((), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("G,KV,hd,geometry,cache_type", [
    (4, 8, 128, (512, 1024), I8),     # Qwen3, the served join
    (4, 10, 128, (512, 1024), BF16),  # Phi-4's heads over a bf16 cache
    (3, 8, 128, (512, 2048), BF16),   # Llama-3.2's 24/8: 22 MiB of the 32
    (2, 4, 256, (512, 1024), BF16),   # Gemma3's 256-wide heads
])
def test_gqa_prefill_kernel_compiles_unrolled_one_product_ahead(
        one_chip, G, KV, hd, geometry, cache_type):
    """Groups of up to four heads at the geometry the wrapper gives them:
    a static unroll written one score product ahead (PR 45), so two heads'
    [bq, bk] float32 tiles are alive where one was, inside the limit the
    kernel asks for."""
    from vnsum_tpu.ops import flash_attention

    assert flash_attention._heads_ahead(G) == 1
    assert flash_attention._block_geometry(2048, 8448, G, hd) == geometry
    if cache_type == I8:
        cache = _int8_cache(2, 2, KV, 8448, hd)
    else:
        cache = {"k": ((2, 2, KV, 8448, hd), BF16),
                 "v": ((2, 2, KV, 8448, hd), BF16)}
    c = _compiled(
        lambda q, cache, pads, win: flash_attention.flash_prefill_attention(
            q, cache, 1, pads, G, win, 6144),
        one_chip, ((2, 2048, G * KV, hd), BF16), cache, ((2,), I32), ((), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("G,offset", [(6, 6144), (9, 0), (9, 6144)])
def test_gqa_prefill_kernel_compiles_at_lagunas_two_groups(one_chip, G,
                                                           offset):
    """48/8 and 72/8 heads of 128 (groups of 6 and 9: looped two heads a
    step, an odd head out at 9; (1024, 1024) tiles), a 2,048-query chunk
    over the int8 cache of 8,448 slots under the 512 window: one program
    holds both calls."""
    from vnsum_tpu.ops import flash_attention

    assert flash_attention._block_geometry(2048, 8448, G, 128) == (1024, 1024)
    c = _compiled(
        lambda q, cache, pads, win: flash_attention.flash_prefill_attention(
            q, cache, 2, pads, G, win, offset),
        one_chip, ((2, 2048, 8 * G, 128), BF16),
        _int8_cache(5, 2, 8, 8448, 128), ((2,), I32), ((), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("G", [6, 9])
def test_gqa_decode_kernel_compiles_at_lagunas_two_groups(one_chip, G):
    """6 and 9 query rows a KV head over the int8 cache, 12 rows."""
    from vnsum_tpu.ops.decode_attention import flash_decode_attention

    c = _compiled(
        lambda q, cache, pads, win: flash_decode_attention(
            q, cache, 3, pads, 8200, G, win),
        one_chip, ((12, 1, 8 * G, 128), BF16),
        _int8_cache(5, 12, 8, 8448, 128), ((12,), I32), ((), I32))
    assert "tpu_custom_call" in c.as_text()


def test_verify_kernel_compiles_at_the_served_segments_shape(one_chip):
    """A slot segment's step: four slots of 8,320, one query a row at its
    own fill, 32/8 heads over the int8 cache of 36 layers — one row a block
    of 512 slots (`decode_block_k`), from the row's pad to its fill."""
    from vnsum_tpu.ops import decode_attention

    assert decode_attention.decode_block_k(8, 128, 1, 8320) == 512
    c = _compiled(
        lambda q, cache, pads, fills: (
            decode_attention.flash_spec_verify_attention(
                q, cache, 35, pads, fills, 4)),
        one_chip, ((4, 1, 32, 128), BF16), _int8_cache(36, 4, 8, 8320, 128),
        ((4,), I32), ((4,), I32))
    assert "tpu_custom_call" in c.as_text()


def test_gqa_decode_kernel_compiles_at_g7_under_a_window(one_chip):
    """7 query rows a KV head — the first group size here that is no
    divisor of the 8-sublane tile — over the int8 cache, 24 rows."""
    from vnsum_tpu.ops.decode_attention import flash_decode_attention

    c = _compiled(
        lambda q, cache, pads, win: flash_decode_attention(
            q, cache, 3, pads, 8200, 7, win),
        one_chip, ((24, 1, 28, 128), BF16), _int8_cache(4, 24, 4, 8448, 128),
        ((24,), I32), ((), I32))
    assert "tpu_custom_call" in c.as_text()


# -- the Granite-4.0-H family: the scan kernels, the GQA kernels at hd 64 ----


@pytest.mark.parametrize("tile", [2, 1])
@pytest.mark.parametrize("offset", [0, 6144])
def test_gqa_prefill_kernel_compiles_at_64_wide_heads(one_chip, offset, tile):
    """32/8 heads of 64, a 2,048-query chunk of the S=8192 bucket over the
    int8 cache of the 4 attention layers, 24 rows: the cache as
    ``init_kv_cache`` lays it out, two KV heads a lane tile (a cell takes
    its head's half of the pair's tile by a lane slice Mosaic has to take),
    and one a tile, blocks of the array's own width (what an odd KV count
    or a tensor axis that splits the pairs falls back to)."""
    from vnsum_tpu.ops import flash_attention

    assert flash_attention.head_dim_supported(64)
    assert not flash_attention.head_dim_supported(96)
    cache = _int8_cache(4, 24, 8, 8448, 64, tile)
    assert cache["k"][0] == (4, 24, 8 // tile, 8448, 64 * tile)
    c = _compiled(
        lambda q, cache, pads: flash_attention.flash_prefill_attention(
            q, cache, 1, pads, 4, None, offset),
        one_chip, ((24, 2048, 32, 64), BF16), cache, ((24,), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("tile", [2, 1])
def test_gqa_decode_kernel_compiles_at_64_wide_heads(one_chip, tile):
    """Two KV heads a tile: 4 tiles of 128 in blocks of 1,024 slots, a
    tile's 8 rows two heads' groups; and the fallback, one a tile."""
    from vnsum_tpu.ops.decode_attention import flash_decode_attention

    c = _compiled(
        lambda q, cache, pads: flash_decode_attention(
            q, cache, 3, pads, 8200, 4),
        one_chip, ((24, 1, 32, 64), BF16),
        _int8_cache(4, 24, 8, 8448, 64, tile), ((24,), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("Sq", [1, 5])
def test_gqa_verify_kernel_compiles_over_paired_heads(one_chip, Sq):
    """llama3.2-1b's slot segment (one query a row) and speculative verify
    (k = 4) over 8 KV heads of 64, two a tile: the limits' rows pair off as
    the queries' do."""
    from vnsum_tpu.ops.decode_attention import flash_spec_verify_attention

    c = _compiled(
        lambda q, cache, pads, fills: flash_spec_verify_attention(
            q, cache, 3, pads, fills, 4),
        one_chip, ((8, Sq, 32, 64), BF16), _int8_cache(16, 8, 8, 8320, 64),
        ((8,), I32), ((8,), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("offset", [0, 6144])
def test_gqa_prefill_kernel_compiles_for_lfm2s_row_piece(one_chip, offset):
    """LFM2-8B-A1B's geometry: 32/8 rotary QK-normed heads of 64, a row
    piece of FOUR rows of a 2,048-query chunk (``cache_rows``) over the
    int8 cache of its 6 attention layers, 24 rows of 8,448 slots."""
    from vnsum_tpu.ops import flash_attention

    c = _compiled(
        lambda q, cache, pads, rows: flash_attention.flash_prefill_attention(
            q, cache, 5, pads, 4, None, offset, rows),
        one_chip, ((4, 2048, 32, 64), BF16), _int8_cache(6, 24, 8, 8448, 64),
        ((4,), I32), ((4,), I32))
    assert "tpu_custom_call" in c.as_text()


def test_gqa_decode_kernel_compiles_over_lfm2s_six_layers(one_chip):
    from vnsum_tpu.ops.decode_attention import flash_decode_attention

    c = _compiled(
        lambda q, cache, pads: flash_decode_attention(
            q, cache, 5, pads, 8200, 4),
        one_chip, ((24, 1, 32, 64), BF16), _int8_cache(6, 24, 8, 8448, 64),
        ((24,), I32))
    assert "tpu_custom_call" in c.as_text()


def _scan_shapes(B, S):
    """x, dt, A, B, C, D at Granite-4.0-H-Micro's widths: 64 heads of 64, a
    state of 128."""
    seq = (S,) if S else ()
    return (((B, *seq, 64, 64), BF16), ((B, *seq, 64), F32), ((64,), F32),
            ((B, *seq, 128), BF16), ((B, *seq, 128), BF16), ((64,), F32))


def test_prefill_scan_kernel_compiles_at_the_cells_shapes(one_chip):
    """A 2,048-token prefill chunk of 24 rows in scan chunks of 256 over the
    stacked float32 state of 36 layers, written in place: the x and y
    blocks of [256, 4096], the row's state in, out and in scratch, and the
    lane tiles sliced by a traced index, within the VMEM the kernel asks
    for."""
    from vnsum_tpu.ops import ssd_scan

    c = _compiled(
        lambda x, dt, A, Bm, Cm, D, state, pads: ssd_scan.ssd_prefill_scan(
            x, dt, A, Bm, Cm, D, state, 7, pads, chunk=256),
        one_chip, *_scan_shapes(24, 2048), ((36, 24, 128, 4096), F32),
        ((24,), I32))
    assert "tpu_custom_call" in c.as_text()
    assert ssd_scan.VMEM_LIMIT_BYTES <= 64 * 1024 * 1024


@pytest.mark.parametrize("R", [1, 2])
def test_prefill_scan_kernel_compiles_for_a_row_piece_in_place(one_chip, R):
    """A piece of R rows of the 24 (PR 51: ``rows``, a third prefetched
    vector steering the state's block to ``(layer, rows[b])``): the same
    kernel at the piece's shapes, and with the stacked state donated the
    compiled program holds no second copy of its 1.81 GB."""
    from vnsum_tpu.ops import ssd_scan

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (*_scan_shapes(R, 2048), ((36, 24, 128, 4096), F32),
                         ((R,), I32), ((R,), I32))]
    c = jax.jit(
        lambda x, dt, A, Bm, Cm, D, state, pads, rows:
        ssd_scan.ssd_prefill_scan(x, dt, A, Bm, Cm, D, state, 7, pads, rows,
                                  chunk=256),
        donate_argnums=(6,)).lower(*args).compile()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 256 * 1024 * 1024


def test_decode_update_kernel_compiles_in_place_at_the_cells_shapes(one_chip):
    """One token for 24 rows: the layer's 2 MiB state blocks read and
    written in place. With the stacked state donated the compiled program
    holds no second copy of its 1.81 GB."""
    from vnsum_tpu.ops import ssd_scan

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (*_scan_shapes(24, 0), ((36, 24, 128, 4096), F32))]
    c = jax.jit(
        lambda x, dt, A, Bm, Cm, D, state: ssd_scan.ssm_decode_update(
            x, dt, A, Bm, Cm, D, state, 7),
        donate_argnums=(6,)).lower(*args).compile()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


# -- the Nemotron-H family (PR 47) ---------------------------------------------


@pytest.mark.parametrize("G,chunk", [(1, 256), (8, 128), (8, 256)])
def test_scan_kernels_compile_at_one_group_and_at_eight(one_chip, G, chunk):
    """Both scan kernels with B and C as [B, S, G, N]: Granite's one group
    at its chunk of 256 and Nemotron-H's eight groups of eight heads (four
    lane tiles a group; the group's rows of B^T and lanes of C sliced by a
    traced index) at the published chunk of 128 and at 256, a 2,048-token
    prefill chunk of 12 rows over the stacked state of 7 layers in place,
    and the one-token update."""
    from vnsum_tpu.ops import ssd_scan

    x, dt, A, _, _, D = _scan_shapes(12, 2048)
    bc = ((12, 2048, G, 128), BF16)
    state = ((7, 12, 128, 4096), F32)
    c = _compiled(
        lambda x, dt, A, Bm, Cm, D, state, pads: ssd_scan.ssd_prefill_scan(
            x, dt, A, Bm, Cm, D, state, 3, pads, chunk=chunk),
        one_chip, x, dt, A, bc, bc, D, state, ((12,), I32))
    assert "tpu_custom_call" in c.as_text()
    x, dt, A, _, _, D = _scan_shapes(12, 0)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (x, dt, A, ((12, G, 128), BF16), ((12, G, 128), BF16),
                         D, state)]
    c = jax.jit(
        lambda x, dt, A, Bm, Cm, D, state: ssd_scan.ssm_decode_update(
            x, dt, A, Bm, Cm, D, state, 3),
        donate_argnums=(6,)).lower(*args).compile()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


# (layers, experts held, hidden, expert width as stored, column tiles)
_NEMOTRON_H = (7, 128, 2688, 1920, (640, 896))


@pytest.mark.parametrize("tm,tiles", [(256, 321), (32, 73)])
def test_single_product_expert_form_compiles_at_the_stored_width(one_chip, tm,
                                                                 tiles):
    """Nemotron-H's two products of an expert with no gate — relu2 in the
    kernel after ONE product, then the down product — on int8 rows at the
    prefill's and the decode's row tile (a piece of 8,192 tokens x 6 picks
    over 128 experts; a decode step of 12 rows), at the 1,920 the experts
    are stored at. No copy of the stack: its last dim is whole lanes."""
    from vnsum_tpu.models.experts import _column_tile
    from vnsum_tpu.ops.expert_matmul import expert_grouped_matmul

    L, E, D, F, column_tiles = _NEMOTRON_H
    M = tm * tiles
    up = {"q": ((L, E, D, F), I8), "s": ((L, E, F), F32)}
    down = {"q": ((L, E, F, D), I8), "s": ((L, E, D), F32)}
    sched = (((tiles,), I32), ((1,), I32))
    c = _compiled(
        lambda x, xs, w, te, nu: expert_grouped_matmul(
            x, xs, w, None, 2, te, nu, tm=tm, tn=_column_tile(D, F),
            out_dtype=BF16, act="relu2"),
        one_chip, ((M, D), I8), ((M, 1), F32), up, *sched)
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024
    c = _compiled(
        lambda x, xs, w, te, nu: expert_grouped_matmul(
            x, xs, w, None, 2, te, nu, tm=tm, tn=_column_tile(F, D),
            out_dtype=BF16),
        one_chip, ((M, F), I8), ((M, 1), F32), down, *sched)
    assert "tpu_custom_call" in c.as_text()
    assert (_column_tile(D, F), _column_tile(F, D)) == column_tiles


def test_a_stack_stored_1856_wide_is_copied_whole_at_every_call(one_chip):
    """Why the experts are stored padded: at the published 1,856 columns
    (14.5 lane tiles) the whole-width block compiles, but XLA copies the
    stack into a padded layout for the call — the temporaries are the
    stack's own size."""
    from vnsum_tpu.models.experts import _column_tile
    from vnsum_tpu.ops.expert_matmul import expert_grouped_matmul

    L, E, D, F = 2, 128, 2688, 1856
    assert _column_tile(D, F) == F
    up = {"q": ((L, E, D, F), I8), "s": ((L, E, F), F32)}
    c = _compiled(
        lambda x, xs, w, te, nu: expert_grouped_matmul(
            x, xs, w, None, 1, te, nu, tm=32, tn=F, out_dtype=BF16,
            act="relu2"),
        one_chip, ((32 * 73, D), I8), ((32 * 73, 1), F32), up,
        ((73,), I32), ((1,), I32))
    assert c.memory_analysis().temp_size_in_bytes > L * E * D * F


@pytest.mark.parametrize("offset", [0, 6144])
def test_gqa_prefill_kernel_compiles_at_g16_on_two_kv_heads(one_chip, offset):
    """Nemotron-H's 32 query heads on 2 KV heads of 128 over the int8
    cache of its 2 attention layers, 12 rows: the looped group at the
    (1024, 1024) tile, the scales' block the array's own 2 KV heads."""
    from vnsum_tpu.ops import flash_attention

    assert flash_attention._block_geometry(2048, 8448, 16, 128) == (1024, 1024)
    c = _compiled(
        lambda q, cache, pads, win: flash_attention.flash_prefill_attention(
            q, cache, 1, pads, 16, win, offset),
        one_chip, ((12, 2048, 32, 128), BF16), _int8_cache(2, 12, 2, 8448, 128),
        ((12,), I32), ((), I32))
    assert "tpu_custom_call" in c.as_text()


def test_gqa_decode_kernel_compiles_at_g16_on_two_kv_heads(one_chip):
    """16 query rows a KV head over the int8 cache, 12 rows."""
    from vnsum_tpu.ops.decode_attention import flash_decode_attention

    c = _compiled(
        lambda q, cache, pads, win: flash_decode_attention(
            q, cache, 1, pads, 8200, 16, win),
        one_chip, ((12, 1, 32, 128), BF16),
        _int8_cache(2, 12, 2, 8448, 128), ((12,), I32), ((), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("offset", [0, 6144])
def test_gqa_prefill_kernel_compiles_at_one_query_head_a_kv_head(one_chip,
                                                                 offset):
    """Ouro-2.6B's 16 query heads on 16 KV heads of 128 over the int8 cache
    of a four-pass loop — 48 cache layers, the last pass's last layer read —
    as the engine calls it: ONE row's 2,048-token piece of an 8-row batch
    (``cache_rows``), at the geometry the wrapper gives G = 1."""
    from vnsum_tpu.ops import flash_attention

    assert flash_attention._block_geometry(2048, 8448, 1, 128) == (1024, 1024)
    c = _compiled(
        lambda q, cache, pads, win, rows:
        flash_attention.flash_prefill_attention(
            q, cache, 47, pads, 1, win, offset, rows),
        one_chip, ((1, 2048, 16, 128), BF16),
        _int8_cache(48, 8, 16, 8448, 128), ((1,), I32), ((), I32),
        ((1,), I32))
    assert "tpu_custom_call" in c.as_text()


def test_gqa_decode_kernel_compiles_at_one_query_head_a_kv_head(one_chip):
    """One query row a KV head, 16 KV heads, 8 rows, over the 48 cache
    layers of a four-pass loop: a K/V block holds one row's 16 heads over
    ``decode_block_k`` slots."""
    from vnsum_tpu.ops.decode_attention import (
        decode_block_k,
        flash_decode_attention,
    )

    assert decode_block_k(16, 128, 1, 8448) == 512   # 1 MiB of keys: PR 50
    c = _compiled(
        lambda q, cache, pads, win: flash_decode_attention(
            q, cache, 47, pads, 8200, 1, win),
        one_chip, ((8, 1, 16, 128), BF16),
        _int8_cache(48, 8, 16, 8448, 128), ((8,), I32), ((), I32))
    assert "tpu_custom_call" in c.as_text()


# -- the Ling-3.0-flash family (ops/kda_scan.py; the latent kernels at 32
# heads with no compressed query) ------------------------------------------


@pytest.mark.parametrize("R,S,rows", [
    (4, 2048, True),    # the map dispatch's row piece: four rows of a chunk
    (1, 2048, False),   # parity's chunk: one row, the whole state
    (4, 1024, True),    # the smoke phase's chunk
])
def test_kda_prefill_scan_compiles_at_the_published_widths(one_chip, R, S,
                                                           rows):
    """A head a lane tile of the [rows, tokens, 32 x 128] arrays, scan
    chunks of 64 in sub-blocks of 16, token blocks of 1,024, the head's
    [128, 128] float32 state in VMEM, the cell's stacked state of 10 layers
    x 24 rows written in place at a piece's own rows: the float32 products
    of the 64 x 64 inverse and the transposed state update are what Mosaic
    might refuse — and, of what the kernel makes for itself, the gate from
    a bfloat16 tile and a [2, 128] block of ``exp(A_log)`` over ``dt_bias``,
    the running sum's rolls along the sublanes, a head's column of the
    [1,024, 32] float32 block of beta."""
    from vnsum_tpu.ops import kda_scan

    head = ((R, S, 32, 128), BF16)
    args = [head, head, head, head, ((R, S, 32), F32),
            ((10, 24, 32, 128, 128), F32), ((R,), I32), ((32,), F32),
            ((32, 128), F32)]
    static = dict(lower_bound=-5.0, chunk=64)
    if rows:
        c = _compiled(
            lambda q, k, v, a, b, st, pads, A, dt, rows:
            kda_scan.kda_prefill_scan(
                q, k, v, a, b, st, 7, pads, rows, A_log=A, dt_bias=dt,
                **static),
            one_chip, *args, ((R,), I32))
    else:
        c = _compiled(
            lambda q, k, v, a, b, st, pads, A, dt: kda_scan.kda_prefill_scan(
                q, k, v, a, b, st[:, :R], 7, pads, A_log=A, dt_bias=dt,
                **static),
            one_chip, *args)
    assert "tpu_custom_call" in c.as_text()
    assert kda_scan.VMEM_LIMIT_BYTES <= 64 * 1024 * 1024


@pytest.mark.parametrize("B", [24, 4, 1])
def test_kda_decode_update_compiles_in_place_at_the_cells_shapes(one_chip, B):
    """A row's 32 states of one layer a grid step (2 MiB in, 2 MiB out,
    double-buffered), the heads unrolled, a head's beta v and output as
    [128, 1] columns of [128, 32] blocks."""
    from vnsum_tpu.ops import kda_scan

    row = ((B, 32, 128), BF16)
    c = _compiled(
        lambda q, k, v, g, b, st: kda_scan.kda_decode_update(
            q, k, v, g, b, st, 3),
        one_chip, row, row, row, ((B, 32, 128), F32), ((B, 32), F32),
        ((10, B, 32, 128, 128), F32))
    assert "tpu_custom_call" in c.as_text()


def _copies(text, dtype):
    """Element counts of the ``copy`` instructions of ``dtype`` results."""
    return [math.prod(int(d) for d in dims.split(","))
            for dims in re.findall(
                rf" = {dtype}\[([\d,]+)\]\S* copy\(", text)]


def _root_slices(text, result):
    """ROOT ``dynamic-slice`` instructions whose result starts ``result``:
    fusions that do nothing but copy a slice out of their operand."""
    return re.findall(
        rf"ROOT \S+ = {re.escape(result)}\S* dynamic-slice\(", text)


def _kda_layers(decode_steps=None):
    """Ling's ten KDA mixers at the cell's widths, scanned over the stacked
    int8 group as ``models/ling.py::forward`` hands it (the stored 4-D
    parameters in, ``_lane_views`` once before the scans, a layer's slice
    taken where it is used); a token loop of ``decode_steps`` around the
    scan, or a row piece with ``cache_rows``. Returns (the function, the
    group's shapes, the state's)."""
    import functools

    from vnsum_tpu.models import ling
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = ling.LingConfig(n_layers=12, experts_held=128, w8a8_prefill=True)
    kda = jax.eval_shape(functools.partial(init_params_quantized, cfg=cfg),
                         jax.random.key(0))["kda"]
    group = jax.tree.map(lambda x: (x.shape, x.dtype), kda)
    state = {"kda": ((cfg.n_kda, 24, 32, 128, 128), F32),
             "conv": ((cfg.n_kda, 24, 3, 3 * 4096), BF16)}

    def layers(kda, x, valid, cache, rows=None):
        kda = ling._lane_views(kda)

        def step(carry, i):
            x, cache = carry
            lp = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
                w, i, 0, keepdims=False), kda)
            u = ling._rmsnorm(x, lp["mixer_norm"], cfg.norm_eps)
            out, cache = ling._kda_mixer(u, lp, i, valid, cache, cfg, True,
                                         False, rows)
            return (x + out.astype(x.dtype), cache), None

        def token(t, carry):
            return jax.lax.scan(step, carry, jnp.arange(cfg.n_kda))[0]

        if decode_steps is None:
            return token(0, (x, cache))
        return jax.lax.fori_loop(0, decode_steps, token, (x, cache))

    return layers, group, state


def test_a_kda_row_piece_re_tiles_no_float32_array(one_chip):
    """The row piece [4, 2048, 2560] with ``cache_rows``: the compiled text
    holds no ``copy`` of a float32 array of 4 x 2048 x 4096 elements. On the
    tree before PR 62 it holds three a layer body, ``copy
    f32[1024,8,32,128]{3,2,1,0:T(8,128)}`` of a bitcast of the
    ``f32[4,2048,4096]{2,1,0:T(8,128)}`` convolution outputs (q's and k's
    unit norms) and of the scan's output (the head norm): a reduction over a
    head's channels on the ``[B, S, H, hd]`` reshape draws that shape's own
    tiling (8 heads x 128 lanes) where the array lies (8 tokens x 128
    lanes). Necessary, not sufficient: XLA undid the same views inside the
    WHOLE program until ``_head_tiles`` pinned their layout
    (``test_lings_map_program_...`` below is the one that decides)."""
    layers, group, state = _kda_layers()
    c = _compiled(layers, one_chip, group, ((4, 2048, 2560), BF16),
                  ((4, 2048), jnp.bool_), state, ((4,), I32))
    text = c.as_text()
    assert "kda_prefill_scan" in text
    assert 4 * 2048 * 4096 not in _copies(text, "f32")
    # nor do the four projections' outputs turn (parent: four
    # ``copy bf16[4,32,256,8,128]`` a body) or a layer's weight leave its
    # stack (parent: four root slices + ``copy s8[1,2560,32,128]``)
    assert 4 * 2048 * 4096 not in _copies(text, "bf16")
    assert not _root_slices(text, "s8[1,2560,")


def test_a_kda_decode_step_reads_its_projections_in_place(one_chip):
    """The [24, 1, 2560] step inside a token loop: no fusion's ROOT is a
    ``dynamic-slice`` with an ``s8[1,2560,`` result and no ``copy`` of an
    int8 array of 2560 x 4096 elements. On the tree before PR 62 the loop's
    body holds three of each, ``ROOT dynamic-slice
    s8[1,2560,32,128]{...S(1)}`` (``wq``, ``wk``, ``wv``: 10.5 MB out of
    the stack a projection, layer and step) and ``copy
    s8[320,8,32,128]{3,1,2,0}`` at the head of the product's fusion (the
    stored (32 heads x 128) tile turned to (32 rows of D x 128 lanes))."""
    layers, group, state = _kda_layers(decode_steps=256)
    c = _compiled(layers, one_chip, group, ((24, 1, 2560), BF16),
                  ((24, 1), jnp.bool_), state)
    text = c.as_text()
    assert "kda_decode_update" in text
    assert not _root_slices(text, "s8[1,2560,")
    assert 2560 * 4096 not in _copies(text, "s8")


def test_lings_map_program_re_tiles_nothing_of_the_kda_mixers(one_chip):
    """The cell's (24, 8192, 256) one-shot program whole, from shapes alone:
    what the two tests above hold a scanned mixer to, held where it counts.
    Before PR 62 this text has 36 ``copy f32[1024,8,32,128]`` (three a KDA
    body, twelve bodies: four prefill chunks of three scanned blocks), 48
    ``copy bf16[4,32,256,8,128]`` and nine root slices of an
    ``s8[1,2560,32,128]`` with as many copies; a tree with the views but
    without their layout pinned kept all 36 (XLA's simplifier folds the two
    transposes around the element-wise work back into the 4-D reshape).
    What is left to move is the four stacks' views, once a call of
    ``forward`` outside every loop (``reshape s8[10,2560,4096]``)."""
    config, c = _map_program(one_chip, "ling-3.0-flash-ep4-l12-int8.json",
                             "engine_setup_ling")
    text = c.as_text()
    kernels = set(re.findall(r"/(\w+)/pallas_call", text))
    assert {"kda_prefill_scan", "kda_decode_update"} <= kernels
    assert 4 * 2048 * 4096 not in _copies(text, "f32")
    assert 4 * 2048 * 4096 not in _copies(text, "bf16")
    assert not _root_slices(text, "s8[1,2560,")
    assert 2560 * 4096 not in _copies(text, "s8")
    m = c.memory_analysis()
    # 9.18 GB of weights; 3.60 GB of temporaries as before the views (the
    # four stacks' are made and dropped a call of ``forward``)
    assert m.temp_size_in_bytes < 3.75e9
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 0.80 * 16 * 1024 ** 3


@pytest.mark.parametrize("R,S,offset", [
    (4, 2048, 6144),   # the map dispatch's last chunk, a piece of four rows
    (4, 2048, 0),      # its first
    (1, 2048, 6144),   # parity's last chunk
])
def test_latent_prefill_kernel_compiles_at_32_heads(one_chip, R, S, offset):
    """As ``models/ling.py`` calls it: 32 heads in groups of 8, the queries
    of a 2,048-token chunk, a piece's latent rows gathered into a one-layer
    stack of their own (layer 0, first row 0) of the cell's 8,448 slots."""
    from vnsum_tpu.ops.mla_attention import mla_prefill_attention

    H = 32
    c = _compiled(
        lambda qn, qr, lat, wk, wv, p: mla_prefill_attention(
            qn, qr, lat, wk, wv, p, scale=192 ** -0.5, q_offset=offset,
            layer_idx=0, row_offset=0),
        one_chip, ((R, H, S, 128), BF16), ((R, H, S, 64), BF16),
        ((1, R, 8448, 576), BF16), ((H, 512, 128), BF16),
        ((H, 512, 128), BF16), ((R,), I32))
    assert "tpu_custom_call" in c.as_text()


def test_absorbed_decode_kernel_compiles_at_32_heads_over_two_layers(
        one_chip):
    from vnsum_tpu.ops.mla_attention import mla_decode_attention

    B, H = 24, 32
    c = _compiled(
        lambda ql, qr, cache, pads: mla_decode_attention(
            ql, qr, cache, 1, pads, 8200, scale=192 ** -0.5, rank=512),
        one_chip, ((B, H, 512), BF16), ((B, H, 64), BF16),
        ((2, B, 8448, 576), BF16), ((B,), I32))
    assert "tpu_custom_call" in c.as_text()


# -- the Brumby family (ops/power_retention.py; no attention kernel) ----------


@pytest.mark.parametrize("R,S,rows", [
    (1, 2048, True),    # the map dispatch's row piece: one row of a chunk
    (1, 2048, False),   # parity's chunk: one row, the whole state
    (2, 1000, True),    # a ragged call: not a whole number of chunks
])
def test_retention_prefill_scan_compiles_at_the_published_widths(one_chip, R,
                                                                 S, rows):
    """A KV head's five query heads as five lane tiles of the [rows, tokens,
    40 x 128] array stacked in VMEM, retention chunks of 256, the KV head's
    [65, 128, 128] float32 state and [128, 128] normaliser in VMEM scratch,
    the cell's stacked state of 10 layers x 12 rows written in place at a
    piece's own rows: a lane rotation by a traced amount, a scratch tile
    taken by a traced index, seven tiles' phi side by side in one product
    and the transposed decayed values are what Mosaic might refuse."""
    from vnsum_tpu.ops import power_retention as pr

    args = [((R, S, 40, 128), BF16), ((R, S, 8, 128), BF16),
            ((R, S, 8, 128), BF16), ((R, S, 8), F32),
            ((10, 12, 8, 65, 128, 128), F32), ((10, 12, 8, 128, 128), F32),
            ((R,), I32)]
    how = dict(chunk=256, scale=128 ** -0.5, eps=1e-6)
    if rows:
        c = _compiled(
            lambda q, k, v, g, st, z, pads, rows: pr.retention_prefill_scan(
                q, k, v, g, st, z, 7, pads, rows, **how),
            one_chip, *args, ((R,), I32))
    else:
        c = _compiled(
            lambda q, k, v, g, st, z, pads: pr.retention_prefill_scan(
                q, k, v, g, st[:, :R], z[:, :R], 7, pads, **how),
            one_chip, *args)
    assert "tpu_custom_call" in c.as_text()
    assert pr.VMEM_LIMIT_BYTES <= 64 * 1024 * 1024
    # nothing of phi's size lies around the kernel (phi(k) alone would be
    # 133 MB a thousand tokens and row): its temporaries are the running
    # gate sum by columns and by rows, a ragged call's padded copies of q, k
    # and v and, without rows, the state's slice
    if rows:
        assert c.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


@pytest.mark.parametrize("B", [12, 4, 1])
def test_retention_decode_update_compiles_in_place_at_the_cells_shapes(
        one_chip, B):
    """The stacked state stays in HBM and a grid step (a row, a KV head)
    holds its [65, 128, 128] block of one layer in one of two VMEM buffers
    (4.3 MB a transfer, 8.5 MB of scratch) by the kernel's own copies:
    slices of a 4.15 GB operand by three traced indices, a buffer taken by a
    traced index; the five query heads' read of 13 new tiles is one
    [8, 1664] x [128, 1664]^T product."""
    from vnsum_tpu.ops import power_retention as pr

    c = _compiled(
        lambda q, k, v, g, st, z: pr.retention_decode_update(
            q, k, v, g, st, z, 3, scale=128 ** -0.5, eps=1e-6),
        one_chip, ((B, 40, 128), BF16), ((B, 8, 128), BF16),
        ((B, 8, 128), BF16), ((B, 8), F32),
        ((10, B, 8, 65, 128, 128), F32), ((10, B, 8, 128, 128), F32))
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 8 * 1024 * 1024


def _map_program(one_chip, config_file, setup_module):
    """(the configuration file, its (batch, 8192, 256) one-shot program
    compiled for the chip from shapes alone: the weights a tree of shapes)."""
    import functools
    import importlib
    import json
    import types
    from pathlib import Path

    from benchmarks import engine_setup
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig
    from vnsum_tpu.models.quant import init_params_quantized

    root = Path(__file__).resolve().parents[1]
    config = json.loads(
        (root / "benchmarks" / "configs" / config_file).read_text())
    cfg = importlib.import_module(
        f"benchmarks.{setup_module}").model_config(config, False)
    params = jax.eval_shape(
        functools.partial(init_params_quantized, cfg=cfg), jax.random.key(0))
    B, S, new = config["engine"]["batch"], 8192, 256

    class OnTheChip(TpuBackend):
        def _devices(self):
            return [types.SimpleNamespace(platform="tpu")]

    be = OnTheChip(model_config=cfg, tokenizer="byte", params=params,
                   batch_size=B, max_new_tokens=new,
                   generation=GenerationConfig(temperature=1.0, seed=1),
                   **engine_setup.backend_kwargs(config, False))
    spec = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    return config, be._make_fn(B, S, new, be.gen_cfg).lower(
        jax.tree.map(lambda x: spec(x.shape, x.dtype), params),
        spec((B, S), I32), spec((B,), I32), spec((), jnp.uint32)).compile()


def test_the_cells_map_program_holds_no_phi_and_no_keys_and_values(one_chip):
    """The (12, 8192, 256) one-shot program of the cell's configuration,
    compiled for the chip from shapes alone (the weights a tree of shapes):
    its arguments are the int8 weights, its temporaries the float32 state
    of 10 layers x 12 rows and a 2,048-token row piece's activations — no
    array of ``phi(k)``'s or ``phi(q)``'s size (13 GB and 66 GB a layer at
    this dispatch) and no keys and values. The compiler's two byte counts
    are held to bands: to the digit they move with any kernel's scratch and
    with the compiler (``engine_notes`` quotes one machine's)."""
    config, c = _map_program(one_chip, "brumby-14b-l10-int8.json",
                             "engine_setup_brumby")
    m = c.memory_analysis()
    state = 12 * config["bytes"]["state_and_normaliser_a_row_and_layer"] * 10
    assert config["bytes"]["weights"] <= m.argument_size_in_bytes \
        < config["bytes"]["weights"] + 4 * 1024 * 1024
    # the state, and under a GiB of a row piece's activations beside it
    assert state < m.temp_size_in_bytes < state + 1024 ** 3
    # the kernels by their calls' own names: the text ends in tables of
    # every function the PROCESS has traced, other tests' kernels among
    # them (a worker that had compiled ``mla_decode_attention`` failed a
    # search of the whole text: ROADMAP D20)
    kernels = set(re.findall(r"/(\w+)/pallas_call", c.as_text()))
    assert kernels == {"retention_prefill_scan", "retention_decode_update"}
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 0.62 * 16 * 1024 ** 3


# -- the Keye family: selection and attention over a selection -------------------


def _keye_caches(L=12, B=8, C=16640):
    return {"k": ((L, B, 4, C, 128), I8), "v": ((L, B, 4, C, 128), I8),
            "ks": ((L, B, 4, C), F32), "vs": ((L, B, 4, C), F32),
            "ki": ((L, B, 64, C), BF16)}


@pytest.mark.parametrize("S,offset,C", [
    (2048, 14336, 16640),   # the map dispatch's last chunk: a row piece
    (2048, 0, 16640),       # its first
    (1024, 10240, 16392),   # parity's cache: blocks end past it
])
def test_dsa_prefill_kernels_compile_at_the_published_widths(one_chip, S,
                                                             offset, C):
    """A row piece's selection — 16 indexer heads of 64 against the
    transposed indexer cache, a 256-query tile's int32 keys over every slot
    in VMEM scratch (17.8 MB), the bisection's dynamic trip counts and its
    conditional second bisection, an int8 mask written by dynamic lane
    slices — and the masked attention at 8 query heads a KV head over the
    int8 cache, in place at a row of the batch's state."""
    from vnsum_tpu.ops import sparse_attention as sa

    cache = _keye_caches(C=C)
    Cp = -(-C // sa._KEY_BLOCK) * sa._KEY_BLOCK
    c = _compiled(
        lambda q, w, cache, pads, rows: sa.dsa_index_select(
            q, w, cache, 7, pads, offset, rows, topk=2048),
        one_chip, ((1, S, 16, 64), BF16), ((1, S, 16), F32), cache,
        ((1,), I32), ((1,), I32))
    assert "tpu_custom_call" in c.as_text()
    # no copy of a cache stands beside the kernel: the indexer cache alone
    # is 204 MB, and [L, B, C, 64] cost a 409 MB re-tiling a call
    assert c.memory_analysis().temp_size_in_bytes < 16 * 1024 * 1024
    c = _compiled(
        lambda q, cache, m, pads, rows: sa.dsa_prefill_attention(
            q, cache, 7, m, pads, offset, rows),
        one_chip, ((1, S, 32, 128), BF16), cache, ((1, S, Cp), I8),
        ((1,), I32), ((1,), I32))
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024
    assert sa.VMEM_LIMIT_BYTES <= 64 * 1024 * 1024


@pytest.mark.parametrize("B,C", [(8, 16640), (4, 2304), (1, 16392)])
def test_dsa_decode_kernels_compile_at_the_cells_shapes(one_chip, B, C):
    """A decode step's selection (the rows on the sublanes of one tile, a
    fill that is a traced scalar) and the masked walk over every KV head of
    a block in one step."""
    from vnsum_tpu.ops import sparse_attention as sa

    cache = _keye_caches(B=B, C=C)
    Cp = -(-C // sa._DECODE_KEY_BLOCK) * sa._DECODE_KEY_BLOCK
    for fn, shapes in (
            (lambda q, w, cache, pads, fill: sa.dsa_index_select_decode(
                q, w, cache, 3, pads, fill, topk=2048),
             (((B, 16, 64), BF16), ((B, 16), F32))),
            (lambda q, m, cache, pads, fill: sa.dsa_decode_attention(
                q, cache, 3, m, pads, fill),
             (((B, 32, 128), BF16), ((B, Cp), I32)))):
        c = _compiled(fn, one_chip, *shapes, cache, ((B,), I32), ((), I32))
        assert "tpu_custom_call" in c.as_text()
        assert c.memory_analysis().temp_size_in_bytes < 8 * 1024 * 1024


def test_keyes_map_program_carries_three_caches_and_fits_the_chip(one_chip):
    """The (8, 16384, 256) one-shot program of the cell's configuration,
    compiled for the chip from shapes alone: its arguments are the int8
    weights, its temporaries hold the KV cache, the indexer-key cache and a
    2,048-token row piece's activations (the selection's int8 mask 35 MB
    among them); the kernels it calls are the family's three and the expert
    product's two."""
    import functools
    import importlib
    import json
    import types
    from pathlib import Path

    from benchmarks import engine_setup
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.core.config import GenerationConfig
    from vnsum_tpu.models.quant import init_params_quantized

    root = Path(__file__).resolve().parents[1]
    config = json.loads((root / "benchmarks" / "configs"
                         / "keye-vl-2.0-l12-int8.json").read_text())
    cfg = importlib.import_module(
        "benchmarks.engine_setup_keye").model_config(config, False)
    params = jax.eval_shape(
        functools.partial(init_params_quantized, cfg=cfg), jax.random.key(0))
    B, S, new = config["engine"]["batch"], 16384, 256

    class OnTheChip(TpuBackend):
        def _devices(self):
            return [types.SimpleNamespace(platform="tpu")]

    be = OnTheChip(model_config=cfg, tokenizer="byte", params=params,
                   batch_size=B, max_new_tokens=new,
                   generation=GenerationConfig(temperature=1.0, seed=1),
                   **engine_setup.backend_kwargs(config, False))
    spec = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    c = be._make_fn(B, S, new, be.gen_cfg).lower(
        jax.tree.map(lambda x: spec(x.shape, x.dtype), params),
        spec((B, S), I32), spec((B,), I32), spec((), jnp.uint32)).compile()
    m = c.memory_analysis()
    b = config["bytes"]
    assert b["weights"] <= m.argument_size_in_bytes \
        < b["weights"] + 4 * 1024 * 1024
    caches = b["kv_cache"] + b["indexer_cache"]
    # the caches, and under a GiB of a row piece's activations beside them
    assert caches < m.temp_size_in_bytes < caches + 1024 ** 3
    text = c.as_text()
    kernels = set(re.findall(r"/(\w+)/pallas_call", text))
    assert kernels == {"dsa_index_select", "dsa_prefill_attention",
                       "dsa_decode_attention", "expert_grouped_matmul",
                       "expert_combine"}
    # the four leaves a parity check alone reads (models/keye.py
    # ``row_record``) are dead in this program: the compiler carries none
    for dead in ("s8[12,8,16640]", "f32[12,8,16640]", "bf16[12,8,16,64]",
                 "f32[12,8,16]{"):
        assert dead not in text, dead
    assert "bf16[12,8,64,16640]" in text          # the indexer keys are
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 0.7 * 16 * 1024 ** 3
