import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vnsum_tpu.models.llama import (
    _attention,
    _quantize_kv,
    prefill_attention_mask,
)
from vnsum_tpu.ops import flash_attention
from vnsum_tpu.ops.flash_attention import (
    flash_prefill_attention,
    prefill_block_classes,
    supports_flash,
)


def make_case(L, B, S, C, H, KV, hd, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (B, S, H, hd), jnp.float32)
    k_all = jnp.zeros((L, B, KV, C, hd), jnp.float32)
    v_all = jnp.zeros((L, B, KV, C, hd), jnp.float32)
    # fill only the prefill region like the engine does
    k_all = k_all.at[:, :, :, :S].set(
        jax.random.normal(kk, (L, B, KV, S, hd), jnp.float32)
    )
    v_all = v_all.at[:, :, :, :S].set(
        jax.random.normal(kv, (L, B, KV, S, hd), jnp.float32)
    )
    return q, {"k": k_all, "v": v_all}


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("pads", [[0, 0], [3, 17]])
def test_flash_matches_dense(layer, pads):
    L, B, S, C, H, KV, hd = 2, 2, 32, 64, 4, 2, 128
    q, cache = make_case(L, B, S, C, H, KV, hd, seed=layer)
    pad = jnp.asarray(pads, jnp.int32)
    mask = prefill_attention_mask(pad, S, C)
    dense = _attention(q, cache["k"][layer], cache["v"][layer], mask, H // KV)
    flash = flash_prefill_attention(
        q, cache, layer, pad, H // KV, interpret=True
    )
    # compare only non-pad rows (pad rows are garbage on both paths)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(dense)[b, pads[b] :],
            np.asarray(flash)[b, pads[b] :],
            rtol=2e-5,
            atol=2e-5,
        )


def test_flash_ragged_blocks():
    """S and C with NO large divisors: ceil-div grid + tail masking must
    still match dense (the old divisor-picker collapsed to 32-wide blocks
    at such shapes)."""
    L, B, S, C, H, KV, hd = 1, 1, 45, 61, 2, 1, 128
    q, cache = make_case(L, B, S, C, H, KV, hd, seed=3)
    pad = jnp.asarray([5], jnp.int32)
    mask = prefill_attention_mask(pad, S, C)
    dense = _attention(q, cache["k"][0], cache["v"][0], mask, H // KV)
    flash = flash_prefill_attention(
        q, cache, 0, pad, H // KV, block_q=16, block_k=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(dense)[0, 5:], np.asarray(flash)[0, 5:], rtol=2e-5, atol=2e-5
    )


def test_flash_multiple_k_blocks():
    L, B, S, C, H, KV, hd = 1, 1, 64, 192, 2, 1, 128
    q, cache = make_case(L, B, S, C, H, KV, hd, seed=3)
    pad = jnp.asarray([5], jnp.int32)
    mask = prefill_attention_mask(pad, S, C)
    dense = _attention(q, cache["k"][0], cache["v"][0], mask, H // KV)
    flash = flash_prefill_attention(
        q, cache, 0, pad, H // KV, block_q=32, block_k=64, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(dense)[0, 5:], np.asarray(flash)[0, 5:], rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("H,KV", [(2, 1), (28, 4)])
@pytest.mark.parametrize("win", [1, 8, 24])
def test_flash_windowed_matches_dense(win, H, KV):
    """Sliding-window clamp (Gemma local layers; SmallThinker's window
    layers at 28/4 heads, a group of 7): kernel vs the dense path's
    slot-space window mask (models.llama._in_window: k_slot > q_slot -
    window), on shapes where below-window whole blocks get clamped/elided."""
    L, B, S, C, hd = 1, 2, 45, 61, 128
    q, cache = make_case(L, B, S, C, H, KV, hd, seed=9)
    pads = [0, 5]
    pad = jnp.asarray(pads, jnp.int32)
    mask = prefill_attention_mask(pad, S, C)
    in_window = jnp.arange(C)[None, :] > jnp.arange(S)[:, None] - win
    dense = _attention(
        q, cache["k"][0], cache["v"][0], mask & in_window[None], H // KV
    )
    flash = flash_prefill_attention(
        q, cache, 0, pad, H // KV, jnp.int32(win),
        block_q=16, block_k=16, interpret=True,
    )
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(dense)[b, pads[b]:],
            np.asarray(flash)[b, pads[b]:],
            rtol=2e-5, atol=2e-5,
        )


def test_flash_window_zero_is_global():
    """window=0 must be bit-identical to the no-window call (global layers
    share the compiled program with sliding ones)."""
    L, B, S, C, H, KV, hd = 1, 1, 45, 61, 2, 1, 128
    q, cache = make_case(L, B, S, C, H, KV, hd, seed=4)
    pad = jnp.asarray([5], jnp.int32)
    a = flash_prefill_attention(
        q, cache, 0, pad, H // KV, block_q=16, block_k=16, interpret=True
    )
    b = flash_prefill_attention(
        q, cache, 0, pad, H // KV, jnp.int32(0),
        block_q=16, block_k=16, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_supports_flash():
    assert supports_flash(1024, 1152, 128)
    assert supports_flash(1001, 1153, 256)  # any S/C via ceil-div grids
    assert supports_flash(1024, 1152, 64)  # half a lane tile: Granite-4.0-H
    assert not supports_flash(1024, 1152, 96)  # no lane multiple, no half


def test_forward_remat_with_attention_fn():
    """remat must treat attention_fn as static, not a traced operand."""
    from vnsum_tpu.models import forward, init_kv_cache, init_params, tiny_llama
    from vnsum_tpu.models.llama import _attention, prefill_positions

    cfg = tiny_llama()
    params = init_params(jax.random.key(0), cfg)
    tokens = jnp.ones((1, 8), jnp.int32)
    pad = jnp.zeros((1,), jnp.int32)
    cache = init_kv_cache(cfg, 1, 8)
    mask = prefill_attention_mask(pad, 8, 8)
    logits, _ = forward(
        params, cfg, tokens, prefill_positions(pad, 8), cache, 0, mask,
        remat=True,
        attention_fn=lambda q, k, v, m, g: _attention(q, k, v, m, g),
    )
    assert bool(jnp.isfinite(logits).all())


def test_unsupported_head_dim_raises():
    L, B, S, C, H, KV, hd = 1, 1, 8, 16, 2, 1, 64
    q, cache = make_case(L, B, S, C, H, KV, hd)
    with pytest.raises(ValueError):
        flash_prefill_attention(
            q, cache, 0, jnp.zeros((1,), jnp.int32), 2
        )


@pytest.mark.parametrize("lo,hi", [(16, 32), (32, 45), (0, 16)])
def test_flash_q_offset_matches_full(lo, hi):
    """Chunked prefill: the kernel run on query slice [lo:hi) with
    q_offset=lo must reproduce the corresponding rows of the whole-prompt
    run (the cache already holds everything the chunk may attend to —
    exactly the state the engine's chunk loop produces)."""
    L, B, S, C, H, KV, hd = 2, 2, 45, 64, 4, 2, 128
    q, cache = make_case(L, B, S, C, H, KV, hd, seed=7)
    pad = jnp.asarray([0, 6], jnp.int32)
    full = flash_prefill_attention(
        q, cache, 1, pad, H // KV, block_q=16, block_k=16, interpret=True
    )
    chunk = flash_prefill_attention(
        q[:, lo:hi], cache, 1, pad, H // KV, None, jnp.int32(lo),
        block_q=16, block_k=16, interpret=True,
    )
    for b in range(2):
        valid = max(0, int(pad[b]) - lo)  # rows below the pad are garbage
        np.testing.assert_allclose(
            np.asarray(full)[b, lo + valid : hi],
            np.asarray(chunk)[b, valid:],
            rtol=2e-5, atol=2e-5,
        )


def test_flash_q_offset_with_window():
    """Sliding window + offset: chunk rows still see exactly the last
    `win` slots (slot-space window is offset-invariant)."""
    L, B, S, C, H, KV, hd = 1, 1, 40, 48, 2, 1, 128
    q, cache = make_case(L, B, S, C, H, KV, hd, seed=9)
    pad = jnp.asarray([0], jnp.int32)
    win = jnp.int32(8)
    full = flash_prefill_attention(
        q, cache, 0, pad, H // KV, win, block_q=8, block_k=8, interpret=True
    )
    lo, hi = 24, 40
    chunk = flash_prefill_attention(
        q[:, lo:hi], cache, 0, pad, H // KV, win, jnp.int32(lo),
        block_q=8, block_k=8, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(full)[0, lo:hi], np.asarray(chunk)[0],
        rtol=2e-5, atol=2e-5,
    )


def test_flash_bf16_compute_dtype_close_to_f32():
    """The kernel computes its dots in the QUERY dtype (f32 tests exact;
    the engine's bf16 gets the MXU full-rate path — the f32 in-kernel dots
    previously made attention 39% of prefill device time for ~18% of its
    FLOPs, earlier machine). bf16 inputs must stay within bf16
    rounding of the f32 oracle: f32 accumulation bounds the error at the
    input-rounding level (~1e-2), not O(sqrt(K)) growth."""
    L, B, S, C, H, KV, hd = 2, 2, 32, 64, 4, 2, 128
    q, cache = make_case(L, B, S, C, H, KV, hd, seed=5)
    pad = jnp.asarray([0, 3], jnp.int32)
    oracle = flash_prefill_attention(q, cache, 1, pad, H // KV, interpret=True)
    bf = flash_prefill_attention(
        q.astype(jnp.bfloat16),
        {k: v.astype(jnp.bfloat16) for k, v in cache.items()},
        1, pad, H // KV, interpret=True,
    )
    assert bf.dtype == jnp.bfloat16
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(oracle, np.float32)[b, int(pad[b]):],
            np.asarray(bf, np.float32)[b, int(pad[b]):],
            rtol=0.05, atol=0.05,
        )


def test_a_group_of_16_loops_its_heads_and_matches_dense():
    """G=16 (an MQA-like ratio): the group's heads go through the kernel's
    loop, the q tile and the softmax state carry 16 x bq rows, and the
    interpreted kernel still matches dense."""
    L, B, S, C, H, KV, hd = 1, 1, 16, 16, 16, 1, 128
    q, cache = make_case(L, B, S, C, H, KV, hd, seed=5)
    pad = jnp.zeros((B,), jnp.int32)
    mask = prefill_attention_mask(pad, S, C)
    dense = _attention(q, cache["k"][0], cache["v"][0], mask, H // KV)
    flash = flash_prefill_attention(
        q, cache, 0, pad, H // KV, interpret=True
    )
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-3, atol=2e-3)


def test_vmem_guard_rejects_explicit_overrides_with_geometry():
    """Explicit blocks that exceed the kernel's VMEM budget must raise a
    ValueError naming the geometry and the bytes instead of a Mosaic
    compile OOM."""
    L, B, S, C, H, KV, hd = 1, 1, 4096, 4096, 16, 1, 128
    q = jnp.zeros((B, S, H, hd), jnp.float32)
    cache = {
        "k": jnp.zeros((L, B, KV, C, hd), jnp.float32),
        "v": jnp.zeros((L, B, KV, C, hd), jnp.float32),
    }
    with pytest.raises(
        ValueError, match=r"scoped-VMEM.*G=16.*bq=1024, bk=2048 need \d+ bytes"
    ):
        flash_prefill_attention(
            q, cache, 0, jnp.zeros((B,), jnp.int32), H // KV,
            block_q=1024, block_k=2048,
        )


# (window, q_offset, block_k): C = 77 leaves a partial tail key block of 13
# slots at bk 16 and of 13 at bk 32; the rows carry pads 0 and 5
_G7_CASES = {
    "global": (0, 0, 16),
    "window": (24, 0, 16),
    "global_chunk": (0, 32, 32),
    "window_chunk": (24, 32, 16),
    "window_wide_keys": (40, 32, 32),
}


@pytest.mark.parametrize("case", sorted(_G7_CASES))
def test_flash_looped_heads_match_dense_at_g7(case):
    """SmallThinker's 28/4 heads: a group of 7 is wider than the kernel
    unrolls, so its heads go through the loop, each on its own rows of the
    scratch; against the dense path under no window and under one smaller
    than C, with a left pad, a chunk offset and a partial tail key block."""
    win, off, bk = _G7_CASES[case]
    assert flash_attention._heads_per_step(7) < 7
    L, B, S, C, H, KV, hd = 1, 2, 45, 77, 28, 4, 128
    q, cache = make_case(L, B, off + S, C, H, KV, hd, seed=13)
    q = q[:, off:]
    pads = [0, 5]
    pad = jnp.asarray(pads, jnp.int32)
    mask = prefill_attention_mask(pad, off + S, C)[:, off:]
    if win:
        mask = mask & (
            jnp.arange(C)[None, :] > off + jnp.arange(S)[:, None] - win
        )[None]
    dense = _attention(q, cache["k"][0], cache["v"][0], mask, H // KV)
    flash = flash_prefill_attention(
        q, cache, 0, pad, H // KV, jnp.int32(win), jnp.int32(off),
        block_q=16, block_k=bk, interpret=True,
    )
    for b in range(B):
        lo = max(0, pads[b] - off)
        np.testing.assert_allclose(
            np.asarray(dense)[b, lo:], np.asarray(flash)[b, lo:],
            rtol=2e-5, atol=2e-5,
        )


# -- block classes: dead / interior / edge ---------------------------------


@pytest.fixture(scope="module")
def all_edge_kernel():
    """The kernel at the parent commit's semantics: a second instance of the
    module in which every cell the causal/window rule lets through runs the
    masked body (no pad-dead cell, no interior cell) — what the kernel did
    before it classified its blocks. A second module instance, so no jit or
    trace cache is shared with the kernel under test."""
    spec = importlib.util.spec_from_file_location(
        "_flash_attention_all_edge", flash_attention.__file__
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rule = mod._block_class

    def all_edge(*args):
        seen, padded, interior = rule(*args)
        return seen, padded & False, interior & False

    mod._block_class = all_edge
    return mod.flash_prefill_attention


def _cache_as(cache, kind):
    """The f32 test cache as the engine would hold it: f32, bf16 or int8
    with per-(slot, head) scales, here rounded up to powers of two (see
    _unoptimized)."""
    if kind == "f32":
        return cache
    if kind == "bf16":
        return {n: a.astype(jnp.bfloat16) for n, a in cache.items()}
    out = {}
    for n in ("k", "v"):
        _, scales = _quantize_kv(cache[n])
        scales = jnp.exp2(jnp.ceil(jnp.log2(scales)))
        out[n] = jnp.clip(
            jnp.round(cache[n] / scales[..., None]), -127, 127
        ).astype(jnp.int8)
        out[n + "s"] = scales
    return out


def _unoptimized(fn, q, cache, layer, pad, q_per_kv, win, off, **static):
    """``fn`` compiled with the CPU backend's optimizer off. Optimized, the
    backend compiles the masked and the unmasked body differently, which
    moves a last bit in the INTERPRETED kernel and says nothing about the
    kernel on the chip (bit-equal there against the parent commit's file;
    PERF.md, PR 27). One difference survives the switch: a multiply and the
    subtract after it become one FMA where no select sits between them.
    The bit-identity cases therefore make ``dot * scale * ks`` exact — head
    size 256 (scale 1/16) and power-of-two int8 scales — so that the FMA
    rounds as the two operations do."""
    compiled = fn.lower(
        q, cache, layer, pad, q_per_kv, win, off, **static
    ).compile(compiler_options={"xla_backend_optimization_level": 0})
    return compiled(q, cache, layer, pad, win, off)


# (S, C, q_offset, window, block_q, block_k, pads); the kernel gets the
# queries [q_offset, q_offset + S) of a prompt whose cache is filled to there
_CLASS_CASES = {
    # pad under zero, one and several whole K blocks (and mid-block)
    "pads_0_1_3_blocks": (64, 96, 0, 0, 16, 16, [0, 16 + 5, 48 + 9]),
    # pad ends on a block's edge exactly: the next block is interior
    "pad_on_block_edge": (64, 96, 0, 0, 16, 16, [16, 32, 48]),
    # batch-bucketing filler rows (pad == S) beside a real one
    "filler_rows": (64, 96, 0, 0, 16, 16, [64, 7, 64]),
    # a later chunk wholly inside one row's pad, partly in another's
    "chunk_in_pad": (32, 128, 32, 0, 16, 16, [80, 40, 0]),
    "last_chunk_tail_rows": (32, 128, 64, 0, 16, 32, [90, 70, 3]),
    # partial tail block: C % bk != 0, and S % bq != 0
    "partial_tail_blocks": (45, 61, 0, 0, 16, 16, [0, 17, 45]),
    "wide_k_partial_tail": (64, 80, 0, 0, 16, 32, [0, 33, 64]),
    # window > 0 with a pad. A window of more than bq + bk - 1 slots holds
    # whole cells under the diagonal: interior, no mask built
    "window_with_pad": (64, 96, 0, 40, 16, 16, [0, 20, 64]),
    "window_chunk_with_pad": (32, 96, 32, 36, 16, 16, [0, 37, 64]),
    "window_wide_keys_with_pad": (64, 96, 0, 56, 16, 32, [0, 20, 64]),
    # a narrower one crosses every cell it reaches: all edge, as before
    "narrow_window_with_pad": (64, 96, 0, 24, 16, 16, [0, 20, 64]),
    "narrow_window_chunk_with_pad": (32, 96, 32, 8, 16, 16, [0, 37, 64]),
}


@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("case", sorted(_CLASS_CASES))
def test_block_classes_bit_identical_to_masking_every_block(
    case, kind, all_edge_kernel
):
    """Skipping the dead cells and dropping the mask in the interior ones —
    under a window too, where every slot of the cell is inside the last
    query row's window — must not change one bit of the output, pad rows
    (zeros) included."""
    S, C, off, win, bq, bk, pads = _CLASS_CASES[case]
    L, B, H, KV, hd = 2, len(pads), 4, 2, 256
    q, cache = make_case(L, B, off + S, C, H, KV, hd, seed=11)
    q = q[:, off:]
    pad = jnp.asarray(pads, jnp.int32)
    classes = prefill_block_classes(
        pads, S, C, off, win, H // KV, hd, block_q=bq, block_k=bk
    )
    assert classes["dead_pad"] > 0 or max(pads) == 0
    assert (classes["interior"] > 0) == (win == 0 or win > bq + bk - 1)

    cache = _cache_as(cache, kind)
    if kind != "f32":
        q = q.astype(jnp.bfloat16)
    args = (q, cache, jnp.int32(1), pad, H // KV, jnp.int32(win), jnp.int32(off))
    kw = dict(block_q=bq, block_k=bk, interpret=True)
    got, want = (
        np.asarray(_unoptimized(fn, *args, **kw).astype(jnp.float32))
        for fn in (flash_prefill_attention, all_edge_kernel)
    )
    assert not np.isnan(got).any()
    np.testing.assert_array_equal(got, want)
    for b, p in enumerate(pads):  # pad query rows come back as zeros
        assert not got[b, : max(0, min(S, p - off))].any()

    if kind == "f32":  # and the real rows are still the dense path's
        mask = prefill_attention_mask(pad, off + S, C)[:, off:]
        if win:
            mask = mask & (
                jnp.arange(C)[None, :] > off + jnp.arange(S)[:, None] - win
            )[None]
        dense = np.asarray(
            _attention(q, cache["k"][1], cache["v"][1], mask, H // KV)
        )
        for b, p in enumerate(pads):
            lo = max(0, min(S, p - off))
            np.testing.assert_allclose(
                dense[b, lo:], got[b, lo:], rtol=2e-5, atol=2e-5
            )
