"""Pallas decode-attention kernel vs the dense cache attention (interpret
mode on CPU; the kernel's semantics must match _attention with the decode
mask pad_b <= j <= fill)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vnsum_tpu.models.llama import _attention, decode_attention_mask
from vnsum_tpu.ops.decode_attention import flash_decode_attention, supports_decode


def make_case(L, B, KV, C, H, hd, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (B, 1, H, hd), jnp.float32)
    k_all = jax.random.normal(kk, (L, B, KV, C, hd), jnp.float32)
    v_all = jax.random.normal(kv, (L, B, KV, C, hd), jnp.float32)
    return q, {"k": k_all, "v": v_all}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("fill,pads", [(37, [0, 5]), (63, [0, 0]), (8, [3, 8])])
def test_decode_kernel_matches_dense(layer, fill, pads):
    L, B, KV, C, H, hd = 3, 2, 2, 64, 4, 128
    q, cache = make_case(L, B, KV, C, H, hd, seed=layer)
    pad = jnp.asarray(pads, jnp.int32)

    mask = decode_attention_mask(pad, fill, C)
    dense = _attention(q, cache["k"][layer], cache["v"][layer], mask, H // KV)
    kernel = flash_decode_attention(
        q, cache, layer, pad, fill, H // KV, block_k=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(kernel), rtol=2e-5, atol=2e-5
    )


def test_decode_kernel_ignores_past_fill_garbage():
    """Slots past fill must not leak in even if they hold huge values."""
    L, B, KV, C, H, hd = 1, 1, 1, 32, 2, 128
    q, cache = make_case(L, B, KV, C, H, hd, seed=7)
    fill = 9
    poisoned = {
        "k": cache["k"].at[:, :, :, fill + 1 :, :].set(30.0),  # huge scores
        "v": cache["v"].at[:, :, :, fill + 1 :, :].set(1e9),
    }
    pad = jnp.zeros((B,), jnp.int32)
    clean = flash_decode_attention(
        q, cache, 0, pad, fill, H // KV, block_k=8, interpret=True
    )
    poisoned = flash_decode_attention(
        q, poisoned, 0, pad, fill, H // KV, block_k=8,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(clean), np.asarray(poisoned))


@pytest.mark.parametrize("H,KV", [(4, 2), (28, 4)])
@pytest.mark.parametrize("win,fill", [(1, 37), (8, 37), (16, 8), (64, 37)])
def test_decode_windowed_matches_dense(win, fill, H, KV):
    """Sliding-window decode: kernel vs dense with the slot-space window
    (k_slot > fill - win), including win > fill (window not yet binding);
    at 4/2 heads and at SmallThinker's 28/4 (7 query rows a KV head: the
    first group size that is no divisor of the 8-sublane tile)."""
    L, B, C, hd = 1, 2, 64, 128
    q, cache = make_case(L, B, KV, C, H, hd, seed=13)
    pad = jnp.asarray([0, 3], jnp.int32)
    mask = decode_attention_mask(pad, fill, C) & (
        jnp.arange(C)[None, None, :] > fill - win
    )
    dense = _attention(q, cache["k"][0], cache["v"][0], mask, H // KV)
    kernel = flash_decode_attention(
        q, cache, 0, pad, fill, H // KV, jnp.int32(win),
        block_k=16, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(kernel), rtol=2e-5, atol=2e-5
    )


def test_decode_windowed_ignores_below_window_garbage():
    """Below-window slots must not leak in even with huge values — they are
    DMA-clamped away, not just masked."""
    L, B, KV, C, H, hd = 1, 1, 1, 64, 2, 128
    q, cache = make_case(L, B, KV, C, H, hd, seed=7)
    fill, win = 40, 8
    poisoned = {
        "k": cache["k"].at[:, :, :, : fill - win + 1, :].set(30.0),
        "v": cache["v"].at[:, :, :, : fill - win + 1, :].set(1e9),
    }
    pad = jnp.zeros((B,), jnp.int32)
    clean = flash_decode_attention(
        q, cache, 0, pad, fill, H // KV, jnp.int32(win),
        block_k=8, interpret=True,
    )
    dirty = flash_decode_attention(
        q, poisoned, 0, pad, fill, H // KV, jnp.int32(win),
        block_k=8, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(clean), np.asarray(dirty))


def quantize_case(cache):
    """Round-trip the float case through the int8 cache format."""
    from vnsum_tpu.models.llama import _quantize_kv

    k8, ks = jax.vmap(_quantize_kv)(cache["k"])  # vmap over L
    v8, vs = jax.vmap(_quantize_kv)(cache["v"])
    return {"k": k8, "v": v8, "ks": ks, "vs": vs}


@pytest.mark.parametrize("fill,pads", [(37, [0, 5]), (8, [3, 8])])
def test_decode_kernel_int8_cache_matches_dequantized_dense(fill, pads):
    """The in-kernel dequant (scores x ks, probs x vs) must equal dense
    attention over the explicitly dequantized cache."""
    from vnsum_tpu.models.llama import dequantize_cache_layer

    L, B, KV, C, H, hd = 2, 2, 2, 64, 4, 128
    q, cache = make_case(L, B, KV, C, H, hd, seed=11)
    qcache = quantize_case(cache)
    pad = jnp.asarray(pads, jnp.int32)

    kd, vd = dequantize_cache_layer(qcache, 1)
    mask = decode_attention_mask(pad, fill, C)
    dense = _attention(q, kd, vd, mask, H // KV)
    kernel = flash_decode_attention(
        q, qcache, 1, pad, fill, H // KV, block_k=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(kernel), rtol=2e-5, atol=2e-5
    )


def test_prefill_kernel_int8_cache_matches_dequantized_dense():
    from vnsum_tpu.models.llama import (
        dequantize_cache_layer,
        prefill_attention_mask,
    )
    from vnsum_tpu.ops.flash_attention import flash_prefill_attention

    L, B, S, C, KV, H, hd = 2, 2, 32, 48, 2, 4, 128
    kq = jax.random.key(21)
    q = jax.random.normal(kq, (B, S, H, hd), jnp.float32)
    _, cache = make_case(L, B, KV, C, H, hd, seed=21)
    qcache = quantize_case(cache)
    pad = jnp.asarray([0, 7], jnp.int32)

    kd, vd = dequantize_cache_layer(qcache, 0)
    mask = prefill_attention_mask(pad, S, C)
    dense = _attention(q, kd, vd, mask, H // KV)
    flash = flash_prefill_attention(
        q, qcache, 0, pad, H // KV, block_q=16, block_k=16, interpret=True
    )
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(dense)[b, int(pad[b]):],
            np.asarray(flash)[b, int(pad[b]):],
            rtol=2e-5, atol=2e-5,
        )


def test_int8_cache_quantization_roundtrip_accuracy():
    """Per-(token, head) scales keep relative error ~1/127."""
    from vnsum_tpu.models.llama import _quantize_kv

    x = jax.random.normal(jax.random.key(3), (2, 4, 16, 128), jnp.float32) * 5
    q8, s = _quantize_kv(x)
    deq = q8.astype(jnp.float32) * s[..., None]
    err = jnp.abs(deq - x).max() / jnp.abs(x).max()
    assert float(err) < 1.5 / 127


def test_supports_decode():
    assert supports_decode(1152, 128)
    assert supports_decode(1152, 64)      # half a lane tile (Granite-4.0-H)
    assert not supports_decode(1152, 96)  # neither whole lane tiles nor half
    assert supports_decode(1151, 128)     # any C via ceil-div grid


def test_engine_decode_kernel_path_matches_dense_cpu():
    """Engine with the decode kernel forced on (interpret path not available
    in-engine; emulate by comparing forward() with/without stacked fn)."""
    from vnsum_tpu.models import init_kv_cache, init_params, tiny_llama
    from vnsum_tpu.models.llama import forward, prefill_positions

    cfg = tiny_llama(max_seq_len=64)
    params = init_params(jax.random.key(0), cfg)
    B, S, C = 2, 8, 16
    tokens = jnp.ones((B, S), jnp.int32)
    pad = jnp.asarray([0, 2], jnp.int32)
    cache = init_kv_cache(cfg, B, C)
    from vnsum_tpu.models.llama import prefill_attention_mask

    logits, cache = forward(
        params, cfg, tokens, prefill_positions(pad, S), cache, 0,
        prefill_attention_mask(pad, S, C), last_only=True,
    )
    cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    t = 0
    mask_t = decode_attention_mask(pad, S + t, C)
    pos = (S - pad) + t

    def stacked(q, cache, layer_idx):
        return flash_decode_attention(
            q, cache, layer_idx, pad, S + t, cfg.q_per_kv,
            block_k=8, interpret=True,
        )

    ref, _ = forward(params, cfg, cur[:, None], pos[:, None], cache, S + t, mask_t)
    got, _ = forward(
        params, cfg, cur[:, None], pos[:, None], cache, S + t, mask_t,
        stacked_attention_fn=stacked,
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=2e-4, atol=2e-4)


def test_decode_return_partials_normalize_to_direct():
    """return_partials exposes the unnormalized (o, m, l) state; o/l must
    equal the kernel's own normalized output (the long-context LSE merge
    depends on this contract)."""
    L, B, KV, C, H, hd = 2, 2, 2, 64, 4, 128
    q, cache = make_case(L, B, KV, C, H, hd, seed=17)
    pad = jnp.asarray([0, 5], jnp.int32)
    fill = 40
    direct = flash_decode_attention(
        q, cache, 1, pad, fill, H // KV, block_k=16, interpret=True
    )
    o, m, l = flash_decode_attention(
        q, cache, 1, pad, fill, H // KV, block_k=16, interpret=True,
        return_partials=True,
    )
    assert o.shape == (B, H, hd) and m.shape == l.shape == (B, H)
    normalized = o / np.maximum(np.asarray(l), 1e-30)[..., None]
    np.testing.assert_allclose(
        normalized, np.asarray(direct)[:, 0], rtol=2e-5, atol=2e-5
    )


def test_decode_partials_fully_masked_rows_are_inert():
    """A row whose pad covers the whole cache (an empty shard in the
    long-context merge) must come back with l=0 so the cross-shard merge
    ignores it."""
    L, B, KV, C, H, hd = 1, 2, 1, 32, 2, 128
    q, cache = make_case(L, B, KV, C, H, hd, seed=3)
    pad = jnp.asarray([0, 32], jnp.int32)  # row 1: everything padded out
    o, m, l = flash_decode_attention(
        q, cache, 0, pad, 31, H // KV, block_k=8, interpret=True,
        return_partials=True,
    )
    assert np.asarray(l)[1].max() == 0.0
    assert np.asarray(l)[0].min() > 0.0


def test_prefill_kernel_int8_cache_bf16_queries_close_to_f32():
    """The PRODUCTION prefill configuration — bf16 queries against the int8
    quantized cache — must track the f32-query/dequantized-dense oracle to
    bf16 rounding. Guards the quantized+bf16 interaction specifically: the
    in-kernel order is (scores x ks) and (p x vs) in f32 BEFORE p drops to
    bf16 for the PV dot; applying vs after the cast, or casting the f32
    scales themselves, would pass the f32-only parity tests but corrupt
    this path (code-review finding, round 5)."""
    from vnsum_tpu.models.llama import (
        dequantize_cache_layer,
        prefill_attention_mask,
    )
    from vnsum_tpu.ops.flash_attention import flash_prefill_attention

    L, B, S, C, KV, H, hd = 2, 2, 32, 48, 2, 4, 128
    q = jax.random.normal(jax.random.key(33), (B, S, H, hd), jnp.float32)
    _, cache = make_case(L, B, KV, C, H, hd, seed=33)
    qcache = quantize_case(cache)
    pad = jnp.asarray([0, 5], jnp.int32)

    kd, vd = dequantize_cache_layer(qcache, 1)
    mask = prefill_attention_mask(pad, S, C)
    oracle = _attention(q, kd, vd, mask, H // KV)
    flash_bf16 = flash_prefill_attention(
        q.astype(jnp.bfloat16), qcache, 1, pad, H // KV,
        block_q=16, block_k=16, interpret=True,
    )
    assert flash_bf16.dtype == jnp.bfloat16
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(oracle, np.float32)[b, int(pad[b]):],
            np.asarray(flash_bf16, np.float32)[b, int(pad[b]):],
            rtol=0.05, atol=0.05,
        )


# -- multi-position verify kernel (speculative decoding) ---------------------


def make_verify_case(L, B, KV, C, Sq, H, hd, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (B, Sq, H, hd), jnp.float32)
    k_all = jax.random.normal(kk, (L, B, KV, C, hd), jnp.float32)
    v_all = jax.random.normal(kv, (L, B, KV, C, hd), jnp.float32)
    return q, {"k": k_all, "v": v_all}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize(
    "fills,pads", [([10, 40], [0, 5]), ([58, 12], [3, 0]), ([7, 7], [2, 2])]
)
def test_verify_kernel_matches_dense(layer, fills, pads):
    """flash_spec_verify_attention vs _attention under the verify mask:
    per-row fills, multiple query positions per row."""
    from vnsum_tpu.models.llama import verify_attention_mask
    from vnsum_tpu.ops.decode_attention import flash_spec_verify_attention

    L, B, KV, C, Sq, H, hd = 3, 2, 2, 64, 5, 4, 128
    q, cache = make_verify_case(L, B, KV, C, Sq, H, hd, seed=layer)
    pad = jnp.asarray(pads, jnp.int32)
    fill = jnp.asarray(fills, jnp.int32)

    mask = verify_attention_mask(pad, fill, Sq, C)
    dense = _attention(q, cache["k"][layer], cache["v"][layer], mask, H // KV)
    kernel = flash_spec_verify_attention(
        q, cache, layer, pad, fill, H // KV, block_k=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(kernel), rtol=2e-5, atol=2e-5
    )


def test_verify_kernel_ignores_beyond_limit_garbage():
    """Slots past each row's per-query limit must not leak in — including
    slots BETWEEN two rows' differing fills (the rollback region)."""
    from vnsum_tpu.ops.decode_attention import flash_spec_verify_attention

    L, B, KV, C, Sq, H, hd = 1, 2, 1, 32, 3, 2, 128
    q, cache = make_verify_case(L, B, KV, C, Sq, H, hd, seed=9)
    fills = jnp.asarray([6, 20], jnp.int32)
    pad = jnp.zeros((B,), jnp.int32)
    # poison row 0 beyond ITS visibility (limit 6+3-1=8) but inside row 1's
    poisoned = {
        "k": cache["k"].at[:, 0, :, 9:, :].set(30.0),
        "v": cache["v"].at[:, 0, :, 9:, :].set(1e9),
    }
    clean = flash_spec_verify_attention(
        q, cache, 0, pad, fills, H // KV, block_k=8, interpret=True
    )
    dirty = flash_spec_verify_attention(
        q, poisoned, 0, pad, fills, H // KV, block_k=8, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(clean)[0], np.asarray(dirty)[0]
    )


def test_verify_kernel_int8_cache_matches_dequantized_dense():
    from vnsum_tpu.models.llama import (
        _quantize_kv,
        dequantize_cache_layer,
        verify_attention_mask,
    )
    from vnsum_tpu.ops.decode_attention import flash_spec_verify_attention

    L, B, KV, C, Sq, H, hd = 2, 2, 2, 64, 4, 4, 128
    q, cache = make_verify_case(L, B, KV, C, Sq, H, hd, seed=3)
    k8, ks = jax.vmap(_quantize_kv)(cache["k"])
    v8, vs = jax.vmap(_quantize_kv)(cache["v"])
    qcache = {"k": k8, "v": v8, "ks": ks, "vs": vs}
    pad = jnp.asarray([0, 4], jnp.int32)
    fills = jnp.asarray([30, 55], jnp.int32)

    kd, vd = dequantize_cache_layer(qcache, 1)
    mask = verify_attention_mask(pad, fills, Sq, C)
    dense = _attention(q, kd.astype(q.dtype), vd.astype(q.dtype), mask, H // KV)
    kernel = flash_spec_verify_attention(
        q, qcache, 1, pad, fills, H // KV, block_k=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(kernel), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("win", [4, 16])
def test_verify_kernel_windowed_matches_dense(win):
    """Sliding-window verify: per-query window floor (fill_b + s - win)."""
    from vnsum_tpu.models.llama import verify_attention_mask
    from vnsum_tpu.ops.decode_attention import flash_spec_verify_attention

    L, B, KV, C, Sq, H, hd = 1, 2, 2, 64, 3, 4, 128
    q, cache = make_verify_case(L, B, KV, C, Sq, H, hd, seed=5)
    pad = jnp.asarray([0, 2], jnp.int32)
    fills = jnp.asarray([20, 44], jnp.int32)

    limit = fills[:, None] + jnp.arange(Sq)[None, :]
    mask = verify_attention_mask(pad, fills, Sq, C) & (
        jnp.arange(C)[None, None, :] > (limit[:, :, None] - win)
    )
    dense = _attention(q, cache["k"][0], cache["v"][0], mask, H // KV)
    kernel = flash_spec_verify_attention(
        q, cache, 0, pad, fills, H // KV, window=jnp.int32(win),
        block_k=16, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(kernel), rtol=2e-5, atol=2e-5
    )


# -- partial tail block (cache_len % block_k != 0) ---------------------------


@pytest.mark.parametrize("quantized", [False, True])
def test_partial_tail_block_cannot_poison_the_output(quantized):
    """Past the end of the cache a partial tail block holds whatever was in
    VMEM (the interpreter fills it with NaN; the chip leaves stale bytes,
    seen there as NaN rows). Scores of those slots are masked, but their
    zero probability must not meet the garbage in a multiply. All three
    kernels, float and int8 caches."""
    from vnsum_tpu.models.llama import (
        dequantize_cache_layer,
        prefill_attention_mask,
        verify_attention_mask,
    )
    from vnsum_tpu.ops.decode_attention import flash_spec_verify_attention
    from vnsum_tpu.ops.flash_attention import flash_prefill_attention

    L, B, KV, C, H, hd = 1, 2, 2, 40, 4, 128  # 40 % 16 == 8: a partial tail
    q, cache = make_verify_case(L, B, KV, C, C, H, hd, seed=21)
    if quantized:
        cache = quantize_case(cache)
    kd, vd = dequantize_cache_layer(cache, 0)
    pad = jnp.asarray([0, 3], jnp.int32)
    G = H // KV

    def close(kernel, dense, rows=slice(None)):
        kernel = np.asarray(kernel)
        assert np.isfinite(kernel[:, rows]).all()
        np.testing.assert_allclose(
            np.asarray(dense)[:, rows], kernel[:, rows], rtol=2e-5, atol=2e-5
        )

    # decode at the last slot: the tail block is computed
    close(
        flash_decode_attention(q[:, :1], cache, 0, pad, C - 1, G,
                               block_k=16, interpret=True),
        _attention(q[:, :1], kd, vd, decode_attention_mask(pad, C - 1, C), G),
    )
    # verify with fills that reach into the tail block
    fills = jnp.asarray([C - 3, C - 9], jnp.int32)
    close(
        flash_spec_verify_attention(q[:, :3], cache, 0, pad, fills, G,
                                    block_k=16, interpret=True),
        _attention(q[:, :3], kd, vd,
                   verify_attention_mask(pad, fills, 3, C), G),
    )
    # prefill over the whole cache: the last query block sees the tail block
    close(
        flash_prefill_attention(q, cache, 0, pad, G, block_q=16, block_k=16,
                                interpret=True),
        _attention(q, kd, vd, prefill_attention_mask(pad, C, C), G),
        rows=slice(3, None),  # pad rows are garbage on both paths
    )


# -- one row a block, from the row's first real slot to its fill (PR 49) -----

# the served mix's first four prompts in 8,192-slot rows, a sixteenth the size
_SERVED_PADS = [(8192 - n) // 16 for n in (6000, 7260, 2000, 540)]

# name: rows' pads, rows' fills, window, KV heads, query heads a KV head,
# head dim, int8 cache, queries a row; blocks of 32 slots in a cache of 528
_ROW_CASES = {
    "all_live": ([0, 0, 0, 0], [520] * 4, 0, 2, 2, 128, False, 1),
    "served_mix": (_SERVED_PADS, [520] * 4, 0, 2, 2, 128, True, 1),
    # 40 = block 1's slot 8; 95 = block 2's last slot; 96 = block 3's first
    "pad_ends_inside_a_block": ([40, 95, 96, 0], [300] * 4, 0, 2, 2, 128,
                                False, 1),
    "all_pad_but_the_last_slot": ([300, 0, 511, 0], [300, 300, 511, 511], 0,
                                  2, 2, 128, True, 1),
    # window floor 300 - 100 + 1 = 201: pads 150 and 180 (below it), 201,
    # 230 and 290 (above it)
    "pads_under_a_window": ([150, 230, 201, 290], [300] * 4, 100, 2, 2, 128,
                            False, 1),
    "pads_under_a_window_int8": ([180, 230, 0, 290], [300] * 4, 100, 4, 7,
                                 128, True, 1),
    "ragged_fills": ([40, 0, 250, 96], [300, 95, 260, 511], 0, 2, 2, 128,
                     False, 3),
    "ragged_fills_window_int8": ([40, 0, 250, 96], [300, 95, 260, 511], 64,
                                 2, 2, 128, True, 3),
    "hd64_int8": ([0, 130, 40, 299], [300] * 4, 0, 2, 4, 64, True, 1),
    "hd64_bf16": ([0, 130, 40, 299], [300] * 4, 0, 2, 4, 64, False, 2),
}


def _row_case(name, dtype=jnp.float32):
    from vnsum_tpu.models.llama import dequantize_cache_layer

    pads, fills, win, KV, G, hd, int8, Sq = _ROW_CASES[name]
    B, C, L = len(pads), 528, 2
    q, cache = make_verify_case(L, B, KV, C, Sq, KV * G, hd, seed=len(name))
    cache = {k: v.astype(dtype) for k, v in cache.items()}
    if int8:
        cache = quantize_case(cache)
    kd, vd = dequantize_cache_layer(cache, 1)
    return (q, cache, kd.astype(jnp.float32), vd.astype(jnp.float32),
            jnp.asarray(pads, jnp.int32), jnp.asarray(fills, jnp.int32),
            win, G, Sq, C)


def _window_mask(mask, limits, win, C):
    if not win:
        return mask
    return mask & (jnp.arange(C)[None, None, :] > limits[:, :, None] - win)


@pytest.mark.parametrize("name", sorted(_ROW_CASES))
def test_verify_kernel_reads_each_row_from_its_pad_to_its_fill(name):
    """``flash_spec_verify_attention`` (a slot segment's step where a row
    holds one query) against the dense reference, with the cache poisoned
    under every row's pad and past its last query: a block the row's bounds
    left out, or a slot its mask let through, shows."""
    from vnsum_tpu.models.llama import verify_attention_mask
    from vnsum_tpu.ops.decode_attention import flash_spec_verify_attention

    q, cache, kd, vd, pads, fills, win, G, Sq, C = _row_case(name)
    limits = fills[:, None] + jnp.arange(Sq)[None, :]
    mask = _window_mask(verify_attention_mask(pads, fills, Sq, C), limits,
                        win, C)
    dense = _attention(q, kd, vd, mask, G)
    slot = jnp.arange(C)[None, None, :, None]
    dead = ((slot < pads[:, None, None, None])
            | (slot > limits[:, -1][:, None, None, None]))
    dirty = dict(cache)
    for leaf, poison in (("k", 100), ("v", 100)):
        x = cache[leaf][1]
        dirty[leaf] = cache[leaf].at[1].set(
            jnp.where(dead, jnp.asarray(poison, x.dtype), x))
    kernel = flash_spec_verify_attention(
        q, dirty, 1, pads, fills, G, jnp.int32(win), block_k=32,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(kernel), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("partials", [False, True])
@pytest.mark.parametrize(
    "name", [n for n, c in sorted(_ROW_CASES.items())
             if len(set(c[1])) == 1 and c[7] == 1])
def test_decode_kernel_reads_each_row_from_its_pad_to_the_fill(name, partials):
    """``flash_decode_attention`` on the same rows (one fill for all, one
    query a row), normalized and as the partial sums
    ``backend/long_context.py`` merges."""
    q, cache, kd, vd, pads, fills, win, G, _, C = _row_case(name)
    fill = int(fills[0])
    mask = _window_mask(decode_attention_mask(pads, fill, C),
                        jnp.full((len(pads), 1), fill), win, C)
    dense = _attention(q, kd, vd, mask, G)
    out = flash_decode_attention(
        q, cache, 1, pads, fill, G, jnp.int32(win), block_k=32,
        interpret=True, return_partials=partials,
    )
    if partials:
        o, m, l = out
        assert np.asarray(l).min() > 0.0
        out = (o / l[..., None])[:, None]
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(out), rtol=2e-5, atol=2e-5
    )


def test_bf16_cache_at_hd64_matches_dense():
    """A bfloat16 cache of 64-wide heads, two queries a row."""
    from vnsum_tpu.models.llama import verify_attention_mask
    from vnsum_tpu.ops.decode_attention import flash_spec_verify_attention

    q, cache, kd, vd, pads, fills, win, G, Sq, C = _row_case(
        "hd64_bf16", jnp.bfloat16)
    assert cache["k"].dtype == jnp.bfloat16
    dense = _attention(q, kd, vd, verify_attention_mask(pads, fills, Sq, C), G)
    kernel = flash_spec_verify_attention(
        q, cache, 1, pads, fills, G, block_k=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(kernel), rtol=2e-5, atol=2e-5
    )


def test_a_rows_blocks_are_those_between_its_pad_and_its_fill():
    """``_row_blocks``, the one rule the index maps and the kernel's guard
    share: by hand on blocks of 32."""
    from vnsum_tpu.ops.decode_attention import _row_blocks

    def blocks(pad, fill, n_q=1, win=0):
        lo, hi = _row_blocks(jnp.int32(pad), jnp.int32(fill), n_q,
                             jnp.int32(win), 32, 528)
        return int(lo), int(hi)

    assert blocks(0, 520) == (0, 16)
    assert blocks(40, 300) == (1, 9)        # the pad ends inside block 1
    assert blocks(95, 300) == (2, 9)        # its last slot is block 2's last
    assert blocks(96, 300) == (3, 9)
    assert blocks(511, 511) == (15, 15)     # all pad but the last slot
    assert blocks(528, 527) == (16, 16)     # all pad: the last block, masked
    assert blocks(512, 300) == (16, 9)      # all pad below the fill: nothing
    assert blocks(150, 300, win=100) == (6, 9)   # floor 201 above the pad
    assert blocks(230, 300, win=100) == (7, 9)   # pad above the floor
    assert blocks(0, 300, n_q=3) == (0, 9)
    assert blocks(0, 318, n_q=3) == (0, 10)      # the last query's slot 320
    assert blocks(0, 527, n_q=3) == (0, 16)      # never past the cache's end


@pytest.mark.parametrize("cell,KV,hd,bk,kib", [
    ("qwen3 offline and served, laguna", 8, 128, 512, 512),
    ("phi-4", 10, 128, 512, 640),
    ("smallthinker", 4, 128, 1024, 512),
    # 64-wide heads fill half of each lane tile: 256 KiB of the cache are
    # 512 KiB as VMEM holds them (the sweep: 512 slots read 0.6873 / 0.6503
    # ms a call all-live / with the cell's pads, 1,024 read 0.6987 / 0.6647)
    ("granite-4.0-h", 8, 64, 512, 512),
    # 2 KV heads: 2,048 slots make the 512 KiB (it was 128 KiB at 4 rows)
    ("nemotron-h", 2, 128, 2048, 512),
])
def test_a_one_row_blocks_keys_hold_half_a_mebibyte_to_one(cell, KV, hd, bk,
                                                           kib):
    """The block rule at the seven cells' shapes (int8 caches of 8,448 and
    8,320 slots): a block's keys, as VMEM tiles them, lie in 512 KiB-1 MiB."""
    from vnsum_tpu.ops.decode_attention import decode_block_k

    for C in (8448, 8320):
        assert decode_block_k(KV, hd, 1, C) == bk
    assert KV * bk * max(hd, 128) == kib * 1024
    assert 512 <= kib <= 1024
    # a bfloat16 cache holds the same bytes in half the slots; a short cache
    # is one block
    assert decode_block_k(KV, hd, 2, 8448) * 2 == bk
    assert decode_block_k(KV, hd, 1, 200) == 200
