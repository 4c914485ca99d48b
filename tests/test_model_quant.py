"""Weight-only int8 quantization: round-trip accuracy, forward fidelity, and
engine integration (models/quant.py)."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.core.config import GenerationConfig
from vnsum_tpu.models.llama import (
    forward_train,
    init_params,
    tiny_llama,
)
from vnsum_tpu.models.quant import (
    dequantize_params,
    is_quantized,
    quantize_params,
)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_llama()
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def test_round_trip_error_bounded(model):
    _, params = model
    qp = quantize_params(params)
    assert is_quantized(qp)
    deq = dequantize_params(qp)
    for name in ("wq", "wo", "w_down"):
        w = np.asarray(params["layers"][name], np.float32)
        d = np.asarray(deq["layers"][name])
        # per-channel int8: error bounded by half a quantization step
        step = np.abs(w).max() / 127.0
        assert np.abs(w - d).max() <= step * 0.51
    # norms pass through untouched
    np.testing.assert_array_equal(
        np.asarray(qp["layers"]["attn_norm"]),
        np.asarray(params["layers"]["attn_norm"]),
    )


def test_quantized_forward_close(model):
    cfg, params = model
    qp = quantize_params(params)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16), np.int32)
    )
    ref = np.asarray(forward_train(params, cfg, tokens, remat=False))
    quant = np.asarray(forward_train(qp, cfg, tokens, remat=False))
    # int8 weight-only should track full precision closely on logits
    cos = np.sum(ref * quant, -1) / (
        np.linalg.norm(ref, axis=-1) * np.linalg.norm(quant, axis=-1)
    )
    assert cos.min() > 0.999
    # greedy choice agreement on the vast majority of positions
    agree = (ref.argmax(-1) == quant.argmax(-1)).mean()
    assert agree > 0.9


def test_untied_lm_head_quantization():
    cfg = tiny_llama(tie_embeddings=False)
    params = init_params(jax.random.key(1), cfg)
    qp = quantize_params(params)
    assert "lm_head" in qp and is_quantized(qp)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 8), np.int32)
    )
    ref = np.asarray(forward_train(params, cfg, tokens, remat=False))
    quant = np.asarray(forward_train(qp, cfg, tokens, remat=False))
    assert np.corrcoef(ref.ravel(), quant.ravel())[0, 1] > 0.999


def test_engine_quantized_generation(model):
    cfg, _ = model
    backend = TpuBackend(
        model_config=cfg,
        tokenizer="byte",
        batch_size=2,
        max_new_tokens=8,
        quantize=True,
        flash=False,
        generation=GenerationConfig(temperature=0.0),
    )
    outs = backend.generate(["Xin chào Việt Nam.", "Quốc hội đã họp."])
    assert len(outs) == 2
    assert all(isinstance(o, str) for o in outs)
    # deterministic across calls (greedy, fixed seed)
    outs2 = backend.generate(["Xin chào Việt Nam.", "Quốc hội đã họp."])
    assert outs == outs2


def test_quantized_param_specs_match_tree():
    """The quantized PartitionSpec tree must be structurally identical to a
    quantized param tree, with each scale spec = weight spec minus the
    contracted axes (so scales shard with their output channels)."""
    from jax.sharding import PartitionSpec as P

    from vnsum_tpu.models import init_params
    from vnsum_tpu.models.llama import LlamaConfig
    from vnsum_tpu.models.quant import quantize_params
    from vnsum_tpu.parallel.sharding import param_specs

    cfg = LlamaConfig(
        vocab_size=64, dim=16, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=4, intermediate=32, max_seq_len=32,
        use_llama3_rope_scaling=False, tie_embeddings=False,
    )
    qparams = quantize_params(init_params(jax.random.key(0), cfg))
    specs = param_specs(tie_embeddings=False, quantized=True)
    # same tree structure, and every spec rank matches its leaf rank
    flat_p = jax.tree.structure(qparams)
    flat_s = jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P))
    assert flat_p == flat_s
    for leaf, spec in zip(
        jax.tree.leaves(qparams),
        jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)),
    ):
        assert leaf.ndim == len(spec), (leaf.shape, spec)


def test_init_params_quantized_runs_engine():
    """Direct-int8 random init (no bf16 tree ever resident — the only way a
    14B fits one chip) must produce the exact quantize_params layout and
    drive the engine end to end."""
    import jax

    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import jitted_init, tiny_llama
    from vnsum_tpu.models.quant import init_params_quantized, is_quantized

    cfg = tiny_llama(max_seq_len=128)
    params = jitted_init(init_params_quantized, cfg, seed=1)
    assert is_quantized(params)
    assert params["layers"]["wq"]["q"].dtype == jax.numpy.int8
    be = TpuBackend(
        model_config=cfg, params=params, batch_size=2, max_new_tokens=6,
        flash=False,
    )
    outs = be.generate(["văn bản", "hai"])
    assert len(outs) == 2 and all(isinstance(o, str) for o in outs)


def test_w8a8_proj_exact_on_rounded_activations():
    """_proj(act_quant=True) must equal the EXACT computation over the
    int8-rounded activations and dequantized weights — the only loss is the
    activation rounding itself. Checked for all four einsum shapes the
    decoder uses."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.models.llama import _proj
    from vnsum_tpu.models.quant import _quantize

    rng = jax.random.PRNGKey(0)
    B, S, D, H, hd, I = 2, 4, 32, 4, 8, 48
    cases = [
        ("bsd,dhk->bshk", (B, S, D), (D, H, hd), (0,)),
        ("bshk,hkd->bsd", (B, S, H, hd), (H, hd, D), (0, 1)),
        ("bsd,di->bsi", (B, S, D), (D, I), (0,)),
        ("bsi,id->bsd", (B, S, I), (I, D), (0,)),
    ]
    for sub, xs, ws, contract in cases:
        kx, kw, rng = jax.random.split(rng, 3)
        x = jax.random.normal(kx, xs, jnp.float32)
        w = jax.random.normal(kw, ws, jnp.float32)
        wq = _quantize(w, contract)
        got = np.asarray(_proj(sub, x, wq, act_quant=True))

        # reference: round x per token over its contracted trailing dims,
        # then the exact f32 einsum against the dequantized weight
        axes = tuple(range(len(xs) - len(contract), len(xs)))
        amax = np.max(np.abs(np.asarray(x)), axis=axes, keepdims=True)
        s = np.maximum(amax, 1e-8) / 127.0
        x_r = np.clip(np.round(np.asarray(x) / s), -127, 127) * s
        sdeq = np.asarray(wq["s"])
        for a in sorted(contract):
            sdeq = np.expand_dims(sdeq, a)
        w_deq = np.asarray(wq["q"], np.float32) * sdeq
        want = np.einsum(sub, x_r, w_deq)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_w8a8_engine_runs_and_rejects_without_int8_weights():
    import pytest

    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import tiny_llama

    cfg = tiny_llama(max_seq_len=128)
    kw = dict(model_config=cfg, batch_size=2, max_new_tokens=8, seed=0,
              flash=False)
    with pytest.raises(ValueError, match="quantize_act"):
        TpuBackend(quantize_act=True, **kw)
    w8a8 = TpuBackend(quantize=True, quantize_act=True, **kw)
    outs = w8a8.generate(["một văn bản dài hơn", "hai"])
    assert len(outs) == 2 and all(isinstance(o, str) for o in outs)
    assert w8a8.cfg.w8a8_prefill


def test_w8a8_single_token_forward_bit_identical():
    """The S>1 gate's precise claim, tested at the forward level: a
    SINGLE-token forward (what every decode step is) must be bit-identical
    with and without w8a8_prefill — and a multi-token forward must differ
    (the flag actually does something)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.models import init_kv_cache, tiny_llama
    from vnsum_tpu.models.llama import (
        decode_attention_mask,
        forward,
        init_params,
        prefill_attention_mask,
        prefill_positions,
    )
    from vnsum_tpu.models.quant import quantize_params

    cfg_a = tiny_llama(max_seq_len=128)
    cfg_b = dataclasses.replace(cfg_a, w8a8_prefill=True)
    params = quantize_params(init_params(jax.random.key(0), cfg_a))
    B, C = 2, 16
    pad = jnp.zeros((B,), jnp.int32)

    # single token at decode position: identical graphs -> identical bits
    tok1 = jnp.asarray([[5], [9]], jnp.int32)
    cache = init_kv_cache(cfg_a, B, C)
    mask1 = decode_attention_mask(pad, 0, C)
    out_a, _ = forward(params, cfg_a, tok1, pad[:, None], cache, 0, mask1)
    out_b, _ = forward(params, cfg_b, tok1, pad[:, None], cache, 0, mask1)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))

    # multi-token prefill: the act-quant rounding must show up
    S = 8
    toks = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (B, 1)) + 3
    cache = init_kv_cache(cfg_a, B, C)
    maskS = prefill_attention_mask(pad, S, C)
    pos = prefill_positions(pad, S)
    pre_a, _ = forward(params, cfg_a, toks, pos, cache, 0, maskS)
    pre_b, _ = forward(params, cfg_b, toks, pos, cache, 0, maskS)
    assert not np.array_equal(np.asarray(pre_a), np.asarray(pre_b))


def test_w8a8_mesh_sharded_matches_single_device():
    """W8A8 prefill under a (data, model) mesh: the s8xs8 einsums partition
    like any dot, and sharded outputs must equal unsharded exactly (same
    rounding both sides)."""
    import numpy as np

    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import tiny_llama
    from vnsum_tpu.parallel import make_mesh

    cfg = tiny_llama(max_seq_len=128)
    kw = dict(
        model_config=cfg, batch_size=4, max_new_tokens=6, seed=3,
        quantize=True, quantize_act=True, flash=False,
    )
    plain = TpuBackend(**kw)
    mesh = make_mesh({"data": 2, "model": 2, "seq": 1}, platform="cpu")
    sharded = TpuBackend(mesh=mesh, **kw)
    prompts = ["văn bản một", "văn bản thứ hai dài hơn", "ba", "bốn bốn"]
    np.testing.assert_array_equal(
        plain.generate(prompts), sharded.generate(prompts)
    )
