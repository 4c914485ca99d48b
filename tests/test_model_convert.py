"""HF checkpoint conversion: logit parity against transformers on CPU.

This is the correctness anchor for real-weight runs (SURVEY.md §7 hard part
#2: "Llama-3.2-3B weight port + sharding correctness (logit parity vs HF
CPU)"). A tiny random HF LlamaForCausalLM is converted and both models must
produce near-identical float32 logits.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp

from vnsum_tpu.models.convert import (
    config_from_hf,
    convert_torch_model,
    load_hf_checkpoint,
)
from vnsum_tpu.models.llama import (
    forward_train,
    init_kv_cache,
    forward,
    prefill_attention_mask,
    prefill_positions,
)

HF_CFG = dict(
    vocab_size=384,
    hidden_size=64,
    intermediate_size=128,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=16,
    max_position_embeddings=256,
    rope_theta=10000.0,
    rms_norm_eps=1e-5,
    tie_word_embeddings=True,
)


@pytest.fixture(scope="module")
def hf_model():
    torch.manual_seed(0)
    cfg = transformers.LlamaConfig(**HF_CFG)
    model = transformers.LlamaForCausalLM(cfg).eval()
    return model


@pytest.fixture(scope="module")
def converted(hf_model):
    cfg = config_from_hf(HF_CFG, dtype=jnp.float32)
    params = convert_torch_model(hf_model, cfg)
    return cfg, params


def _hf_logits(hf_model, tokens: np.ndarray) -> np.ndarray:
    with torch.no_grad():
        out = hf_model(torch.from_numpy(tokens).long())
    return out.logits.float().numpy()


def test_config_from_hf_fields(converted):
    cfg, _ = converted
    assert cfg.dim == 64
    assert cfg.n_layers == 2
    assert cfg.n_kv_heads == 2
    assert cfg.head_dim == 16
    assert cfg.tie_embeddings is True
    assert cfg.use_llama3_rope_scaling is False


def test_config_from_hf_llama3_rope():
    hf = dict(
        HF_CFG,
        rope_scaling={
            "rope_type": "llama3",
            "factor": 32.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 8192,
        },
    )
    cfg = config_from_hf(hf)
    assert cfg.use_llama3_rope_scaling
    assert cfg.rope_scale_factor == 32.0
    assert cfg.rope_original_max_len == 8192


def test_train_forward_logit_parity(hf_model, converted):
    cfg, params = converted
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(2, 17), dtype=np.int32)
    ours = np.asarray(
        forward_train(params, cfg, jnp.asarray(tokens), remat=False)
    )
    ref = _hf_logits(hf_model, tokens)
    np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-3)


def test_prefill_forward_logit_parity(hf_model, converted):
    cfg, params = converted
    rng = np.random.default_rng(1)
    B, S = 2, 12
    tokens = rng.integers(0, cfg.vocab_size, size=(B, S), dtype=np.int32)
    pad = jnp.zeros((B,), jnp.int32)
    cache = init_kv_cache(cfg, B, S)
    logits, _ = forward(
        params, cfg, jnp.asarray(tokens), prefill_positions(pad, S), cache,
        0, prefill_attention_mask(pad, S, S),
    )
    ref = _hf_logits(hf_model, tokens)
    np.testing.assert_allclose(np.asarray(logits), ref, atol=2e-4, rtol=2e-3)


def test_load_hf_checkpoint_safetensors(tmp_path, hf_model, converted):
    from safetensors.torch import save_file

    cfg, params = converted
    sd = {k: v.contiguous().clone() for k, v in hf_model.state_dict().items()}
    save_file(sd, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(HF_CFG))

    cfg2, params2 = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert cfg2.dim == cfg.dim and cfg2.n_layers == cfg.n_layers
    np.testing.assert_allclose(
        np.asarray(params2["layers"]["wq"]), np.asarray(params["layers"]["wq"]),
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(params2["embed"]), np.asarray(params["embed"]), atol=1e-6
    )


def test_sharded_checkpoint_with_index(tmp_path, hf_model, converted):
    from safetensors.torch import save_file

    cfg, params = converted
    sd = {k: v.contiguous().clone() for k, v in hf_model.state_dict().items()}
    keys = sorted(sd)
    half = len(keys) // 2
    shards = {
        "model-00001-of-00002.safetensors": {k: sd[k] for k in keys[:half]},
        "model-00002-of-00002.safetensors": {k: sd[k] for k in keys[half:]},
    }
    weight_map = {}
    for shard, tensors in shards.items():
        save_file(tensors, str(tmp_path / shard))
        for k in tensors:
            weight_map[k] = shard
    (tmp_path / "model.safetensors.index.json").write_text(
        json.dumps({"weight_map": weight_map})
    )
    (tmp_path / "config.json").write_text(json.dumps(HF_CFG))

    _, params2 = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(params2["layers"]["w_down"]),
        np.asarray(params["layers"]["w_down"]),
        atol=1e-6,
    )


def test_save_hf_checkpoint_roundtrip(tmp_path, converted):
    """save_hf_checkpoint is the exact inverse of load_hf_checkpoint: a
    params tree exported to sharded HF safetensors and loaded back must be
    bit-identical (modulo the bf16 storage dtype) and produce identical
    prefill logits. This pair is how the 3B runbook artifact proves the
    converter at real scale without the real weights."""
    import jax

    from vnsum_tpu.models.convert import save_hf_checkpoint

    cfg, params = converted
    out = tmp_path / "export"
    index = save_hf_checkpoint(params, cfg, str(out), shard_layers=1)
    # sharding actually happened: 2 layer shards + 1 head shard
    assert len(set(index["weight_map"].values())) == 3
    cfg2, params2 = load_hf_checkpoint(str(out), dtype=jnp.float32)
    assert cfg2.dim == cfg.dim and cfg2.n_layers == cfg.n_layers
    assert cfg2.tie_embeddings == cfg.tie_embeddings

    def max_diff(a, b):
        return max(
            float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
        )

    # bf16 storage: exported tensors round through bfloat16 once
    bf = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    assert max_diff(bf, params2) == 0.0

    tokens = np.arange(12, dtype=np.int32).reshape(1, 12) % cfg.vocab_size
    S = 16
    pad = np.asarray([S - 12], np.int32)
    toks = np.full((1, S), 0, np.int32)
    toks[0, 4:] = tokens
    def logits_of(p):
        cache = init_kv_cache(cfg, 1, S)
        out, _ = forward(
            p, cfg, jnp.asarray(toks), prefill_positions(jnp.asarray(pad), S),
            cache, 0, prefill_attention_mask(jnp.asarray(pad), S, S),
            last_only=True,
        )
        return np.asarray(out)

    np.testing.assert_array_equal(logits_of(bf), logits_of(params2))


def test_save_hf_checkpoint_untied(tmp_path):
    """Untied lm_head round-trips through the [vocab, dim] HF layout."""
    import jax

    from vnsum_tpu.models import init_params
    from vnsum_tpu.models.convert import save_hf_checkpoint
    from vnsum_tpu.models.llama import tiny_llama

    cfg = tiny_llama(tie_embeddings=False)
    params = init_params(jax.random.key(0), cfg)
    out = tmp_path / "export"
    save_hf_checkpoint(params, cfg, str(out))
    cfg2, params2 = load_hf_checkpoint(str(out), dtype=jnp.float32)
    assert not cfg2.tie_embeddings
    got = np.asarray(params2["lm_head"], np.float32)
    want = np.asarray(
        jnp.asarray(params["lm_head"], jnp.bfloat16).astype(jnp.float32)
    )
    np.testing.assert_array_equal(got, want)


# -- four-family trained generation parity (VERDICT r3 #4) -------------------

# harness lifted to models/fixtures.py so artifact scripts train the same
# checkpoints (VERDICT r4 #2 quality A/B); the test keeps its local aliases
from vnsum_tpu.models.fixtures import (  # noqa: E402
    GEN_CORPUS as _GEN_CORPUS,
    TRAINED_FAMILIES as _FAMILIES,
    train_tiny_family as _train_tiny_family_lib,
)


def _train_tiny_family(family: str, out_dir, steps: int = 40):
    return _train_tiny_family_lib(family, out_dir, steps=steps)

@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_trained_generation_string_parity(family, tmp_path):
    """Greedy STRING parity on a trained tiny fixture, per family: the
    engine over load_hf_checkpoint must emit exactly what transformers'
    .generate emits for the same checkpoint (the generation-level
    complement of the logit-parity tests — VERDICT r3 #4)."""
    from vnsum_tpu.backend.engine import TpuBackend

    out = tmp_path / family
    model, hf_tok = _train_tiny_family(family, out)

    cfg, params = load_hf_checkpoint(str(out), dtype=jnp.float32)
    be = TpuBackend(
        model_config=cfg, params=params, tokenizer=f"hf:{out}",
        batch_size=1, max_new_tokens=24,
        flash=False,
    )

    prompt = "Quốc hội đã thông qua nghị quyết"
    enc = hf_tok(prompt, return_tensors="pt", add_special_tokens=False)
    input_ids = torch.cat(
        [torch.tensor([[hf_tok.bos_token_id]]), enc.input_ids], dim=1
    )
    with torch.no_grad():
        hf_out = model.generate(
            input_ids, max_new_tokens=24, do_sample=False,
            pad_token_id=hf_tok.pad_token_id,
        )
    hf_text = hf_tok.decode(
        hf_out[0, input_ids.shape[1]:], skip_special_tokens=True
    ).strip()

    ours = be.generate([prompt], max_new_tokens=24)[0]
    assert ours == hf_text, (family, ours, hf_text)
