"""Qwen3-family support: per-head Q/K RMSNorm (qk_norm) on the shared
Llama/Qwen3 decoder stack.

Parity anchor is HF transformers' Qwen3ForCausalLM on a tiny config — the
same oracle role the reference's torch path plays for Llama
(runners/run_summarization.py:54-62; the reference sweeps qwen3:8b at
run_full_evaluation_pipeline.py:960-962 but only ever through Ollama HTTP).
"""
from __future__ import annotations

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax
import jax.numpy as jnp

from vnsum_tpu.models.convert import (
    config_from_hf,
    convert_torch_model,
    load_hf_checkpoint,
    save_hf_checkpoint,
)
from vnsum_tpu.models.llama import (
    forward,
    init_kv_cache,
    init_params,
    prefill_attention_mask,
    prefill_positions,
    qwen3_8b,
    tiny_llama,
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
    rms_norm_eps=1e-6,
    tie_word_embeddings=True,
    model_type="qwen3",
)


@pytest.fixture(scope="module")
def hf_model():
    torch.manual_seed(0)
    cfg = transformers.Qwen3Config(**{
        k: v for k, v in HF_CFG.items() if k != "model_type"
    })
    return transformers.Qwen3ForCausalLM(cfg).eval()


@pytest.fixture(scope="module")
def converted(hf_model):
    cfg = config_from_hf(HF_CFG, dtype=jnp.float32)
    assert cfg.qk_norm  # model_type=qwen3 flips the QK-norm path on
    params = convert_torch_model(hf_model, cfg)
    assert "q_norm" in params["layers"] and "k_norm" in params["layers"]
    return cfg, params


def _hf_logits(hf_model, tokens: np.ndarray) -> np.ndarray:
    with torch.no_grad():
        out = hf_model(torch.from_numpy(tokens).long())
    return out.logits.float().numpy()


def _our_logits(cfg, params, tokens: np.ndarray) -> np.ndarray:
    B, S = tokens.shape
    pad = np.zeros((B,), np.int32)
    cache = init_kv_cache(cfg, B, S)
    out, _ = forward(
        params, cfg, jnp.asarray(tokens),
        prefill_positions(jnp.asarray(pad), S), cache, 0,
        prefill_attention_mask(jnp.asarray(pad), S, S),
    )
    return np.asarray(out)


def test_qwen3_prefill_logit_parity(hf_model, converted):
    cfg, params = converted
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (2, 24), dtype=np.int32)
    ours = _our_logits(cfg, params, tokens)
    theirs = _hf_logits(hf_model, tokens)
    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-3)


def test_qwen3_hf_checkpoint_roundtrip(tmp_path, converted):
    cfg, params = converted
    out = tmp_path / "export"
    save_hf_checkpoint(params, cfg, str(out))
    cfg2, params2 = load_hf_checkpoint(str(out), dtype=jnp.float32)
    assert cfg2.qk_norm
    assert "q_norm" in params2["layers"]
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab_size, (1, 16), dtype=np.int32)
    bf = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params
    )
    np.testing.assert_array_equal(
        _our_logits(cfg, bf, tokens), _our_logits(cfg2, params2, tokens)
    )


def test_qwen3_engine_generate_and_registry():
    """The engine runs a qk_norm config end to end, and the registry
    resolves the reference's qwen3:8b model tag to the real architecture."""
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import MODEL_REGISTRY

    cfg8 = MODEL_REGISTRY["qwen3:8b"]()
    assert cfg8.qk_norm and cfg8.dim == 4096 and cfg8.n_layers == 36

    tiny_q = tiny_llama(qk_norm=True)
    be = TpuBackend(
        model_config=tiny_q, tokenizer="byte", batch_size=2,
        max_new_tokens=8, seed=0,
        flash=False,
    )
    outs = be.generate(["văn bản một", "hai"])
    assert len(outs) == 2 and all(isinstance(o, str) for o in outs)


def test_qwen3_mesh_sharding():
    """qk_norm params shard over a TP mesh (new leaves replicated)."""
    from vnsum_tpu.parallel import make_mesh
    from vnsum_tpu.parallel.sharding import shard_params

    mesh = make_mesh({"data": 2, "model": 2}, platform="cpu")
    cfg = tiny_llama(qk_norm=True)
    params = init_params(jax.random.key(0), cfg)
    sharded = shard_params(params, mesh, cfg.tie_embeddings)
    assert "q_norm" in sharded["layers"]


def test_qwen3_8b_shapes_match_hf():
    """Registry config matches the published Qwen3-8B architecture."""
    cfg = qwen3_8b()
    assert (cfg.vocab_size, cfg.dim, cfg.n_layers) == (151_936, 4096, 36)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 128)
    assert not cfg.tie_embeddings
