"""Phi-4 family support: Llama-shaped math, fused-projection checkpoints.

The reference's largest model sweep entry is phi4:14b
(run_full_evaluation_pipeline.py:960-962), Ollama-only there. HF Phi-3/4
checkpoints fuse attention into one qkv_proj and the MLP into
gate_up_proj; models.convert adapts them to the shared converter. Parity
anchor: transformers Phi3ForCausalLM on a tiny config.
"""
from __future__ import annotations

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp

from vnsum_tpu.models.convert import config_from_hf, load_hf_checkpoint
from vnsum_tpu.models.llama import (
    forward,
    init_kv_cache,
    phi4_14b,
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
    max_position_embeddings=256,
    rope_theta=10000.0,
    rms_norm_eps=1e-5,
    tie_word_embeddings=False,
    model_type="phi3",
    # Phi3Config defaults pad/bos/eos to 32k-range ids; keep them in-vocab
    pad_token_id=0,
    bos_token_id=1,
    eos_token_id=2,
)


@pytest.fixture(scope="module")
def hf_checkpoint(tmp_path_factory):
    torch.manual_seed(0)
    cfg = transformers.Phi3Config(**{
        k: v for k, v in HF_CFG.items() if k != "model_type"
    })
    model = transformers.Phi3ForCausalLM(cfg).eval()
    out = tmp_path_factory.mktemp("phi") / "ckpt"
    model.save_pretrained(out, safe_serialization=True)
    return model, str(out)


def test_phi_fused_checkpoint_logit_parity(hf_checkpoint):
    """load_hf_checkpoint must split qkv_proj/gate_up_proj correctly: full
    prefill logits match the HF forward."""
    model, ckpt = hf_checkpoint
    cfg, params = load_hf_checkpoint(ckpt, dtype=jnp.float32)
    assert not cfg.tie_embeddings and not cfg.qk_norm
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (2, 20), dtype=np.int32)

    B, S = tokens.shape
    pad = np.zeros((B,), np.int32)
    cache = init_kv_cache(cfg, B, S)
    ours, _ = forward(
        params, cfg, jnp.asarray(tokens),
        prefill_positions(jnp.asarray(pad), S), cache, 0,
        prefill_attention_mask(jnp.asarray(pad), S, S),
    )
    with torch.no_grad():
        theirs = model(torch.from_numpy(tokens).long()).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4, rtol=2e-3)


def test_phi_partial_rotary_rejected():
    cfg = dict(HF_CFG, partial_rotary_factor=0.5)
    with pytest.raises(NotImplementedError):
        config_from_hf(cfg)


def test_phi4_registry_shapes():
    cfg = phi4_14b()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads) == (
        5120, 40, 40, 10,
    )
    assert not cfg.tie_embeddings


def test_phi_engine_generate(hf_checkpoint):
    """Converted fused checkpoint runs the engine end to end."""
    from vnsum_tpu.backend.engine import TpuBackend

    _, ckpt = hf_checkpoint
    cfg, params = load_hf_checkpoint(ckpt, dtype=jnp.float32)
    be = TpuBackend(
        model_config=cfg, tokenizer="byte", params=params, batch_size=2,
        max_new_tokens=8, seed=0,
        flash=False,
    )
    outs = be.generate(["văn bản một", "hai"])
    assert len(outs) == 2 and all(isinstance(o, str) for o in outs)
