"""The one-shot programs of the families a PR did NOT mean to touch, pinned:
each family's tiny form is traced as the engine builds it (`_make_fn`:
int8 weights, W8A8, chunked prefill, kernels interpreted — their bodies are
in the jaxpr) and the text of its jaxpr is hashed. A change to one family's
model or kernels leaves the other hashes as they were; a PR that moves one
on purpose recomputes it here and says so. PR 44 wrote the first five on
the tree of PR 43 and changed none. **PR 45 moved those five on purpose**:
the GQA prefill kernel (`ops/flash_attention.py`) writes a narrow group's
heads one score product ahead, its body is in every one of these families'
programs and their tiny forms all have groups of at most four heads, so
the five were recomputed; `deepseek-v2`, whose family shares no line of
that kernel, was hashed on PR 44's tree first and is what PR 45 did NOT
move."""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import pytest

from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.models import MODEL_REGISTRY
from vnsum_tpu.models.deepseek import tiny_deepseek

# family -> (config, config keywords, sha256 of str(jaxpr)[:16]); a config is
# a registry name, or the function itself where the family has none
_PINNED = {
    "llama": ("tiny", {}, "b53beb5e9cb8a35d"),
    "llama-qk-norm": ("tiny", {"qk_norm": True}, "89aa4218a7d72f06"),
    "smallthinker": ("tiny-smallthinker", {}, "b4b5483b7a77bcac"),
    "laguna": ("tiny-laguna", {}, "e7b30d5c4179f47b"),
    "granite-h": ("tiny-granite-h", {}, "e6b35913e524e08f"),
    "deepseek-v2": (tiny_deepseek, {}, "90bc1e80a899c229"),
}


def one_shot_jaxpr(cfg, B: int = 2, S: int = 256, new: int = 8) -> str:
    """The text of the (B, S) one-shot program's jaxpr for ``cfg``, traced
    on shapes alone."""
    be = TpuBackend(model_config=cfg, tokenizer="byte", batch_size=B,
                    max_new_tokens=new, interpret=True, quantize=True,
                    prefill_chunk_tokens=128)
    fn = be._make_fn(B, S, new, be.gen_cfg)
    return str(jax.make_jaxpr(fn)(
        jax.eval_shape(lambda: be.params),
        jax.ShapeDtypeStruct((B, S), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.uint32)))


@pytest.mark.parametrize("family", list(_PINNED))
def test_the_one_shot_program_traces_to_the_pinned_jaxpr(family):
    config, kw, want = _PINNED[family]
    text = one_shot_jaxpr(MODEL_REGISTRY.get(config, config)(**kw))
    assert "pallas_call" in text          # the kernels' bodies are hashed too
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want
