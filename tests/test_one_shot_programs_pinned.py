"""The one-shot programs of the families a PR did NOT mean to touch, pinned:
each family's tiny form is traced as the engine builds it (`_make_fn`:
int8 weights, W8A8, chunked prefill, kernels interpreted — their bodies are
in the jaxpr) and the text of its jaxpr is hashed. A change to one family's
model or kernels leaves the other hashes as they were; a PR that moves one
on purpose recomputes it here and says so (PR 44 wrote them on the tree of
PR 43 and changed none: ``models/deepseek.py`` and ``ops/mla_attention.py``
serve the DeepSeek-V2 family alone)."""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import pytest

from vnsum_tpu.backend.engine import TpuBackend
from vnsum_tpu.models import MODEL_REGISTRY

# family -> (registry name, config keywords, sha256 of str(jaxpr)[:16])
_PINNED = {
    "llama": ("tiny", {}, "380e8afb56c730ec"),
    "llama-qk-norm": ("tiny", {"qk_norm": True}, "59e72b0f22f5e81e"),
    "smallthinker": ("tiny-smallthinker", {}, "ff25d0bcc13de32a"),
    "laguna": ("tiny-laguna", {}, "e5fcb5b90134ac55"),
    "granite-h": ("tiny-granite-h", {}, "e90b00fbaf9d3437"),
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
    name, kw, want = _PINNED[family]
    text = one_shot_jaxpr(MODEL_REGISTRY[name](**kw))
    assert "pallas_call" in text          # the kernels' bodies are hashed too
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want
