from .llama import (
    LlamaConfig,
    dequantize_cache_layer,
    forward,
    init_kv_cache,
    init_params,
    is_quantized_cache,
    gemma3_4b,
    llama32_1b,
    llama32_3b,
    ouro_2p6b,
    phi4_14b,
    qwen3_0p6b,
    qwen3_8b,
    tiny_llama,
    tiny_ouro,
)
from .sampling import sample_logits


def jitted_init(init_fn, cfg, seed: int = 0):
    """Run a param-init function as ONE compiled program.

    Eager init dispatches (and compiles) once per leaf; a jitted init is a
    single cacheable program. Shared by the generation engine, the
    long-context backend, and the evaluation embedder."""
    import functools

    import jax

    return jax.jit(functools.partial(init_fn, cfg=cfg))(jax.random.key(seed))

def _deepseek_v2(**kw):
    # imported on use: the dense families' paths never load this module
    from .deepseek import deepseek_v2

    return deepseek_v2(**kw)


def _smallthinker(**kw):
    from .smallthinker import smallthinker_21b_a3b

    return smallthinker_21b_a3b(**kw)


def _tiny_smallthinker(**kw):
    from .smallthinker import tiny_smallthinker

    return tiny_smallthinker(**kw)


def _laguna(**kw):
    from .laguna import laguna_s_2_1

    return laguna_s_2_1(**kw)


def _tiny_laguna(**kw):
    from .laguna import tiny_laguna

    return tiny_laguna(**kw)


def _granite_h_micro(**kw):
    from .granite_hybrid import granite_4_0_h_micro

    return granite_4_0_h_micro(**kw)


def _tiny_granite_h(**kw):
    from .granite_hybrid import tiny_granite_h

    return tiny_granite_h(**kw)


def _nemotron_3_nano(**kw):
    from .nemotron_h import nemotron_3_nano_30b_a3b

    return nemotron_3_nano_30b_a3b(**kw)


def _tiny_nemotron_h(**kw):
    from .nemotron_h import tiny_nemotron_h

    return tiny_nemotron_h(**kw)


def _lfm2_8b_a1b(**kw):
    from .lfm2 import lfm2_8b_a1b

    return lfm2_8b_a1b(**kw)


def _tiny_lfm2(**kw):
    from .lfm2 import tiny_lfm2

    return tiny_lfm2(**kw)


def _ling_3_0_flash(**kw):
    from .ling import ling_3_0_flash

    return ling_3_0_flash(**kw)


def _tiny_ling(**kw):
    from .ling import tiny_ling

    return tiny_ling(**kw)


def _brumby_14b(**kw):
    from .brumby import brumby_14b

    return brumby_14b(**kw)


def _tiny_brumby(**kw):
    from .brumby import tiny_brumby

    return tiny_brumby(**kw)


def _keye_vl_2_0(**kw):
    from .keye import keye_vl_2_0_30b_a3b

    return keye_vl_2_0_30b_a3b(**kw)


def _tiny_keye(**kw):
    from .keye import tiny_keye

    return tiny_keye(**kw)


# model name -> config factory (names match the reference's Ollama tags where
# an equivalent open-weights architecture exists)
MODEL_REGISTRY = {
    "llama3.2:3b": llama32_3b,
    "llama3.2-3b": llama32_3b,
    "llama3.2:1b": llama32_1b,
    "llama3.2-1b": llama32_1b,
    "qwen3:8b": qwen3_8b,
    "qwen3-8b": qwen3_8b,
    "qwen3:0.6b": qwen3_0p6b,
    "qwen3-0.6b": qwen3_0p6b,
    "gemma3:4b": gemma3_4b,
    "gemma3-4b": gemma3_4b,
    "phi4:14b": phi4_14b,
    "phi4-14b": phi4_14b,
    "tiny": tiny_llama,
    # the dense family LOOPED over its weights (llama.LlamaConfig.loop_passes):
    # the whole stack four times a token, a final norm after every pass,
    # keys and values of their own for every (pass, layer)
    "ouro-2.6b": ouro_2p6b,
    "tiny-ouro": tiny_ouro,
    # another family (models/deepseek.py): latent attention, sparse experts
    "deepseek-v2": _deepseek_v2,
    # a third (models/smallthinker.py): GQA with window and global layers
    # mixed, sparse ReGLU experts routed on the layer's input
    "smallthinker-21b-a3b": _smallthinker,
    "tiny-smallthinker": _tiny_smallthinker,
    # a fourth (models/laguna.py): query heads that differ by layer kind, a
    # per-head output gate, two rotary schemes, a leading dense layer, 256
    # experts top-10 with a shared one
    "laguna-s-2.1": _laguna,
    "tiny-laguna": _tiny_laguna,
    # a fifth (models/granite_hybrid.py): Mamba-2 layers beside a few
    # position-free GQA layers, a recurrent state in the program's carry
    "granite-4.0-h-micro": _granite_h_micro,
    "tiny-granite-h": _tiny_granite_h,
    # a sixth (models/nemotron_h.py): every layer ONE mixer - Mamba-2 at
    # eight groups of B and C, non-gated relu2 experts with a shared one,
    # or position-free GQA - state, counters and keys in one carry
    "nemotron-3-nano-30b-a3b": _nemotron_3_nano,
    "tiny-nemotron-h": _tiny_nemotron_h,
    # a seventh (models/lfm2.py): gated short-convolution layers whose only
    # state is a two-token tail beside a few rotary QK-normed GQA layers,
    # two leading dense layers, then 32 gated experts top-4 by sigmoid + bias
    "lfm2-8b-a1b": _lfm2_8b_a1b,
    "tiny-lfm2": _tiny_lfm2,
    # an eighth (models/ling.py): Kimi-Delta-Attention layers - a float32
    # matrix state a head under a gated delta rule - beside latent-attention
    # layers with no compressed query 5:1, two leading dense layers, then
    # 512 experts top-8 by group-limited sigmoid score + bias and a shared one
    "ling-3.0-flash": _ling_3_0_flash,
    "tiny-ling": _tiny_ling,
    # a ninth (models/brumby.py): the dense QK-normed rotary skeleton with
    # every attention layer a power-retention layer of degree 2 - a float32
    # matrix state a KV head, expanded from q and k inside the kernels of
    # ops/power_retention.py - and no keys and values at all
    "brumby-14b": _brumby_14b,
    "tiny-brumby": _tiny_brumby,
    # a tenth (models/keye.py): the Qwen3-MoE skeleton (QK-normed rotary GQA
    # by three position components, 128 experts top-8) whose every layer
    # attends the 2,048 keys a learned indexer scores highest
    # (ops/sparse_attention.py), indexer keys in the carry beside keys and
    # values
    "keye-vl-2.0-30b-a3b": _keye_vl_2_0,
    "tiny-keye": _tiny_keye,
}

__all__ = [
    "jitted_init",
    "LlamaConfig",
    "forward",
    "init_kv_cache",
    "init_params",
    "gemma3_4b",
    "llama32_1b",
    "phi4_14b",
    "llama32_3b",
    "ouro_2p6b",
    "qwen3_0p6b",
    "qwen3_8b",
    "tiny_llama",
    "sample_logits",
]
