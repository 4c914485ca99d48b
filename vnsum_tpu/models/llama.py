"""Llama-3.2 family in functional JAX, designed for the MXU.

This fills the architectural slot of the reference's LLM execution layer (the
OllamaLLM HTTP wrapper, runners/run_summarization_ollama_mapreduce.py:23-60,
and the torch path in runners/run_summarization.py:54-62) with an on-device
implementation:

- params are a plain pytree with a stacked leading layer dim, so the decoder
  runs as one `lax.scan` over layers (fast XLA compiles, clean TP shardings);
- GQA attention with RoPE (llama3 frequency scaling), RMSNorm, SwiGLU;
- a preallocated KV cache written with `lax.dynamic_update_slice` so prefill
  and single-token decode share one code path and static shapes;
- bfloat16 storage/matmuls with float32 softmax and norms.

No HF/torch code is used on the compute path; weights can be randomly
initialized (benchmarks, tests) or converted from safetensors offline.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 3072
    n_layers: int = 28
    n_heads: int = 24
    n_kv_heads: int = 8
    head_dim: int = 128
    intermediate: int = 8192
    rope_theta: float = 500_000.0
    use_llama3_rope_scaling: bool = True
    rope_scale_factor: float = 32.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_len: int = 8192
    norm_eps: float = 1e-5
    max_seq_len: int = 16_384
    tie_embeddings: bool = True
    # Qwen3-style per-head RMSNorm on Q/K before RoPE — the one structural
    # delta between the Llama and Qwen3 decoder stacks; everything else
    # (GQA, SwiGLU, pre-norm residuals) is shared, so both families run
    # through this module (reference sweeps qwen3:8b alongside llama3.2:3b,
    # run_full_evaluation_pipeline.py:960-962)
    qk_norm: bool = False
    # --- Gemma3 deltas (reference sweeps gemma3:4b) — all default-off so
    # the Llama/Qwen traces are unchanged ---
    act: str = "silu"              # "silu" | "gelu_tanh" (GeGLU)
    sandwich_norms: bool = False   # post-attention + pre/post-FFW norms
    norm_plus_one: bool = False    # RMSNorm scale is (1 + w), zero-init w
    embed_scale: bool = False      # hidden states scaled by sqrt(dim)
    query_scale: float = 0.0       # 0 => 1/sqrt(head_dim); else 1/sqrt(this)
    sliding_window: int = 0        # 0 => every layer attends globally
    # per-layer attention kind when sliding_window > 0: True = global.
    # Gemma3 interleaves 5 sliding : 1 global
    layer_is_global: tuple = ()
    rope_local_theta: float = 10_000.0  # RoPE base for sliding layers
    rope_linear_factor: float = 0.0     # linear position scaling (Gemma3 global)
    # W8A8 prefill: dynamically int8-quantize ACTIVATIONS (per-token absmax)
    # into the int8-weight matmuls during multi-token forwards, hitting the
    # MXU's double-rate s8xs8 path (measured 132.7 vs 83.1 TFLOP/s on v5e).
    # LOSSY (~1/127 relative rounding per matmul input) and opt-in; decode
    # (single-token) keeps the exact mixed path — it is HBM-bound, not
    # MXU-bound. Requires int8-quantized weights to do anything.
    w8a8_prefill: bool = False
    # a LOOPED stack (Ouro): the whole stack of n_layers runs this many
    # times over the SAME weights, ``final_norm`` after every pass (the
    # normed stream enters the next pass and, after the last, the head),
    # and pass t of layer l has keys and values of its own: cache layer
    # ``t * n_layers + l`` of ``cache_layers(cfg)``. 1 traces what it always
    # traced
    loop_passes: int = 1
    dtype: Any = field(default=jnp.bfloat16)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads


def llama32_3b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama32_1b(**kw) -> LlamaConfig:
    base = dict(
        dim=2048, n_layers=16, n_heads=32, n_kv_heads=8, head_dim=64,
        intermediate=8192,
    )
    base.update(kw)
    return LlamaConfig(**base)


def qwen3_8b(**kw) -> LlamaConfig:
    base = dict(
        vocab_size=151_936, dim=4096, n_layers=36, n_heads=32, n_kv_heads=8,
        head_dim=128, intermediate=12_288, rope_theta=1_000_000.0,
        use_llama3_rope_scaling=False, norm_eps=1e-6, max_seq_len=32_768,
        tie_embeddings=False, qk_norm=True,
    )
    base.update(kw)
    return LlamaConfig(**base)


def qwen3_0p6b(**kw) -> LlamaConfig:
    base = dict(
        vocab_size=151_936, dim=1024, n_layers=28, n_heads=16, n_kv_heads=8,
        head_dim=128, intermediate=3072, rope_theta=1_000_000.0,
        use_llama3_rope_scaling=False, norm_eps=1e-6, max_seq_len=32_768,
        tie_embeddings=True, qk_norm=True,
    )
    base.update(kw)
    return LlamaConfig(**base)


def gemma3_4b(**kw) -> LlamaConfig:
    """Gemma3-4B text decoder (reference model family #3,
    run_full_evaluation_pipeline.py:960-962 `gemma3:4b`)."""
    n_layers = 34
    base = dict(
        vocab_size=262_208, dim=2560, n_layers=n_layers, n_heads=8,
        n_kv_heads=4, head_dim=256, intermediate=10_240,
        rope_theta=1_000_000.0, use_llama3_rope_scaling=False,
        rope_linear_factor=8.0, norm_eps=1e-6, max_seq_len=32_768,
        tie_embeddings=True, qk_norm=True, act="gelu_tanh",
        sandwich_norms=True, norm_plus_one=True, embed_scale=True,
        query_scale=256.0, sliding_window=1024,
        layer_is_global=tuple((i + 1) % 6 == 0 for i in range(n_layers)),
        rope_local_theta=10_000.0,
    )
    base.update(kw)
    return LlamaConfig(**base)


def phi4_14b(**kw) -> LlamaConfig:
    """Phi-4 decoder (reference model family #4, `phi4:14b`): Llama math
    with fused-projection checkpoints (models.convert._phi_fused_getter)."""
    base = dict(
        vocab_size=100_352, dim=5120, n_layers=40, n_heads=40,
        n_kv_heads=10, head_dim=128, intermediate=17_920,
        rope_theta=250_000.0, use_llama3_rope_scaling=False,
        norm_eps=1e-5, max_seq_len=16_384, tie_embeddings=False,
    )
    base.update(kw)
    return LlamaConfig(**base)


def ouro_2p6b(early_exit_threshold: float = 1.0, **kw) -> LlamaConfig:
    """Ouro-2.6B (ByteDance; arXiv:2510.25741): a Qwen-like dense stack of 48
    layers applied ``total_ut_steps`` = 4 times over the same weights —
    sandwich norms, the final norm after every pass, 16 full KV heads, keys
    and values of their own for every (pass, layer).

    The published ``early_exit_threshold`` is 1: every token runs every
    pass and the exit gate (``params["exit_gate"]``) changes no logit, so the
    program does not evaluate it. A threshold under 1 lets the rows of one
    batch leave the loop at different passes, and the later passes' keys
    and values of a token that left have to be filled from somewhere: no
    entry of the engine does either, so it is refused here by name."""
    if early_exit_threshold < 1.0:
        raise NotImplementedError(
            f"early_exit_threshold {early_exit_threshold} < 1: adaptive exit "
            "from the loop of passes is not built (rows of a batch leaving "
            "at different passes; the later passes' cache of a token that "
            "left). Every pass runs for every token, as at the published 1.0")
    base = dict(
        vocab_size=49_152, dim=2048, n_layers=48, n_heads=16, n_kv_heads=16,
        head_dim=128, intermediate=5632, rope_theta=1_000_000.0,
        use_llama3_rope_scaling=False, norm_eps=1e-6, max_seq_len=65_536,
        tie_embeddings=False, qk_norm=False, sandwich_norms=True,
        loop_passes=4,
    )
    base.update(kw)
    return LlamaConfig(**base)


def cache_layers(cfg: LlamaConfig) -> int:
    """Layers of keys and values a program carries: one a (pass, layer).
    THE count for the cache, the prefix pool, the kernels' per-layer tables
    and the block counters; ``cfg.n_layers`` is the layers of WEIGHTS. The
    families that borrow this module's cache hand in configs of their own,
    which know no pass."""
    return cfg.n_layers * getattr(cfg, "loop_passes", 1)


def tiny_llama(**kw) -> LlamaConfig:
    """Small config for hermetic CPU tests."""
    base = dict(
        vocab_size=384, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, intermediate=128, max_seq_len=256,
        use_llama3_rope_scaling=False, rope_theta=10_000.0,
        dtype=jnp.float32,
    )
    base.update(kw)
    return LlamaConfig(**base)


def tiny_ouro(**kw) -> LlamaConfig:
    """``ouro_2p6b``'s mechanisms at ``tiny_llama``'s size: three passes
    over two layers, sandwich norms, a KV head a query head, untied head."""
    base = dict(loop_passes=3, sandwich_norms=True, n_kv_heads=4,
                tie_embeddings=False, norm_eps=1e-6)
    base.update(kw)
    return tiny_llama(**base)


# -- parameters -------------------------------------------------------------


def init_params(key: jax.Array, cfg: LlamaConfig) -> dict:
    """Random init; layer weights are stacked on a leading L dim."""
    L, D, H, KV, hd, I = (
        cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
        cfg.intermediate,
    )
    keys = iter(jax.random.split(key, 16))

    def norm(shape, k, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(cfg.dtype)

    # plus-one norms (Gemma) are zero-centered: w=0 means identity scale
    norm_init = jnp.zeros if cfg.norm_plus_one else jnp.ones
    params = {
        "embed": norm((cfg.vocab_size, D), next(keys)),
        "layers": {
            "attn_norm": norm_init((L, D), cfg.dtype),
            "wq": norm((L, D, H, hd), next(keys)),
            "wk": norm((L, D, KV, hd), next(keys)),
            "wv": norm((L, D, KV, hd), next(keys)),
            "wo": norm((L, H, hd, D), next(keys)),
            "mlp_norm": norm_init((L, D), cfg.dtype),
            "w_gate": norm((L, D, I), next(keys)),
            "w_up": norm((L, D, I), next(keys)),
            "w_down": norm((L, I, D), next(keys)),
        },
        "final_norm": norm_init((D,), cfg.dtype),
    }
    if cfg.qk_norm:
        params["layers"]["q_norm"] = norm_init((L, hd), cfg.dtype)
        params["layers"]["k_norm"] = norm_init((L, hd), cfg.dtype)
    if cfg.sandwich_norms:
        params["layers"]["post_attn_norm"] = norm_init((L, D), cfg.dtype)
        params["layers"]["post_ffw_norm"] = norm_init((L, D), cfg.dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = norm((D, cfg.vocab_size), next(keys))
    if cfg.loop_passes > 1:
        # the gate a looped model leaves the loop by: sigmoid(w . h + b) on
        # a pass's normed stream. In the tree so that the tree is the
        # model's; not evaluated at the published threshold (ouro_2p6b)
        params["exit_gate"] = {
            "w": jax.random.normal(next(keys), (D,), jnp.float32) * 0.02,
            "b": jnp.zeros((), jnp.float32)}
    return params


def init_kv_cache(
    cfg: LlamaConfig, batch: int, cache_len: int, *, quantized: bool = False,
    model_shards: int = 1,
) -> dict:
    """Stacked cache [L, B, KV, C, hd] — KV heads BEFORE the sequence dim;
    L is ``cache_layers(cfg)``, a layer a (pass, layer) of a looped stack.

    Heads half a lane tile wide (hd 64) are stored TWO A TILE where they
    pair off (``ops.flash_attention.heads_per_lane_tile``; ``model_shards``
    is the mesh's tensor axis, which the pairs must still divide over):
    [L, B, KV/2, C, 128], heads 2p and 2p+1 in lanes 0-63 and 64-127 of
    tile p, so that what streams the cache moves and multiplies full tiles.
    The scales stay a head, [L, B, KV, C]. Shapes alone decide; what writes
    and reads the cache takes the heads a tile off its last dim
    (``_write_kv``, ``dequantize_cache_layer``, the kernels' wrappers), and
    what moves whole slots of it (``_cache_write``, ``cache/store.py``)
    never looks inside a tile.

    This is the layout the attention einsums consume directly ((b, kv) as
    batch dims, hd/c as the minor contraction dims). With the sequence dim
    ahead of the heads, XLA inserts whole-cache layout-conversion copies plus
    per-layer extraction copies inside the decode loop — measured ~19 GB of
    pure copy traffic per decode step on a 48×1088 cache, 3× the mandatory
    weight+cache reads.

    ``quantized=True`` stores K/V as int8 with per-(token, head) float32
    scales ``ks``/``vs`` [L, B, KV, C] — decode attention streams the whole
    cache every step, so this halves its HBM traffic (decode attention is
    the largest decode-phase cost once weights are int8)."""
    from ..ops.flash_attention import heads_per_lane_tile

    heads = (cache_layers(cfg), batch, cfg.n_kv_heads, cache_len)
    tile = heads_per_lane_tile(cfg.n_kv_heads, cfg.head_dim, model_shards)
    shape = (*heads[:2], cfg.n_kv_heads // tile, cache_len,
             cfg.head_dim * tile)
    if not quantized:
        return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}
    return {
        "k": jnp.zeros(shape, jnp.int8),
        "v": jnp.zeros(shape, jnp.int8),
        "ks": jnp.zeros(heads, jnp.float32),
        "vs": jnp.zeros(heads, jnp.float32),
    }


def is_quantized_cache(cache: dict) -> bool:
    return "ks" in cache


def _quantize_kv(x: jax.Array):
    """x [B, KV, S, hd] -> (int8 values, f32 scales [B, KV, S])."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def heads_to_tiles(x: jax.Array, tile: int) -> jax.Array:
    """x [..., KV, S, hd] -> [..., KV/tile, S, tile * hd]: ``tile``
    neighbouring heads side by side on the lanes (``init_kv_cache``)."""
    if tile == 1:
        return x
    *lead, KV, S, hd = x.shape
    x = x.reshape(*lead, KV // tile, tile, S, hd)
    return jnp.moveaxis(x, -3, -2).reshape(*lead, KV // tile, S, tile * hd)


def tiles_to_heads(x: jax.Array, tile: int) -> jax.Array:
    """The way back: x [..., KV/tile, S, tile * hd] -> [..., KV, S, hd]."""
    if tile == 1:
        return x
    *lead, T, S, width = x.shape
    x = x.reshape(*lead, T, S, tile, width // tile)
    return jnp.moveaxis(x, -2, -3).reshape(*lead, T * tile, S, width // tile)


def dequantize_cache_layer(cache: dict, layer_idx,
                           head_dim: int | None = None,
                           ) -> tuple[jax.Array, jax.Array]:
    """Extract layer `layer_idx` as dense float K/V [B, KV, C, hd], a head
    of ``head_dim`` apiece where the cache holds several a lane tile (None:
    the cache's last dim is the head)."""
    k = jax.lax.dynamic_index_in_dim(cache["k"], layer_idx, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache["v"], layer_idx, 0, keepdims=False)
    tile = k.shape[-1] // (head_dim or k.shape[-1])
    k, v = tiles_to_heads(k, tile), tiles_to_heads(v, tile)
    if not is_quantized_cache(cache):
        return k, v
    ks = jax.lax.dynamic_index_in_dim(cache["ks"], layer_idx, 0, keepdims=False)
    vs = jax.lax.dynamic_index_in_dim(cache["vs"], layer_idx, 0, keepdims=False)
    return (
        k.astype(jnp.float32) * ks[..., None],
        v.astype(jnp.float32) * vs[..., None],
    )


# -- building blocks --------------------------------------------------------


def _proj(sub: str, x: jax.Array, w, act_quant: bool = False) -> jax.Array:
    """Einsum against a weight that may be int8-quantized ({"q", "s"}).

    The int8 values go straight into the matmul (the dtype convert fuses into
    the MXU tile load, so HBM sees int8); the per-output-channel scale
    multiplies the result, which is exact because scales never cross the
    contraction (models/quant.py layout).

    ``act_quant=True`` (cfg.w8a8_prefill) additionally quantizes x per token
    (absmax over its contracted — trailing — dims) and runs the s8xs8->s32
    MXU dot at double rate; the activation scale factors out of the
    contraction exactly like the weight scale, so the ONLY loss is the int8
    rounding of x."""
    if not isinstance(w, dict):
        return jnp.einsum(sub, x, w)
    if act_quant:
        xs, rest = sub.split(",")
        ws, out = rest.split("->")
        n_contract = sum(c in ws and c not in out for c in xs)
        axes = tuple(range(x.ndim - n_contract, x.ndim))
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axes,
                       keepdims=True)
        s_act = jnp.maximum(amax, 1e-8) / 127.0
        q = jnp.clip(
            jnp.round(x.astype(jnp.float32) / s_act), -127, 127
        ).astype(jnp.int8)
        y = jnp.einsum(
            sub, q, w["q"], preferred_element_type=jnp.int32
        ).astype(jnp.float32)
        # broadcast the per-token scale over the weight's output dims
        n_out = len(out) - (len(xs) - n_contract)
        s_act = s_act.reshape(s_act.shape[: x.ndim - n_contract] + (1,) * n_out)
        return (y * s_act * w["s"]).astype(x.dtype)
    y = jnp.einsum(sub, x, w["q"].astype(x.dtype))
    return (y.astype(jnp.float32) * w["s"]).astype(x.dtype)


def _embed_lookup(embed, tokens: jax.Array, dtype) -> jax.Array:
    if isinstance(embed, dict):
        rows = jnp.take(embed["q"], tokens, axis=0).astype(jnp.float32)
        scales = jnp.take(embed["s"], tokens, axis=0)
        return (rows * scales[..., None]).astype(dtype)
    return jnp.take(embed, tokens, axis=0)


def _lm_head_logits(x: jax.Array, params: dict, cfg: "LlamaConfig") -> jax.Array:
    """Final projection in float32 (sampling wants full-precision logits)."""
    if cfg.tie_embeddings:
        w = params["embed"]
        sub = "bsd,vd->bsv"  # tied head contracts the embed row dim
    else:
        w = params["lm_head"]
        sub = "bsd,dv->bsv"
    if isinstance(w, dict):
        y = jnp.einsum(
            sub, x, w["q"].astype(x.dtype), preferred_element_type=jnp.float32
        )
        return y * w["s"]
    return jnp.einsum(sub, x, w, preferred_element_type=jnp.float32)


def _rmsnorm(
    x: jax.Array, w: jax.Array, eps: float, plus_one: bool = False
) -> jax.Array:
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if plus_one:
        # Gemma-family RMSNorm: zero-centered weight, applied in float32
        return ((x32 * scale) * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
    return (x32 * scale).astype(x.dtype) * w


def _mlp_act(x: jax.Array, act: str) -> jax.Array:
    if act == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if act == "relu":
        return jnp.maximum(x, 0)
    if act == "relu2":   # the square of relu: an expert with no gate
        return jnp.square(jnp.maximum(x, 0))
    return jax.nn.silu(x)


def _rope_inv_freq(cfg: LlamaConfig) -> jax.Array:
    half = cfg.head_dim // 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if not cfg.use_llama3_rope_scaling:
        return inv
    # llama3 long-context frequency scaling: low-frequency bands divided by
    # `factor`, high-frequency bands kept, smooth ramp between.
    lo_wavelen = cfg.rope_original_max_len / cfg.rope_low_freq_factor
    hi_wavelen = cfg.rope_original_max_len / cfg.rope_high_freq_factor
    wavelen = 2.0 * jnp.pi / inv
    ramp = (cfg.rope_original_max_len / wavelen - cfg.rope_low_freq_factor) / (
        cfg.rope_high_freq_factor - cfg.rope_low_freq_factor
    )
    ramp = jnp.clip(ramp, 0.0, 1.0)
    scaled = inv / cfg.rope_scale_factor
    smooth = (1.0 - ramp) * scaled + ramp * inv
    out = jnp.where(wavelen > lo_wavelen, scaled, inv)
    between = (wavelen <= lo_wavelen) & (wavelen >= hi_wavelen)
    return jnp.where(between, smooth, out)


def yarn_inv_freq(dim: int, base: float, factor: float,
                  original_max_len: int, beta_fast: float,
                  beta_slow: float) -> jax.Array:
    """YaRN inverse frequencies of ``dim`` rotated dims at ``base``:
    interpolated (divided by ``factor``) below the low correction
    dimension, extrapolated (unchanged) above the high one, a linear ramp
    between. What the families with a YaRN table share
    (``models/deepseek.py``, ``models/laguna.py``)."""
    import math

    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / (base ** exponent)
    inter = extra / factor

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(original_max_len / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return inter * ramp + extra * (1.0 - ramp)


def _rope_cos_sin(cfg: LlamaConfig, positions: jax.Array):
    """positions [B, S] -> cos/sin [B, S, hd/2] (float32)."""
    pos = positions[..., None].astype(jnp.float32)
    if cfg.rope_linear_factor:
        pos = pos / cfg.rope_linear_factor
    angles = pos * _rope_inv_freq(cfg)
    return jnp.cos(angles), jnp.sin(angles)


def _apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, S, H, hd]; rotate-half convention (pairs are [..:half],[half:..])."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = x1f * c - x2f * s
    out2 = x2f * c + x1f * s
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def _attention(
    q: jax.Array,        # [B, S, H, hd]
    k: jax.Array,        # [B, KV, C, hd]
    v: jax.Array,        # [B, KV, C, hd]
    mask: jax.Array,     # [B, S, C] bool — True = attend
    q_per_kv: int,
) -> jax.Array:
    B, S, H, hd = q.shape
    KV = k.shape[1]
    # (b, kv) are batch dims of both einsums and lead both operands; the
    # contractions run over the minor dims (hd, then c) — no cache transpose
    qg = q.reshape(B, S, KV, q_per_kv, hd).transpose(0, 2, 3, 1, 4)
    scores = jnp.einsum(
        "bkgsh,bkch->bkgsc", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores / jnp.sqrt(jnp.float32(hd))
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(mask[:, None, None, :, :], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgsc,bkch->bkgsh", probs, v)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


def _cache_write(buf, val, layer_idx, write_index, rows=None):
    """Write a per-layer K/V (or scale) slab into the stacked cache.

    ``buf`` [L, B, KV, C(, hd)], ``val`` [B, KV, S(, hd)]. ``write_index``
    is the cache slot of val's first token — a scalar (prefill/decode: every
    row writes at the same slot) or a [B] vector (the speculative verify
    step: rows sit at different fills after ragged draft acceptance, so each
    row writes at its own slot, one small in-place update per row).

    The per-row form is a chain of dynamic_update_slices on the cache in its
    own layout, not a vmap over the batch axis: vmapping moves axis 1 to the
    front and back, and on the chip XLA materializes both whole-cache
    transposes per layer per step — the slot loop's first B=2 segment ran
    past 230 ms/step and was declared hung (B=1 hid it: a size-1 axis
    transposes for free).

    ``rows`` [B] names val's rows of a cache that holds more (a row piece of
    the engine's prefill, scalar ``write_index``): the same chain, each row
    written in place at its own batch row."""
    tail = (0,) * (buf.ndim - 4)  # hd present on k/v, absent on ks/vs
    if rows is not None:
        for b in range(val.shape[0]):
            buf = jax.lax.dynamic_update_slice(
                buf, val[None, b : b + 1],
                (layer_idx, rows[b], 0, write_index) + tail
            )
        return buf
    if jnp.ndim(write_index) == 0:
        return jax.lax.dynamic_update_slice(
            buf, val[None], (layer_idx, 0, 0, write_index) + tail
        )
    for b in range(val.shape[0]):
        buf = jax.lax.dynamic_update_slice(
            buf, val[None, b : b + 1], (layer_idx, b, 0, write_index[b]) + tail
        )
    return buf


def _in_window(write_index, S: int, C: int, window):
    """Which cache slots lie inside a sliding window of ``window`` slots
    behind each of S queries written from ``write_index``: [1, S, C] for a
    scalar index, [B, S, C] for per-row slots (spec verify). Slot space, as
    the kernels: a left pad shifts queries and keys alike."""
    k_slot = jnp.arange(C)
    if jnp.ndim(write_index) == 0:
        q_slot = write_index + jnp.arange(S)
        return (k_slot[None, :] > q_slot[:, None] - window)[None]
    q_slot = write_index[:, None] + jnp.arange(S)[None, :]
    return k_slot[None, None, :] > q_slot[:, :, None] - window


def _write_kv(cache: dict, k, v, layer_idx, write_index, rows=None) -> dict:
    """Write a layer's new keys and values k, v [B, S, KV, hd] into the
    stacked cache at ``write_index`` (int8 with per-token scales where the
    cache is quantized), at the cache's batch rows ``rows`` where k and v
    are a piece of its rows. Scope ``kv_write``; shared by every family
    whose cache is ``init_kv_cache``'s."""
    with jax.named_scope("kv_write"):
        new = {"k": k.transpose(0, 2, 1, 3),  # [B, KV, S, hd] — cache-native
               "v": v.transpose(0, 2, 1, 3)}
        if is_quantized_cache(cache):
            new["k"], new["ks"] = _quantize_kv(new["k"])
            new["v"], new["vs"] = _quantize_kv(new["v"])
        # the numbers stored are a head's whatever the tile holds: heads
        # side by side where the cache keeps several a lane tile
        tile = cache["k"].shape[-1] // k.shape[-1]
        new["k"] = heads_to_tiles(new["k"], tile)
        new["v"] = heads_to_tiles(new["v"], tile)
        return dict(cache, **{
            name: _cache_write(cache[name], val, layer_idx, write_index, rows)
            for name, val in new.items()})


def _cache_attention(q, cache: dict, layer_idx, mask, q_per_kv: int,
                     attention_fn=None, stacked_attention_fn=None,
                     rows=None):
    """Attention of q [B, S, H, hd] over layer ``layer_idx`` of the stacked
    cache: ``stacked_attention_fn`` (the Pallas kernels, reading the cache
    in place), else ``attention_fn`` or the dense ``_attention`` over the
    extracted layer under ``mask`` (its batch rows ``rows`` where q is a
    piece of the cache's rows). Scope ``attn``."""
    with jax.named_scope("attn"):
        if stacked_attention_fn is not None:
            # reads the stacked cache in place (Pallas kernels): no
            # per-layer extraction copy materializes
            return stacked_attention_fn(q, cache, layer_idx)
        k_cache, v_cache = dequantize_cache_layer(
            cache, layer_idx, q.shape[-1])
        if rows is not None:
            k_cache, v_cache = k_cache[rows], v_cache[rows]
        k_cache = k_cache.astype(q.dtype)
        v_cache = v_cache.astype(q.dtype)
        if attention_fn is None:
            return _attention(q, k_cache, v_cache, mask, q_per_kv)
        return attention_fn(q, k_cache, v_cache, mask, q_per_kv)


def _block(
    x, lp, layer_idx, rope, mask, is_global, cache, write_index,
    cfg: LlamaConfig, attention_fn=None, stacked_attention_fn=None,
    cache_rows=None,
):
    """One decoder layer.

    ``cache`` holds the FULL stacked caches [L, B, KV, C, hd] (plus
    per-token scales when int8-quantized); only the [S]-token slice of layer
    ``layer_idx`` is written (a tiny in-place dynamic_update_slice on the
    scan carry). Carrying the whole cache and writing the small slice keeps
    decode HBM traffic at weights+cache-read — emitting per-layer caches as
    scan outputs would re-materialize the whole ~GB cache every decode
    step. ``write_index`` may be a [B] vector (see _cache_write) for the
    speculative verify step's per-row fills. ``cache_rows`` [B] names x's
    rows of a cache that holds more of them (``forward``).

    The ``jax.named_scope`` names here and in ``forward`` (embed, qkv,
    kv_write, attn, attn_out, mlp, lm_head) are metadata of the compiled
    program: a device trace is read by them
    (``core.profiling.hlo_scope_map``, README "Device time by layer"), so
    none carries a shape or a number."""
    P1 = cfg.norm_plus_one
    cos, sin = rope[0]
    if cfg.sliding_window:
        # per-layer global/sliding select: rope pair 1 and the windowed
        # mask apply on sliding layers (is_global is a traced per-layer
        # scalar from the scan xs). Static-gated: the Llama/Qwen traces
        # never build these selects.
        (cos_l, sin_l) = rope[1]
        cos = jnp.where(is_global, cos, cos_l)
        sin = jnp.where(is_global, sin, sin_l)
        in_window = _in_window(
            write_index, x.shape[1], mask.shape[-1], cfg.sliding_window)
        mask = mask & (is_global | in_window)

    # W8A8 only on MULTI-token forwards (prefill): decode's single-token
    # matmuls are HBM-bound and S is trace-static, so this gate adds no
    # device control flow. The spec VERIFY forward is multi-token but
    # decode-phase (per-row write_index is its signature): it must stay
    # exact — speculation promises greedy outputs identical to plain
    # decode, and plain decode scores these positions unquantized
    aq = cfg.w8a8_prefill and x.shape[1] > 1 and jnp.ndim(write_index) == 0
    with jax.named_scope("qkv"):
        h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps, P1)
        q = _proj("bsd,dhk->bshk", h, lp["wq"], aq)
        k = _proj("bsd,dhk->bshk", h, lp["wk"], aq)
        v = _proj("bsd,dhk->bshk", h, lp["wv"], aq)
        if cfg.qk_norm:
            # Qwen3/Gemma3: RMSNorm over each head's hd dim before RoPE
            q = _rmsnorm(q, lp["q_norm"], cfg.norm_eps, P1)
            k = _rmsnorm(k, lp["k_norm"], cfg.norm_eps, P1)
        if cfg.query_scale:
            # fold a non-default score scale (Gemma's query_pre_attn_scalar)
            # into q so every attention implementation (dense, ring, Pallas)
            # keeps its built-in 1/sqrt(head_dim)
            q = q * jnp.asarray(
                (cfg.head_dim ** 0.5) / (cfg.query_scale ** 0.5), q.dtype
            )
        q = _apply_rope(q, cos, sin)
        k = _apply_rope(k, cos, sin)

    cache = _write_kv(cache, k, v, layer_idx, write_index, cache_rows)
    attn = _cache_attention(q, cache, layer_idx, mask, cfg.q_per_kv,
                            attention_fn, stacked_attention_fn, cache_rows)
    with jax.named_scope("attn_out"):
        attn_out = _proj("bshk,hkd->bsd", attn, lp["wo"], aq)
        if cfg.sandwich_norms:
            attn_out = _rmsnorm(
                attn_out, lp["post_attn_norm"], cfg.norm_eps, P1
            )
        x = x + attn_out

    with jax.named_scope("mlp"):
        h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, P1)
        gate = _proj("bsd,di->bsi", h, lp["w_gate"], aq)
        up = _proj("bsd,di->bsi", h, lp["w_up"], aq)
        mlp_out = _proj(
            "bsi,id->bsd", _mlp_act(gate, cfg.act) * up, lp["w_down"], aq
        )
        if cfg.sandwich_norms:
            mlp_out = _rmsnorm(
                mlp_out, lp["post_ffw_norm"], cfg.norm_eps, P1
            )
        return x + mlp_out, cache


def forward(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,       # [B, S] int32
    positions: jax.Array,    # [B, S] int32 (RoPE positions, pad rows clipped)
    kv_cache: dict,          # {"k","v": [L, B, KV, C, hd]}
    write_index,             # cache slot of tokens[:, 0]: scalar, or [B]
    #                          vector for per-row slots (spec verify)
    mask: jax.Array,         # [B, S, C] bool over cache slots
    *,
    remat: bool = False,
    last_only: bool = False,
    attention_fn=None,
    stacked_attention_fn=None,
    cache_rows=None,
) -> tuple[jax.Array, dict]:
    """Run the decoder; returns (logits [B, S, vocab] f32, updated cache).

    ``last_only=True`` projects only the final position through the LM head
    (prefill sampling needs just that; a full [B, S, vocab] f32 tensor at
    S=2048 would be ~8 GB on the 128k vocab).

    ``attention_fn(q, k_cache, v_cache, mask, q_per_kv)`` overrides the
    dense cache attention on the extracted (dequantized) layer cache;
    ``stacked_attention_fn(q, cache, layer_idx)`` overrides it with a
    consumer of the FULL stacked cache dict (the Pallas kernels) and takes
    precedence.

    ``cache_rows`` [B] int32: the tokens are a row piece of a batch whose
    cache ``kv_cache`` is (the engine's prefill, ``Family.
    prefill_piece_tokens``) and row b of them lives at the cache's batch row
    ``cache_rows[b]`` — written and read there in place, the cache's other
    rows untouched. ``mask`` and a ``stacked_attention_fn`` are the
    piece's own."""
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype)
        if cfg.embed_scale:
            # Gemma scales hidden states by sqrt(dim), rounded through the
            # model dtype like the HF implementation's normalizer
            x = x * jnp.asarray(cfg.dim ** 0.5, cfg.dtype)
    with jax.named_scope("qkv"):  # the rope tables every layer's qkv reads
        rope = (_rope_cos_sin(cfg, positions),)
        if cfg.sliding_window:
            import dataclasses as _dc

            local_cfg = _dc.replace(
                cfg, rope_theta=cfg.rope_local_theta,
                use_llama3_rope_scaling=False, rope_linear_factor=0.0,
            )
            rope = rope + (_rope_cos_sin(local_cfg, positions),)
    flags = _layer_global_flags(cfg)

    block = _block
    if remat:
        block = jax.checkpoint(_block, static_argnums=(8, 9, 10))

    def layer_step(carry, xs):
        h, cache = carry
        lp, li, is_global = xs
        h, cache = block(
            h, lp, li, rope, mask, is_global, cache, write_index, cfg,
            attention_fn, stacked_attention_fn, cache_rows,
        )
        return (h, cache), None

    def stack(x, cache, first_cache_layer=None):
        """The layers once over; layer l writes and reads cache layer
        ``first_cache_layer + l`` (None: l, and nothing is added)."""
        cache_layer = jnp.arange(cfg.n_layers)
        if first_cache_layer is not None:
            cache_layer = cache_layer + first_cache_layer
        (x, cache), _ = jax.lax.scan(
            layer_step, (x, cache), (params["layers"], cache_layer, flags))
        return x, cache

    if cfg.loop_passes == 1:
        x, new_cache = stack(x, kv_cache)
    else:
        # a LOOPED stack: the same layers ``loop_passes`` times, pass t over
        # cache layers [t * L, (t + 1) * L), the final norm after every pass
        # (its output is the next pass's input). A scan over the passes and
        # not a Python loop: one copy of the stack in the program whatever
        # the number of passes, as the layer scan holds one copy of a layer
        def pass_step(carry, t):
            h, cache = stack(*carry, t * cfg.n_layers)
            with jax.named_scope("loop_norm"):
                h = _rmsnorm(h, params["final_norm"], cfg.norm_eps,
                             cfg.norm_plus_one)
            return (h, cache), None

        (x, new_cache), _ = jax.lax.scan(
            pass_step, (x, kv_cache), jnp.arange(cfg.loop_passes))

    with jax.named_scope("lm_head"):
        if last_only:
            x = x[:, -1:, :]
        if cfg.loop_passes == 1:   # a looped stack's last pass normed it
            x = _rmsnorm(
                x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
        logits = _lm_head_logits(x, params, cfg)
    return logits, new_cache


def _layer_global_flags(cfg: LlamaConfig) -> jax.Array:
    """[L] bool — which layers attend globally.

    With sliding_window set and no explicit layer_is_global, EVERY layer is
    sliding (Mistral-style) — a silent all-global fallback would make the
    window a no-op while still paying its dense-path costs."""
    if not cfg.sliding_window:
        return jnp.ones((cfg.n_layers,), dtype=bool)
    if not cfg.layer_is_global:
        return jnp.zeros((cfg.n_layers,), dtype=bool)
    if len(cfg.layer_is_global) != cfg.n_layers:
        raise ValueError(
            f"layer_is_global has {len(cfg.layer_is_global)} entries "
            f"for {cfg.n_layers} layers"
        )
    return jnp.asarray(cfg.layer_is_global, dtype=bool)


def dense_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, q_per_kv: int):
    """Full causal attention without a cache (training path).

    k/v arrive projection-shaped [B, S, KV, hd]; _attention consumes the
    cache-native head-major layout, so transpose here (cheap next to the
    training matmuls)."""
    B, S = q.shape[0], q.shape[1]
    i = jnp.arange(S)[None, :, None]
    j = jnp.arange(S)[None, None, :]
    mask = jnp.broadcast_to(j <= i, (B, S, S))
    return _attention(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), mask, q_per_kv
    )


def cache_free_block(x, lp, cos, sin, cfg: LlamaConfig, attention_fn):
    """One cache-free decoder layer; returns (x, (k, v)) with k/v
    projection-shaped [B, S, KV, hd]. Shared by forward_train (which
    discards the k/v) and the long-context ring prefill (which stacks them
    into the frozen prefill cache) — ONE copy of the block math.

    Sliding-window (Gemma local) layers are NOT supported on this path —
    ring attention streams global K/V blocks; callers gate on
    cfg.sliding_window."""
    P1 = cfg.norm_plus_one
    h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps, P1)
    q = _proj("bsd,dhk->bshk", h, lp["wq"])
    k = _proj("bsd,dhk->bshk", h, lp["wk"])
    v = _proj("bsd,dhk->bshk", h, lp["wv"])
    if cfg.qk_norm:
        # Qwen3/Gemma3: RMSNorm over each head's hd dim before RoPE
        q = _rmsnorm(q, lp["q_norm"], cfg.norm_eps, P1)
        k = _rmsnorm(k, lp["k_norm"], cfg.norm_eps, P1)
    if cfg.query_scale:
        q = q * jnp.asarray(
            (cfg.head_dim ** 0.5) / (cfg.query_scale ** 0.5), q.dtype
        )
    q = _apply_rope(q, cos, sin)
    k = _apply_rope(k, cos, sin)
    attn = attention_fn(q, k, v, cfg.q_per_kv)
    attn_out = _proj("bshk,hkd->bsd", attn, lp["wo"])
    if cfg.sandwich_norms:
        attn_out = _rmsnorm(attn_out, lp["post_attn_norm"], cfg.norm_eps, P1)
    x = x + attn_out
    h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, P1)
    gate = _proj("bsd,di->bsi", h, lp["w_gate"])
    up = _proj("bsd,di->bsi", h, lp["w_up"])
    mlp_out = _proj("bsi,id->bsd", _mlp_act(gate, cfg.act) * up, lp["w_down"])
    if cfg.sandwich_norms:
        mlp_out = _rmsnorm(mlp_out, lp["post_ffw_norm"], cfg.norm_eps, P1)
    return x + mlp_out, (k, v)


def forward_train(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,        # [B, S] int32
    *,
    attention_fn=None,        # (q, k, v, q_per_kv) -> out; default dense causal
    remat: bool = True,
) -> jax.Array:
    """Cache-free causal forward for training; returns logits [B, S, V] f32.

    ``attention_fn`` is the sequence-parallelism seam: pass
    parallel.ring.ring_attention (wrapped over a mesh) to run blockwise ring
    attention over a sharded sequence axis instead of dense attention.
    """
    B, S = tokens.shape
    if cfg.sliding_window:
        raise NotImplementedError(
            "sliding-window (Gemma local) layers are not supported on the "
            "cache-free train/ring path; use the KV-cache forward"
        )
    attention_fn = attention_fn or dense_causal_attention
    x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.dim ** 0.5, cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    cos, sin = _rope_cos_sin(cfg, positions)

    def block(x, lp):
        x, _ = cache_free_block(x, lp, cos, sin, cfg, attention_fn)
        return x

    if remat:
        block = jax.checkpoint(block)

    def layer_step(carry, lp):
        return block(carry, lp), None

    def pass_step(x, _):
        # the layers once over and the final norm: the whole of a plain
        # stack, one pass of a looped one (``forward``)
        x, _ = jax.lax.scan(layer_step, x, params["layers"])
        return _rmsnorm(
            x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one), None

    if cfg.loop_passes == 1:
        x, _ = pass_step(x, None)
    else:
        x, _ = jax.lax.scan(pass_step, x, None, length=cfg.loop_passes)
    return _lm_head_logits(x, params, cfg)


# -- mask / position helpers (host-independent, shape-static) ----------------


def prefill_attention_mask(pad_lens: jax.Array, seq_len: int, cache_len: int):
    """Left-padded causal mask: query i attends cache slot j iff
    pad_b <= j <= i. [B, S, C]."""
    i = jnp.arange(seq_len)[None, :, None]
    j = jnp.arange(cache_len)[None, None, :]
    pad = pad_lens[:, None, None]
    return (j >= pad) & (j <= i)


def decode_attention_mask(pad_lens: jax.Array, fill: jax.Array, cache_len: int):
    """Single-token step: attend j iff pad_b <= j <= fill. [B, 1, C]."""
    j = jnp.arange(cache_len)[None, None, :]
    pad = pad_lens[:, None, None]
    return (j >= pad) & (j <= fill)


def prefill_positions(pad_lens: jax.Array, seq_len: int) -> jax.Array:
    """RoPE positions for left-padded prompts: max(0, i - pad). [B, S]."""
    i = jnp.arange(seq_len)[None, :]
    return jnp.maximum(0, i - pad_lens[:, None])


def verify_attention_mask(
    pad_lens: jax.Array, fills: jax.Array, num_q: int, cache_len: int
):
    """Speculative verify step: ``num_q`` query tokens per row sit at
    per-row cache slots fills_b .. fills_b + num_q - 1; query i attends
    j iff pad_b <= j <= fills_b + i. [B, num_q, C]. With num_q=1 and a
    shared fill this degenerates to decode_attention_mask."""
    j = jnp.arange(cache_len)[None, None, :]
    pad = pad_lens[:, None, None]
    limit = (fills[:, None] + jnp.arange(num_q)[None, :])[:, :, None]
    return (j >= pad) & (j <= limit)


def verify_positions(
    pad_lens: jax.Array, fills: jax.Array, num_q: int
) -> jax.Array:
    """RoPE positions of the verify queries: (fills_b - pad_b) + i. [B, S]."""
    return (fills - pad_lens)[:, None] + jnp.arange(num_q)[None, :]


# -- the engine's seam (models/family.py) -------------------------------------


def _kernels_supported(cfg: LlamaConfig, interpret: bool) -> bool:
    # the kernels want heads of whole lane tiles or half of one
    # (ops/flash_attention.head_dim_supported); interpret mode has no limit
    from ..ops.flash_attention import head_dim_supported

    return head_dim_supported(cfg.head_dim) or interpret


def _attention_supported(cfg: LlamaConfig, S: int, C: int):
    from ..ops.decode_attention import supports_decode
    from ..ops.flash_attention import supports_flash

    return supports_flash(S, C, cfg.head_dim), supports_decode(C, cfg.head_dim)


def _group_of(q, cache: dict) -> int:
    """Query heads a KV head, off the operands: q [B, S, H, hd] over the
    stacked cache [L, B, KV, C, hd] (or its KV heads two a lane tile,
    ``init_kv_cache``). A family whose layers differ in their query heads
    (models/laguna.py) hands each layer's own queries in."""
    # all the queries' lanes over all the keys', however the heads tile
    return q.shape[2] * q.shape[3] // (
        cache["k"].shape[2] * cache["k"].shape[4])


def _prefill_attention(cfg: LlamaConfig, mesh, interpret: bool, pad_lens,
                       layer_window, q_offset: int = 0, cache_rows=None):
    """Flash/sharded-flash stacked-attention fn for a prefill-style forward
    whose queries start at cache slot ``q_offset`` (0 = whole prompt;
    chunked prefill passes each chunk's start). ``pad_lens`` and
    ``cache_rows`` are those of a row piece where the forward runs one
    (``forward``)."""
    if mesh is not None:
        from ..ops.sharded import sharded_flash_prefill

        def stacked_fn(q, cache, layer_idx):
            return sharded_flash_prefill(
                mesh, q, cache, layer_idx, pad_lens, _group_of(q, cache),
                layer_window(layer_idx), q_offset, cache_rows,
                interpret=interpret,
            )
    else:
        from ..ops.flash_attention import flash_prefill_attention

        def stacked_fn(q, cache, layer_idx):
            return flash_prefill_attention(
                q, cache, layer_idx, pad_lens, _group_of(q, cache),
                layer_window(layer_idx), q_offset, cache_rows,
                interpret=interpret,
            )

    return stacked_fn


def _decode_attention(cfg: LlamaConfig, mesh, interpret: bool, pad_lens,
                      S: int, t, layer_window):
    """Stacked-attention fn of decode step ``t`` after a prompt bucket of
    ``S``: its token sits at cache slot ``S + t``, summed where the layer
    calls for it, as the program has always traced it."""
    if mesh is not None:
        from ..ops.sharded import sharded_flash_decode

        def stacked_fn(q, cache, layer_idx):
            return sharded_flash_decode(
                mesh, q, cache, layer_idx, pad_lens, S + t,
                _group_of(q, cache), layer_window(layer_idx),
                interpret=interpret,
            )
    else:
        from ..ops.decode_attention import flash_decode_attention

        def stacked_fn(q, cache, layer_idx):
            return flash_decode_attention(
                q, cache, layer_idx, pad_lens, S + t,
                _group_of(q, cache), layer_window(layer_idx),
                interpret=interpret,
            )

    return stacked_fn


def _layer_windows(cfg: LlamaConfig):
    """Each layer's window for the kernels (``Family.layer_windows``): the
    host's reading of ``_layer_global_flags``."""
    if not cfg.sliding_window:
        return None
    flags = cfg.layer_is_global or (False,) * cfg.n_layers
    if len(flags) != cfg.n_layers:
        raise ValueError(
            f"layer_is_global has {len(flags)} entries for "
            f"{cfg.n_layers} layers")
    # a cache layer a (pass, layer): every pass's layers, in the cache's order
    return tuple(0 if g else cfg.sliding_window
                 for g in flags) * cfg.loop_passes


def _prefill_counts(cfg: LlamaConfig, pad_lens, spans, cache_len) -> dict:
    """What a LOOPED stack's prefill kernel computed against what its
    attention needs (``Family.prefill_counts``), from the pads a dispatch
    was packed with: ``scores_computed`` (the cells the kernel fetched,
    interior and edge, by its own rule x the tile's area) and
    ``scores_needed`` (the causal pairs of real tokens), both x query heads
    x ``cache_layers`` — every (pass, layer) runs the kernel. What a tile
    trades at one query head a KV head: a wider tile steps less and
    computes more above the diagonal and under the pads. Nothing for a
    plain stack (its counts are what they were) and for window layers
    (``window_scores_*``, the engine's)."""
    if cfg.loop_passes == 1 or cfg.sliding_window:
        return {}
    import numpy as np

    from ..ops.flash_attention import prefill_block_class_grid

    pads = np.asarray(pad_lens, np.int64)
    computed = needed = 0
    for lo, hi in spans:
        grid, (bq, bk) = prefill_block_class_grid(
            pad_lens, hi - lo, cache_len, lo, 0, cfg.q_per_kv, cfg.head_dim)
        computed += int((grid >= 2).sum()) * bq * bk   # interior and edge
        # a real query at slot i sees the i + 1 - pad keys of its row
        needed += int(np.clip(
            np.arange(lo, hi)[None, :] + 1 - pads[:, None], 0, None).sum())
    heads = cfg.n_heads * cache_layers(cfg)
    return {"scores_computed": computed * heads,
            "scores_needed": needed * heads}


def _config_missing(cfg: LlamaConfig) -> dict:
    """Engine entries THIS config cannot run (``Family.config_missing``):
    a looped stack on the entries that know no pass."""
    if cfg.loop_passes == 1:
        return {}
    return {
        "long-context backend": (
            f"a stack looped {cfg.loop_passes} times: the ring prefill "
            "(backend/long_context.py) stacks ONE pass's keys and values "
            "into its frozen cache, a layer of weights a layer of cache, "
            "and its decode step scans the layers once"),
    }


def _family():
    from .family import Family

    return Family(
        name="llama", forward=forward, init_cache=init_kv_cache,
        init_params=init_params, kernels_supported=_kernels_supported,
        attention_supported=_attention_supported,
        prefill_attention=_prefill_attention,
        decode_attention=_decode_attention, counts_prefill_blocks=True,
        layer_windows=_layer_windows, attention_layers=cache_layers,
        config_missing=_config_missing, prefill_counts=_prefill_counts,
        # a row of one 2,048-token chunk: 4,096 operations a weight byte
        # (W8A8), far over the v5e's ~480; measured in PERF.md, PR 48
        prefill_piece_tokens=2048,
    )


FAMILY = _family()
