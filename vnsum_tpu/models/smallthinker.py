"""SmallThinker family in functional JAX: grouped-query attention with
rotary sliding-window layers and position-free global layers, and a sparse
ReGLU expert layer whose router reads the layer's input, for the one-shot
generation program.

A third family behind ``models/family.py``. Its attention is
``models/llama.py``'s — the ``[L, B, KV, C, hd]`` cache (``init_kv_cache``,
int8 with per-token scales), ``_write_kv``, ``_cache_attention`` and the two
flash kernels with the per-layer window — and its feed-forward is the
expert layer of ``models/experts.py`` that ``models/deepseek.py`` runs too.
What it owns is the config, the parameters, its routing rule, the block and
``forward``. ``FAMILY`` at the end is what the engine's seam picks up for a
``SmallThinkerConfig``.

The layer (``benchmarks/reference_smallthinker.py`` is the same equations
in plain float32), for layer ``l`` with input ``x``:

- **Router, first.** ``s = x W_r`` in float32 on the layer's INPUT, before
  any norm; ``ids = top_k(s)``, ``w = softmax(s[ids])`` over the picked
  logits alone. No groups, no scaling factor, no shared expert. Nothing of
  it hangs on the attention's output, so it can be scheduled beside it.
- **Attention.** ``h = RMSNorm(x)``; q, k, v with no bias and no QK-norm;
  rotary (half-split pairs, all of ``head_dim``) on the layers whose
  ``rope_layout`` is 1 and NO position encoding on the others; causal GQA,
  on the layers whose ``sliding_window_layout`` is 1 over the last
  ``sliding_window`` positions alone; ``x' = x + attn W_o``.
- **Experts.** ``h' = RMSNorm(x')``; ``y = sum_e w_e (relu(h' G_e) * (h'
  U_e)) D_e`` over the picks; ``out = x' + y``. Every layer is an expert
  layer and every expert is held here.

State a program carries (``init_cache``): the KV cache — full length for
window layers too; a cache sized per layer kind is ROADMAP B3 — and the
expert counters of ``models/experts.py`` with the decode steps' distinct
experts (``decode_touched``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from .experts import (
    EXPERT_LEAVES,
    counters,
    expert_layer,
    grouped_experts,
    init_expert_state,
    last_picks,
)
from .llama import (
    _apply_rope,
    _attention_supported,
    _cache_attention,
    _decode_attention,
    _embed_lookup,
    _in_window,
    _kernels_supported,
    _lm_head_logits,
    _prefill_attention,
    _proj,
    _rmsnorm,
    _write_kv,
    init_kv_cache,
)


@dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151_936
    dim: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate: int = 768
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    rope_theta: float = 1_500_000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 16_384
    tie_embeddings: bool = False
    act: str = "relu"
    sliding_window: int = 4096
    # per layer, 1 = the layer attends inside the window / applies rotary;
    # empty = the published period [0, 1, 1, 1] repeated over the layers
    sliding_window_layout: tuple = ()
    rope_layout: tuple = ()
    # W8A8 on multi-token forwards, as LlamaConfig's; the engine sets it
    w8a8_prefill: bool = False
    dtype: Any = field(default=jnp.bfloat16)

    def __post_init__(self):
        period = (0, 1, 1, 1)
        for name in ("sliding_window_layout", "rope_layout"):
            layout = tuple(getattr(self, name)) or tuple(
                period[i % 4] for i in range(self.n_layers))
            if len(layout) != self.n_layers:
                raise ValueError(f"{name} has {len(layout)} entries for "
                                 f"{self.n_layers} layers")
            object.__setattr__(self, name, layout)
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    # what ``models/experts.py`` asks of a config: every expert is held
    expert_offset = 0

    @property
    def n_held(self) -> int:
        return self.n_routed_experts

    @property
    def intermediate(self) -> int:
        """The feed-forward width under the name every config has: there
        is no dense feed-forward, every layer's is its experts'."""
        return self.moe_intermediate


def smallthinker_21b_a3b(**kw) -> SmallThinkerConfig:
    """PowerInfer/SmallThinker-21BA3B-Instruct ``config.json``, uncut."""
    return SmallThinkerConfig(**kw)


def tiny_smallthinker(**kw) -> SmallThinkerConfig:
    """Small config for hermetic CPU tests: two periods of [global, window,
    window, window], 8 experts top-2, a window shorter than the prompts."""
    base = dict(
        vocab_size=384, dim=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=16, moe_intermediate=32, n_routed_experts=8,
        num_experts_per_tok=2, rope_theta=10_000.0, max_seq_len=256,
        sliding_window=24, dtype=jnp.float32,
    )
    base.update(kw)
    return SmallThinkerConfig(**base)


# -- parameters and state -----------------------------------------------------


def init_params(key: jax.Array, cfg: SmallThinkerConfig) -> dict:
    """Random init, every layer on a leading layer dim."""
    L, D, H, KV, hd = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim)
    F, E = cfg.moe_intermediate, cfg.n_held
    keys = iter(jax.random.split(key, 16))

    def norm(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale
                ).astype(cfg.dtype)

    return {
        "embed": norm((cfg.vocab_size, D)),
        "layers": {
            "attn_norm": jnp.ones((L, D), cfg.dtype),
            "wq": norm((L, D, H, hd)), "wk": norm((L, D, KV, hd)),
            "wv": norm((L, D, KV, hd)), "wo": norm((L, H, hd, D)),
            "mlp_norm": jnp.ones((L, D), cfg.dtype),
            "router": norm((L, D, cfg.n_routed_experts)),
            "we_gate": norm((L, E, D, F)), "we_up": norm((L, E, D, F)),
            "we_down": norm((L, E, F, D)),
        },
        "final_norm": jnp.ones((D,), cfg.dtype),
        "lm_head": norm((D, cfg.vocab_size)),
    }


def init_cache(cfg: SmallThinkerConfig, batch: int, cache_len: int, *,
               quantized: bool = False) -> dict:
    """What a program carries: llama's KV cache (every layer at full
    length) and the expert counters."""
    return {
        **init_kv_cache(cfg, batch, cache_len, quantized=quantized),
        **init_expert_state(cfg.n_layers, cfg.n_held, batch,
                            cfg.num_experts_per_tok, decode_touched=True),
    }


# -- the block and forward ----------------------------------------------------


def route(logits: jax.Array, top_k: int):
    """logits [T, E] float32 -> (expert ids [T, k] int32, weights [T, k]):
    the ``top_k`` largest logits, softmax over those alone."""
    picked, ids = jax.lax.top_k(logits, top_k)
    return ids.astype(jnp.int32), jax.nn.softmax(picked, axis=-1)


def _block(x, lp, layer_idx, rope, mask, window, rotary, valid, cache,
           write_index, cfg: SmallThinkerConfig, experts,
           stacked_attention_fn=None, experts_fn=None):
    """One layer. ``window`` (0 = global) and ``rotary`` (0 = no position
    encoding) are the layer's traced scalars from the scan. The
    ``jax.named_scope`` names are metadata a device trace is read by
    (README "Device time by layer")."""
    B, S, D = x.shape
    aq = cfg.w8a8_prefill and S > 1
    with jax.named_scope("router"):
        # on the layer's input itself: nothing here waits for the attention
        ids, weights = route(
            jnp.einsum("td,de->te", x.reshape(B * S, D).astype(jnp.float32),
                       lp["router"].astype(jnp.float32)),
            cfg.num_experts_per_tok)
    with jax.named_scope("qkv"):
        h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q = _proj("bsd,dhk->bshk", h, lp["wq"], aq)
        k = _proj("bsd,dhk->bshk", h, lp["wk"], aq)
        v = _proj("bsd,dhk->bshk", h, lp["wv"], aq)
        # a layer without rotary turns by the angle 0
        cos = jnp.where(rotary > 0, rope[0], 1.0)
        sin = jnp.where(rotary > 0, rope[1], 0.0)
        q = _apply_rope(q, cos, sin)
        k = _apply_rope(k, cos, sin)
    cache = _write_kv(cache, k, v, layer_idx, write_index)
    if stacked_attention_fn is None:
        # the dense path's mask; the kernels take the window as a scalar
        mask = mask & ((window == 0) | _in_window(
            write_index, S, mask.shape[-1], window))
    attn = _cache_attention(q, cache, layer_idx, mask, cfg.q_per_kv,
                            None, stacked_attention_fn)
    with jax.named_scope("attn_out"):
        x = x + _proj("bshk,hkd->bsd", attn, lp["wo"], aq)

    h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    routed, cache = expert_layer(
        h.reshape(B * S, D), lambda: (ids, weights), valid, experts,
        layer_idx, cache, cfg, experts_fn, rows=B)
    return x + routed.reshape(B, S, D).astype(x.dtype), cache


def forward(params: dict, cfg: SmallThinkerConfig, tokens, positions, cache,
            write_index, mask, *, last_only: bool = False,
            stacked_attention_fn=None, experts_fn=None):
    """Run the decoder over ``tokens`` [B, S] written at cache slots
    ``write_index ..``; returns (logits [B, S, vocab] float32, cache).

    ``stacked_attention_fn(q, cache, layer_idx)`` is the phase's kernel over
    the stacked cache (llama's, with this family's per-layer window); None
    is the dense XLA attention under ``mask`` [B, S, C].
    ``experts_fn(x, local, weights, experts, slot)`` is the routed experts'
    product (``grouped_experts``); None is ``dense_experts``."""
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    with jax.named_scope("qkv"):  # the rope table the rotary layers read
        half = cfg.head_dim // 2
        inv = 1.0 / (cfg.rope_theta ** (
            jnp.arange(0, half, dtype=jnp.float32) / half))
        angles = positions[..., None].astype(jnp.float32) * inv
        rope = (jnp.cos(angles), jnp.sin(angles))
    # a token under a row's left pad attends nothing: it is routed nowhere
    # and counted nowhere
    valid = jnp.any(mask, axis=-1)

    def layer_step(carry, xs):
        h, cache = carry
        lp, li, window, rotary = xs
        h, cache = _block(h, lp, li, rope, mask, window, rotary, valid,
                          cache, write_index, cfg, experts,
                          stacked_attention_fn, experts_fn)
        return (h, cache), None

    # the experts stay out of the scan's slices: the grouped product reads
    # the stack in place, by the layer's index
    experts = {n: params["layers"][n] for n in EXPERT_LEAVES}
    scanned = {n: w for n, w in params["layers"].items()
               if n not in EXPERT_LEAVES}
    (x, cache), _ = jax.lax.scan(
        layer_step, (x, cache),
        (scanned, jnp.arange(cfg.n_layers),
         jnp.asarray(layer_windows(cfg), jnp.int32),
         jnp.asarray(cfg.rope_layout, jnp.int32)))
    with jax.named_scope("lm_head"):
        if last_only:
            x = x[:, -1:, :]
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head_logits(x, params, cfg)
    return logits, cache


def forward_dense(params: dict, cfg: SmallThinkerConfig, tokens) -> jax.Array:
    """Cache-free causal forward of whole sequences [B, S] with no kernel:
    logits [B, S, vocab] float32. (Keys and values still pass through a
    cache of exactly S slots.)"""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    logits, _ = forward(params, cfg, tokens, positions,
                        init_cache(cfg, B, S), 0, mask)
    return logits


# -- the engine's seam (models/family.py) -------------------------------------


def layer_windows(cfg: SmallThinkerConfig) -> tuple:
    """Each layer's window in cache slots, 0 where it attends globally."""
    return tuple(cfg.sliding_window if w else 0
                 for w in cfg.sliding_window_layout)


def _forward_kwargs(cfg: SmallThinkerConfig, kernels: bool, interpret: bool):
    if not kernels:
        return {}   # flash=False: dense attention and dense_experts
    return {"experts_fn": functools.partial(
        grouped_experts, cfg=cfg, interpret=interpret)}


def _family():
    from .family import Family

    carries_counters = (
        "its programs carry a KV cache alone; this family's state holds the "
        "expert counters and picks beside the keys and values")
    return Family(
        name="smallthinker", forward=forward, init_cache=init_cache,
        init_params=init_params, kernels_supported=_kernels_supported,
        attention_supported=_attention_supported,
        prefill_attention=_prefill_attention,
        decode_attention=_decode_attention, counts_prefill_blocks=True,
        layer_windows=layer_windows, forward_kwargs=_forward_kwargs,
        counters=counters, row_record=last_picks,
        missing={
            "slot loop": (
                "the slot programs (backend/inflight.py, engine._make_slot_*"
                ", _make_adopt_fn) scatter every leaf of a joined batch's "
                "cache on its second axis, as keys and values, and return "
                "no counters: " + carries_counters),
            "prefix cache": (
                "the resume program (cache/store.py gather, engine."
                "_prepare_resume) seeds a KV cache alone and returns the "
                "final cache in the counters' place: " + carries_counters),
            "mesh": (
                "parallel/sharding.py has no specs for the router and the "
                "stacked experts, no expert axis and no exchange of the "
                "experts' partial sums"),
            "speculative decoding": (
                "the verify step writes the cache at per-row slots and its "
                "host loop hands a KV cache from step to step: "
                + carries_counters),
            "long-context backend": (
                "the ring prefill runs models.llama.cache_free_block, "
                "which has neither a window nor an expert layer"),
        },
    )


FAMILY = _family()
