"""Laguna family in functional JAX: grouped-query attention whose query
heads differ by layer kind (48 on full layers, 72 on sliding-window ones), a
per-head output gate, two rotary schemes, a leading dense layer and sparse
SwiGLU experts with a shared one, for the one-shot generation program.

A fourth family behind ``models/family.py``. Its cache, ``_write_kv``,
``_cache_attention`` and the two flash kernels are ``models/llama.py``'s
(the kernels read the group G off the queries they are handed, so one
program runs them at G = 6 and G = 9); its expert layer is
``models/experts.py``'s. What it owns is the config, the parameters, its
routing rule, the blocks and ``forward``. ``FAMILY`` at the end is what the
engine's seam picks up for a ``LagunaConfig``.

The layer (``benchmarks/reference_laguna.py`` is the same equations in plain
float32), for layer ``l`` with input ``x``:

- **Attention.** ``h = RMSNorm(x)``; ``q = h W_q`` as ``[H_l, head_dim]``
  with ``H_l`` = ``n_heads`` on full layers and ``n_heads_sliding`` on
  sliding ones; k, v as ``[n_kv_heads, head_dim]``; no bias, no QK-norm.
  Rotary with rotate-half pairing: on full layers the first
  ``partial_rotary_factor`` of each head's dims turn and the rest pass, by
  YaRN frequencies (``rope_theta`` over the rotary dims, ``rope_factor``,
  ``rope_original_max_len``, a linear ramp between the correction dims of
  ``rope_beta_fast`` and ``rope_beta_slow``) with cos and sin times
  ``rope_attention_factor``; on sliding layers every dim turns, plain, at
  ``rope_local_theta``. Causal GQA, on sliding layers over the last
  ``sliding_window`` positions alone.
- **Gate.** ``g = sigmoid(h W_g)``, ``W_g: [D, H_l]``: one scalar a head and
  token, on the attention's normed input; each head's output is scaled by
  its gate before ``W_o``; ``x' = x + (g * a) W_o``.
- **Feed-forward.** ``h' = RMSNorm(x')``. The leading ``n_dense_layers``
  layers: a dense SwiGLU of ``intermediate``. The others: ``s =
  softmax(h' W_r)`` in float32 over all experts; ``ids = top_k(s)``; ``w =
  s[ids] / sum(s[ids]) * routed_scaling_factor``; ``y = sum_e w_e
  SwiGLU_e(h') + SwiGLU_shared(h')``; ``out = x' + y``.

Layers of unequal shape are not padded to one: the parameters are grouped
by what is stacked together — ``dense`` (the leading layers whole: full
attention and the dense feed-forward), ``full`` and ``sliding`` (the
attention of the sparse layers of each kind) and ``layers`` (every sparse
layer's feed-forward: norm, router, experts, shared expert) — and the stack
runs as the leading layers, then ONE ``lax.scan`` over whole periods of the
layout (published: sliding, sliding, sliding, full), then what is left of a
period. The cache index is the layer's own throughout.

State a program carries (``init_cache``): llama's KV cache — full length
for sliding layers too; a cache sized per layer kind is ROADMAP B3 — and the
expert counters of ``models/experts.py`` with ``decode_touched``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from .experts import (
    EXPERT_LEAVES,
    _EXPERT_PIECE_TOKENS,
    by_rows,
    counters,
    expert_layer,
    grouped_experts,
    init_expert_state,
    last_picks,
)
from .llama import (
    _attention_supported,
    _cache_attention,
    _decode_attention,
    _embed_lookup,
    _in_window,
    _kernels_supported,
    _lm_head_logits,
    _mlp_act,
    _prefill_attention,
    _proj,
    _rmsnorm,
    _write_kv,
    init_kv_cache,
    yarn_inv_freq,
)

_PERIOD = (0, 1, 1, 1)   # published layer_types: full, then three sliding


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100_352
    dim: int = 3072
    n_layers: int = 48
    n_heads: int = 48            # query heads of a full-attention layer
    n_heads_sliding: int = 72    # ... of a sliding-window layer
    n_kv_heads: int = 8
    head_dim: int = 128
    intermediate: int = 12_288   # the leading dense layers' SwiGLU
    n_dense_layers: int = 1      # mlp_only_layers [0]
    moe_intermediate: int = 1024
    shared_intermediate: int = 1024
    n_routed_experts: int = 256
    num_experts_per_tok: int = 10
    routed_scaling_factor: float = 2.5
    sliding_window: int = 512
    # per layer, 1 = the layer attends inside the window; empty = the
    # published period [0, 1, 1, 1] repeated over the layers
    sliding_layout: tuple = ()
    # full layers: YaRN over the leading partial_rotary_factor of a head
    rope_theta: float = 500_000.0
    rope_factor: float = 128.0
    rope_original_max_len: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.4852030263919618
    partial_rotary_factor: float = 0.5
    # sliding layers: plain rotary over the whole head
    rope_local_theta: float = 10_000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 1_048_576
    tie_embeddings: bool = False
    act: str = "silu"
    # W8A8 on multi-token forwards, as LlamaConfig's; the engine sets it
    w8a8_prefill: bool = False
    dtype: Any = field(default=jnp.bfloat16)

    def __post_init__(self):
        layout = tuple(self.sliding_layout) or tuple(
            _PERIOD[i % 4] for i in range(self.n_layers))
        if len(layout) != self.n_layers:
            raise ValueError(f"sliding_layout has {len(layout)} entries for "
                             f"{self.n_layers} layers")
        object.__setattr__(self, "sliding_layout", layout)
        if any(layout[:self.n_dense_layers]):
            raise ValueError("the leading dense layers are stacked with "
                             "full attention; sliding_layout says otherwise")
        for heads in (self.n_heads, self.n_heads_sliding):
            if heads % self.n_kv_heads:
                raise ValueError("n_kv_heads must divide the query heads of "
                                 "either layer kind")
        if self.rotary_dims % 2 or not 0 < self.rotary_dims <= self.head_dim:
            raise ValueError(f"partial_rotary_factor {self.partial_rotary_factor}"
                             f" of head_dim {self.head_dim} is no even width")
        periods, rest = self.periods
        period = self.period
        if self.sparse_layout != (period * (periods + 1))[
                :periods * len(period) + len(rest)]:
            raise ValueError("the sparse layers' sliding_layout does not "
                             f"repeat its period {period}")

    @property
    def n_sparse_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def sparse_layout(self) -> tuple:
        return self.sliding_layout[self.n_dense_layers:]

    @property
    def period(self) -> tuple:
        """The sparse layers' repeating unit: up to and with their first
        full layer (published: sliding, sliding, sliding, full)."""
        layout = self.sparse_layout
        return layout[:layout.index(0) + 1] if 0 in layout else layout

    @property
    def periods(self) -> tuple:
        """(whole periods among the sparse layers, the kinds left over)."""
        n = len(self.period)
        whole = self.n_sparse_layers // n if n else 0
        return whole, self.sparse_layout[whole * n:]

    @property
    def rotary_dims(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def heads(self, sliding) -> int:
        return self.n_heads_sliding if sliding else self.n_heads

    @property
    def heads_per_layer(self) -> tuple:
        return tuple(self.heads(s) for s in self.sliding_layout)

    # what ``models/experts.py`` asks of a config: every expert is held
    expert_offset = 0

    @property
    def n_held(self) -> int:
        return self.n_routed_experts


def laguna_s_2_1(**kw) -> LagunaConfig:
    """poolside/Laguna-S-2.1 ``config.json``, uncut."""
    return LagunaConfig(**kw)


def tiny_laguna(**kw) -> LagunaConfig:
    """Small config for hermetic CPU tests: the dense layer and two periods
    of [sliding, sliding, sliding, full], 3 and 2 query heads a KV head, 16
    experts top-4, a window shorter than the prompts."""
    base = dict(
        vocab_size=384, dim=64, n_layers=9, n_heads=4, n_heads_sliding=6,
        n_kv_heads=2, head_dim=16, intermediate=96, moe_intermediate=32,
        shared_intermediate=32, n_routed_experts=16, num_experts_per_tok=4,
        sliding_window=24, rope_original_max_len=64, rope_factor=8.0,
        max_seq_len=256, dtype=jnp.float32,
    )
    base.update(kw)
    return LagunaConfig(**base)


# -- parameters and state -----------------------------------------------------


def init_params(key: jax.Array, cfg: LagunaConfig) -> dict:
    """Random init, each group on a leading layer dim of its own."""
    D, KV, hd = cfg.dim, cfg.n_kv_heads, cfg.head_dim
    F, Fs, E = cfg.moe_intermediate, cfg.shared_intermediate, cfg.n_held
    Ld, Ls, I = cfg.n_dense_layers, cfg.n_sparse_layers, cfg.intermediate
    keys = iter(jax.random.split(key, 40))

    def norm(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale
                ).astype(cfg.dtype)

    def attention(L, H):
        return {
            "attn_norm": jnp.ones((L, D), cfg.dtype),
            "wq": norm((L, D, H, hd)), "wk": norm((L, D, KV, hd)),
            "wv": norm((L, D, KV, hd)), "attn_gate": norm((L, D, H)),
            "wo": norm((L, H, hd, D)),
        }

    n_sliding = sum(cfg.sparse_layout)
    return {
        "embed": norm((cfg.vocab_size, D)),
        "dense": {
            **attention(Ld, cfg.n_heads),
            "mlp_norm": jnp.ones((Ld, D), cfg.dtype),
            "w_gate": norm((Ld, D, I)), "w_up": norm((Ld, D, I)),
            "w_down": norm((Ld, I, D)),
        },
        "full": attention(Ls - n_sliding, cfg.n_heads),
        "sliding": attention(n_sliding, cfg.n_heads_sliding),
        "layers": {
            "mlp_norm": jnp.ones((Ls, D), cfg.dtype),
            "router": norm((Ls, D, cfg.n_routed_experts)),
            "we_gate": norm((Ls, E, D, F)), "we_up": norm((Ls, E, D, F)),
            "we_down": norm((Ls, E, F, D)),
            "ws_gate": norm((Ls, D, Fs)), "ws_up": norm((Ls, D, Fs)),
            "ws_down": norm((Ls, Fs, D)),
        },
        "final_norm": jnp.ones((D,), cfg.dtype),
        "lm_head": norm((D, cfg.vocab_size)),
    }


def init_cache(cfg: LagunaConfig, batch: int, cache_len: int, *,
               quantized: bool = False) -> dict:
    """What a program carries: llama's KV cache (every layer at full
    length) and the sparse layers' expert counters."""
    return {
        **init_kv_cache(cfg, batch, cache_len, quantized=quantized),
        **init_expert_state(cfg.n_sparse_layers, cfg.n_held, batch,
                            cfg.num_experts_per_tok, decode_touched=True),
    }


# -- rotary -------------------------------------------------------------------


def rope_tables(cfg: LagunaConfig, positions: jax.Array) -> tuple:
    """positions [B, S] -> ((cos, sin) of the full layers, (cos, sin) of
    the sliding ones), float32 ``[B, S, turned dims / 2]``."""
    pos = positions[..., None].astype(jnp.float32)
    full = pos * yarn_inv_freq(
        cfg.rotary_dims, cfg.rope_theta, cfg.rope_factor,
        cfg.rope_original_max_len, cfg.rope_beta_fast, cfg.rope_beta_slow)
    half = cfg.head_dim // 2
    local = pos / (cfg.rope_local_theta ** (
        jnp.arange(0, half, dtype=jnp.float32) / half))
    m = cfg.rope_attention_factor
    return ((jnp.cos(full) * m, jnp.sin(full) * m),
            (jnp.cos(local), jnp.sin(local)))


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, S, H, hd]: the leading ``2 * cos.shape[-1]`` dims of each head
    turn (rotate-half pairing inside them), the rest pass."""
    half = cos.shape[-1]
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:2 * half].astype(jnp.float32)
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    # one concatenate in float32 and one cast, as llama's ``_apply_rope``
    parts = [a * c - b * s, b * c + a * s]
    if 2 * half < x.shape[-1]:
        parts.append(x[..., 2 * half:].astype(jnp.float32))
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


# -- routing ------------------------------------------------------------------


def route(logits: jax.Array, top_k: int, scaling: float):
    """logits [T, E] float32 -> (expert ids [T, k] int32, weights [T, k]):
    softmax over ALL experts, its ``top_k`` largest, renormalised to sum to
    one (``norm_topk_prob``), times ``scaling``."""
    picked, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * scaling
    return ids.astype(jnp.int32), weights


# -- the blocks and forward ---------------------------------------------------


def _attend(x, lp, layer_idx, sliding: bool, ropes, mask, cache, write_index,
            cfg: LagunaConfig, stacked_attention_fn):
    """x + the gated attention of one layer of the kind ``sliding`` says
    (static: the kinds differ in shape). The ``jax.named_scope`` names are
    metadata a device trace is read by (README "Device time by layer")."""
    B, S, _ = x.shape
    aq = cfg.w8a8_prefill and S > 1
    with jax.named_scope("qkv"):
        h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q = _proj("bsd,dhk->bshk", h, lp["wq"], aq)
        k = _proj("bsd,dhk->bshk", h, lp["wk"], aq)
        v = _proj("bsd,dhk->bshk", h, lp["wv"], aq)
        cos, sin = ropes[1 if sliding else 0]
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    cache = _write_kv(cache, k, v, layer_idx, write_index)
    if stacked_attention_fn is None and sliding:
        # the dense path's mask; the kernels take the window as a scalar
        mask = mask & _in_window(write_index, S, mask.shape[-1],
                                 cfg.sliding_window)
    attn = _cache_attention(q, cache, layer_idx, mask,
                            cfg.heads(sliding) // cfg.n_kv_heads, None,
                            stacked_attention_fn)
    with jax.named_scope("attn_gate"):
        gate = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", h, lp["attn_gate"],
            preferred_element_type=jnp.float32))
        # in the heads' own type: a float32 copy of a chunk's heads is 1.7 GB
        attn = attn * gate.astype(attn.dtype)[..., None]
    with jax.named_scope("attn_out"):
        return x + _proj("bshk,hkd->bsd", attn, lp["wo"], aq), cache


def _swiglu(h, lp, names, aq: bool, cfg: LagunaConfig):
    gate = _proj("bsd,di->bsi", h, lp[names[0]], aq)
    up = _proj("bsd,di->bsi", h, lp[names[1]], aq)
    return _proj("bsi,id->bsd", _mlp_act(gate, cfg.act) * up, lp[names[2]], aq)


def _dense_ffn(x, lp, cfg: LagunaConfig):
    aq = cfg.w8a8_prefill and x.shape[1] > 1
    h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)

    def mlp(h):
        with jax.named_scope("mlp"):
            return _swiglu(h, lp, ("w_gate", "w_up", "w_down"), aq, cfg)

    # a few rows at a time: 12,288 wide over a chunk of 24 rows is gigabytes
    return x + by_rows(mlp, h, _EXPERT_PIECE_TOKENS)


def _sparse_ffn(x, lp, experts, slot, valid, cache, cfg: LagunaConfig,
                experts_fn):
    """x + the routed experts (``models/experts.py``, under this family's
    routing rule) + the shared expert, and the counters."""
    B, S, D = x.shape
    aq = cfg.w8a8_prefill and S > 1
    h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    flat = h.reshape(B * S, D)

    def picks():
        return route(
            jnp.einsum("td,de->te", flat.astype(jnp.float32),
                       lp["router"].astype(jnp.float32)),
            cfg.num_experts_per_tok, cfg.routed_scaling_factor)

    routed, cache = expert_layer(flat, picks, valid, experts, slot, cache,
                                 cfg, experts_fn, rows=B)
    with jax.named_scope("shared_experts"):
        shared = _swiglu(h, lp, ("ws_gate", "ws_up", "ws_down"), aq, cfg)
    return x + routed.reshape(B, S, D).astype(x.dtype) + shared, cache


def forward(params: dict, cfg: LagunaConfig, tokens, positions, cache,
            write_index, mask, *, last_only: bool = False,
            stacked_attention_fn=None, experts_fn=None):
    """Run the decoder over ``tokens`` [B, S] written at cache slots
    ``write_index ..``; returns (logits [B, S, vocab] float32, cache).

    ``stacked_attention_fn(q, cache, layer_idx)`` is the phase's kernel over
    the stacked cache (llama's, with this family's per-layer window; it
    reads the group off ``q``); None is the dense XLA attention under
    ``mask`` [B, S, C]. ``experts_fn(x, local, weights, experts, slot)`` is
    the routed experts' product (``grouped_experts``); None is
    ``dense_experts``."""
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    with jax.named_scope("qkv"):  # the two rope tables the layers read
        ropes = rope_tables(cfg, positions)
    # a token under a row's left pad attends nothing: it is routed nowhere
    # and counted nowhere
    valid = jnp.any(mask, axis=-1)
    Ld = cfg.n_dense_layers
    # the experts stay out of the scan's slices: the grouped product reads
    # the stack in place, by the sparse layer's index
    experts = {n: params["layers"][n] for n in EXPERT_LEAVES}
    ffn = {n: w for n, w in params["layers"].items() if n not in EXPERT_LEAVES}

    def attend(x, lp, li, sliding, cache):
        return _attend(x, lp, li, sliding, ropes, mask, cache, write_index,
                       cfg, stacked_attention_fn)

    def sparse_layer(x, cache, slot, sliding, attn_lp, ffn_lp):
        x, cache = attend(x, attn_lp, Ld + slot, sliding, cache)
        return _sparse_ffn(x, ffn_lp, experts, slot, valid, cache, cfg,
                           experts_fn)

    def dense_step(carry, xs):
        lp, li = xs
        x, cache = attend(carry[0], lp, li, False, carry[1])
        return (_dense_ffn(x, lp, cfg), cache), None

    carry = (x, cache)
    if Ld:
        carry, _ = jax.lax.scan(dense_step, carry,
                                (params["dense"], jnp.arange(Ld)))

    period = cfg.period
    n_periods, rest = cfg.periods
    per_kind = {1: sum(period), 0: len(period) - sum(period)}

    def take(tree, start, n, per=None):
        """Layers [start, start + n) of a stacked group; with ``per`` as
        [n / per, per, ...] for a scan over periods."""
        cut = jax.tree.map(lambda a: a[start:start + n], tree)
        if per is None:
            return cut
        return jax.tree.map(
            lambda a: a.reshape((n // per, per) + a.shape[1:]), cut)

    def kinds_in_order(kinds, x, cache, first_slot, groups, ffn_lp):
        """The layers of ``kinds`` in order, layer j at sparse slot
        ``first_slot + j``; ``groups[kind]`` and ``ffn_lp`` are stacked on a
        leading dim over them (each kind's own count, all of them)."""
        seen = {0: 0, 1: 0}
        at = lambda t, i: jax.tree.map(lambda a: a[i], t)  # noqa: E731
        for j, kind in enumerate(kinds):
            x, cache = sparse_layer(
                x, cache, first_slot + j, bool(kind),
                at(groups[kind], seen[kind]), at(ffn_lp, j))
            seen[kind] += 1
        return x, cache

    def period_step(carry, xs):
        groups, ffn_lp, p = xs
        return kinds_in_order(period, *carry, p * len(period), groups,
                              ffn_lp), None

    if n_periods:
        n = n_periods * len(period)
        carry, _ = jax.lax.scan(period_step, carry, (
            {kind: take(params["sliding" if kind else "full"], 0,
                        n_periods * per_kind[kind], per_kind[kind])
             for kind in per_kind if per_kind[kind]},
            take(ffn, 0, n, len(period)), jnp.arange(n_periods)))
    if rest:
        # what is left of a period after the last whole one, unrolled
        n = n_periods * len(period)
        groups = {
            kind: take(params["sliding" if kind else "full"],
                       n_periods * per_kind[kind], rest.count(kind))
            for kind in set(rest)}
        carry = kinds_in_order(rest, *carry, n, groups,
                               take(ffn, n, len(rest)))
    x, cache = carry
    with jax.named_scope("lm_head"):
        if last_only:
            x = x[:, -1:, :]
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head_logits(x, params, cfg)
    return logits, cache


def forward_dense(params: dict, cfg: LagunaConfig, tokens) -> jax.Array:
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


def layer_windows(cfg: LagunaConfig) -> tuple:
    """Each layer's window in cache slots, 0 where it attends globally."""
    return tuple(cfg.sliding_window if s else 0 for s in cfg.sliding_layout)


def layer_groups(cfg: LagunaConfig) -> tuple:
    """Each layer's query heads a KV head."""
    return tuple(h // cfg.n_kv_heads for h in cfg.heads_per_layer)


def _forward_kwargs(cfg: LagunaConfig, kernels: bool, interpret: bool):
    if not kernels:
        return {}   # flash=False: dense attention and dense_experts
    return {"experts_fn": functools.partial(
        grouped_experts, cfg=cfg, interpret=interpret)}


def _family():
    from .family import Family

    carries_counters = (
        "its programs carry a KV cache alone; this family's state holds the "
        "expert counters and picks beside the keys and values")
    one_group = (
        "it hands its kernel one query-head count for every layer; this "
        "family's differs by layer kind")
    return Family(
        name="laguna", forward=forward, init_cache=init_cache,
        init_params=init_params, kernels_supported=_kernels_supported,
        attention_supported=_attention_supported,
        prefill_attention=_prefill_attention,
        decode_attention=_decode_attention, counts_prefill_blocks=True,
        layer_windows=layer_windows, layer_groups=layer_groups,
        forward_kwargs=_forward_kwargs, counters=counters,
        row_record=last_picks,
        missing={
            "slot loop": (
                "the slot programs (backend/inflight.py, engine._make_slot_*"
                ", _make_adopt_fn) scatter every leaf of a joined batch's "
                "cache on its second axis, as keys and values, and return "
                "no counters: " + carries_counters + "; " + one_group),
            "prefix cache": (
                "the resume program (cache/store.py gather, engine."
                "_prepare_resume) seeds a KV cache alone and returns the "
                "final cache in the counters' place: " + carries_counters),
            "mesh": (
                "parallel/sharding.py has no specs for parameters grouped "
                "by layer kind, the head gate, the router and the stacked "
                "experts, no expert axis and no exchange of the experts' "
                "partial sums"),
            "speculative decoding": (
                "the verify step writes the cache at per-row slots and its "
                "host loop hands a KV cache from step to step: "
                + carries_counters + "; " + one_group),
            "long-context backend": (
                "the ring prefill runs models.llama.cache_free_block, "
                "which has no window, no head gate, one rotary scheme and "
                "no expert layer"),
        },
    )


FAMILY = _family()
