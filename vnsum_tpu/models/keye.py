"""Keye-VL-2.0's language model in functional JAX: the Qwen3-MoE skeleton
(QK-normed rotary GQA, 128 SwiGLU experts top-8, no shared expert) whose
every layer attends, by a learned indexer, the ``index_topk`` keys a query
scores highest — DeepSeek Sparse Attention — for the one-shot program.

A tenth family behind ``models/family.py``. Its keys-and-values cache,
``_write_kv`` and the W8A8 products are ``models/llama.py``'s, its expert
layer ``models/experts.py``'s, the selection and the attention over it
``ops/sparse_attention.py``'s. What it owns is the config, the parameters,
the indexer, its routing rule, rotary by three position components and
``forward``. ``FAMILY`` at the end is what the engine's seam picks up for a
``KeyeConfig``.

The layer (``benchmarks/reference_keye.py`` is the same equations in plain
float32; all layers alike), ``h = RMSNorm(x)``:

- **Attention.** ``q = h W_q`` [H, hd], ``k, v`` [KV, hd], no bias; RMSNorm
  over each head of q and k; rotary, rotate-half, ``hd / 2`` frequencies at
  ``rope_theta`` where frequency i takes its angle from position component
  c(i) (``mrope_section``: the first 16 from component 0, the next 24 from
  1, the last 24 from 2). A text token at position t has components (t, t,
  t), which is plain rotary; ``positions`` may be [B, S] (text) or [B, S, 3].
- **The indexer**, its own weights a layer: ``qI = h W_qI`` [Hi, di], ``kI =
  LayerNorm(h W_kI)`` [di], both rotated over their di dims by the FIRST
  position component; ``w = (h W_wI) Hi^-1/2 di^-1/2`` [Hi] in float32;
  ``I[t, s] = sum_j w_t[j] relu(qI_t[j] . kI_s)`` over the visible s; the
  ``index_topk`` largest (all where fewer are visible, ties to the lower
  s) are ``T_t``: ONE set a token and layer for all heads. ``kI`` is kept
  in the program's carry as ``cache["ki"] [L, B, di, C]`` (the slots on
  the lanes: ``ops/sparse_attention.py`` says why) beside keys and values, written by every prefill chunk and decode step; a slot under a
  row's left pad is never selectable.
- ``o = softmax over T_t alone``; ``x' = x + concat(o) W_o``.
- **Experts.** ``z = RMSNorm(x')``, ``p = softmax(z W_r)`` float32 over all
  experts, the top-k of p renormalised to sum to one; ``out = x' + sum_e
  p_e SwiGLU_e(z)``. No shared expert, no dense layer.

The layers run as ONE ``lax.scan``; the experts stay out of the scan's
slices (the grouped product reads the stack in place).

State a program carries (``init_cache``): llama's KV cache, ``ki``, the
expert counters of ``models/experts.py`` with ``decode_touched``, and, for
a parity check's eyes alone, the latest forward's last position's
selection of every layer: ``sel`` [L, B, C] int8, ``sel_scores`` [L, B, C]
float32 (-inf where the position sees nothing) and the operands they were
made from, ``sel_q`` [L, B, Hi, di] and ``sel_w`` [L, B, Hi]. Only
``TpuBackend.prefill_then_decode_logits`` returns them (``row_record``); in
a ``generate`` program nothing reads them and the compiler carries none of
the four: the (8, 16384, 256) program compiled for a v5e holds no buffer of
their shapes (``tests/test_ops_compile_tpu.py`` pins it).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Any

import jax
import jax.numpy as jnp

from .experts import (
    EXPERT_LEAVES,
    counters,
    expert_layer,
    grouped_experts,
    init_expert_state,
)
from .llama import (
    _apply_rope,
    _cache_attention,
    _embed_lookup,
    _lm_head_logits,
    _proj,
    _rmsnorm,
    _write_kv,
    init_kv_cache,
)


@dataclass(frozen=True)
class KeyeConfig:
    vocab_size: int = 151_936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    # published and UNUSED: decoder_sparse_step 1 and mlp_only_layers []
    # make every layer's feed-forward its experts
    intermediate: int = 6144
    moe_intermediate: int = 768
    n_routed_experts: int = 128
    num_experts_per_tok: int = 8
    rope_theta: float = 10_000_000.0
    # rotary frequencies a position component: temporal, height, width
    mrope_section: tuple = (16, 24, 24)
    # sa_config: the indexer and the selection
    index_n_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    norm_eps: float = 1e-6
    max_seq_len: int = 262_144
    tie_embeddings: bool = False
    act: str = "silu"
    # W8A8 on multi-token forwards, as LlamaConfig's; the engine sets it
    w8a8_prefill: bool = False
    dtype: Any = field(default=jnp.bfloat16)
    # what the heads' index scores are summed in (float32; a parity check
    # shows bfloat16 failing)
    index_sum_dtype: Any = field(default=jnp.float32)

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        if sum(self.mrope_section) * 2 != self.head_dim:
            raise ValueError(
                f"mrope_section {self.mrope_section} is not the "
                f"{self.head_dim // 2} frequencies of a head")
        if self.index_head_dim % 2:
            raise ValueError("index_head_dim must be even (rotary pairs)")

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    # what ``models/experts.py`` asks of a config: every expert is held
    expert_offset = 0

    @property
    def n_held(self) -> int:
        return self.n_routed_experts


def keye_vl_2_0_30b_a3b(**kw) -> KeyeConfig:
    """Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json`` (the language model),
    uncut."""
    return KeyeConfig(**kw)


def tiny_keye(**kw) -> KeyeConfig:
    """Small config for hermetic CPU tests: three layers, 2 query heads a KV
    head, 8 experts top-2, 4 indexer heads of 8, and a ``index_topk`` of 12
    — shorter than the prompts, so that selection really drops keys."""
    base = dict(
        vocab_size=384, dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=16, intermediate=96, moe_intermediate=32,
        n_routed_experts=8, num_experts_per_tok=2, mrope_section=(2, 3, 3),
        index_n_heads=4,
        index_head_dim=8, index_topk=12, max_seq_len=256, dtype=jnp.float32,
    )
    base.update(kw)
    return KeyeConfig(**base)


# -- parameters and state -----------------------------------------------------


def float_leaves(key: jax.Array, cfg: KeyeConfig) -> dict:
    """The full-precision leaves this family draws its own way, for both
    inits: QK-norm weights and the indexer's LayerNorm gain U[0.5, 1.5], its
    bias N(0, 0.1), and the heads' weights ``w_idx`` float32 N(0, 1 / sqrt
    D) — mixed signs, so that relu and the weights both matter."""
    L, D = cfg.n_layers, cfg.dim
    ks = jax.random.split(key, 5)

    def uniform(k, shape, dtype):
        return jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5).astype(dtype)

    return {"layers": {
        "q_norm": uniform(ks[0], (L, cfg.head_dim), cfg.dtype),
        "k_norm": uniform(ks[1], (L, cfg.head_dim), cfg.dtype),
        "idx_norm_g": uniform(ks[2], (L, cfg.index_head_dim), jnp.float32),
        "idx_norm_b": jax.random.normal(
            ks[3], (L, cfg.index_head_dim), jnp.float32) * 0.1,
        "w_idx": jax.random.normal(
            ks[4], (L, D, cfg.index_n_heads), jnp.float32) * D ** -0.5,
    }}


def init_params(key: jax.Array, cfg: KeyeConfig) -> dict:
    """Random init, every layer stacked on a leading dim."""
    L, D, H, KV, hd = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim)
    E, F = cfg.n_held, cfg.moe_intermediate
    Hi, di = cfg.index_n_heads, cfg.index_head_dim
    keys = iter(jax.random.split(key, 16))

    def norm(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale
                ).astype(cfg.dtype)

    layers = {
        "attn_norm": jnp.ones((L, D), cfg.dtype),
        "wq": norm((L, D, H, hd)), "wk": norm((L, D, KV, hd)),
        "wv": norm((L, D, KV, hd)), "wo": norm((L, H, hd, D)),
        "wq_idx": norm((L, D, Hi, di)), "wk_idx": norm((L, D, di)),
        "mlp_norm": jnp.ones((L, D), cfg.dtype),
        "router": norm((L, D, cfg.n_routed_experts)),
        "we_gate": norm((L, E, D, F)), "we_up": norm((L, E, D, F)),
        "we_down": norm((L, E, F, D)),
    }
    layers.update(float_leaves(next(keys), cfg)["layers"])
    return {"embed": norm((cfg.vocab_size, D)), "layers": layers,
            "final_norm": jnp.ones((D,), cfg.dtype),
            "lm_head": norm((D, cfg.vocab_size))}


def init_cache(cfg: KeyeConfig, batch: int, cache_len: int, *,
               quantized: bool = False) -> dict:
    """What a program carries: llama's KV cache, the indexer keys, the
    expert counters, and the last position's selection a layer."""
    L = cfg.n_layers
    return {
        **init_kv_cache(cfg, batch, cache_len, quantized=quantized),
        "ki": jnp.zeros((L, batch, cfg.index_head_dim, cache_len), cfg.dtype),
        "sel": jnp.zeros((L, batch, cache_len), jnp.int8),
        "sel_scores": jnp.full((L, batch, cache_len), -jnp.inf, jnp.float32),
        "sel_q": jnp.zeros((L, batch, cfg.index_n_heads, cfg.index_head_dim),
                           cfg.dtype),
        "sel_w": jnp.zeros((L, batch, cfg.index_n_heads), jnp.float32),
        **init_expert_state(L, cfg.n_held, batch, cfg.num_experts_per_tok,
                            decode_touched=True),
    }


# -- rotary, routing, the indexer ---------------------------------------------


def position_components(positions: jax.Array) -> jax.Array:
    """positions [B, S] (text: one number a token) or [B, S, 3] -> [B, S,
    3]."""
    if positions.ndim == 2:
        return jnp.broadcast_to(positions[..., None], positions.shape + (3,))
    return positions


def rope_tables(cfg: KeyeConfig, positions: jax.Array) -> tuple:
    """-> ((cos, sin) of the heads [B, S, hd / 2]: frequency i by its
    ``mrope_section`` component; (cos, sin) of the indexer [B, S, di / 2]:
    every frequency by the first component), float32."""
    pos = position_components(positions).astype(jnp.float32)
    half = cfg.head_dim // 2
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    component = jnp.repeat(jnp.arange(3), jnp.asarray(cfg.mrope_section),
                           total_repeat_length=half)
    ang = jnp.take(pos, component, axis=-1) * inv
    ihalf = cfg.index_head_dim // 2
    iinv = 1.0 / cfg.rope_theta ** (
        jnp.arange(ihalf, dtype=jnp.float32) / ihalf)
    iang = pos[..., :1] * iinv
    return (jnp.cos(ang), jnp.sin(ang)), (jnp.cos(iang), jnp.sin(iang))


def route(logits: jax.Array, top_k: int):
    """Qwen3-MoE's rule: logits [T, E] float32 -> (expert ids [T, k] int32,
    weights [T, k]): softmax over ALL experts, its ``top_k`` largest,
    renormalised to sum to one (``norm_topk_prob``)."""
    picked, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return ids.astype(jnp.int32), picked / jnp.sum(picked, -1, keepdims=True)


def _layernorm(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def _indexer_inputs(h, lp, irope, aq: bool, cfg: KeyeConfig):
    """h [B, S, D] -> (qI [B, S, Hi, di], kI [B, S, di], w [B, S, Hi]
    float32 scaled)."""
    q = _proj("bsd,dhk->bshk", h, lp["wq_idx"], aq)
    k = _layernorm(_proj("bsd,dk->bsk", h, lp["wk_idx"], aq),
                   lp["idx_norm_g"], lp["idx_norm_b"], cfg.norm_eps)
    q = _apply_rope(q, *irope)
    k = _apply_rope(k[:, :, None, :], *irope)[:, :, 0]
    w = jnp.einsum("bsd,dh->bsh", h.astype(jnp.float32), lp["w_idx"],
                   preferred_element_type=jnp.float32)
    return q, k, w * (cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5)


def _write_rows(buf, val, layer_idx, write_index, rows):
    """val [B, ..., S] into buf [L, Bc, ..., C] at (layer, row, ..., slot
    ``write_index`` ..): every row at once, or each at its batch row
    ``rows[b]``, in place."""
    mid = (0,) * (buf.ndim - 3)
    if rows is None:
        return jax.lax.dynamic_update_slice(
            buf, val[None], (layer_idx, 0) + mid + (write_index,))
    for b in range(val.shape[0]):
        buf = jax.lax.dynamic_update_slice(
            buf, val[None, b:b + 1],
            (layer_idx, rows[b]) + mid + (write_index,))
    return buf


# -- forward ------------------------------------------------------------------


def forward(params: dict, cfg: KeyeConfig, tokens, positions, cache,
            write_index, mask, *, last_only: bool = False,
            stacked_attention_fn=None, experts_fn=None, cache_rows=None):
    """Run the decoder over ``tokens`` [B, S] written at cache slots
    ``write_index ..``; returns (logits [B, S, vocab] float32, cache).

    ``stacked_attention_fn`` is the phase's pair of kernels over the stacked
    caches (``FAMILY.prefill_attention`` / ``decode_attention``):
    ``.select(qI, w, cache, layer) -> (mask, the last query's scores)`` and
    ``.attend(q, cache, layer, mask) -> [B, S, H, hd]``; None is the XLA
    form under ``mask`` [B, S, C] (scores, ``lax.top_k``, dense attention
    over the selected). ``experts_fn`` is the routed experts' product
    (``grouped_experts``); None is ``dense_experts``. ``cache_rows`` [B]:
    the tokens are a row piece of a batch whose state ``cache`` is."""
    from ..ops.sparse_attention import index_scores_xla, select_xla

    B, S = tokens.shape
    C = cache["ki"].shape[3]
    aq = cfg.w8a8_prefill and S > 1
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    with jax.named_scope("qkv"):   # the rope tables every layer reads
        rope, irope = rope_tables(cfg, positions)
    # a token under a row's left pad attends nothing: it is routed nowhere
    valid = jnp.any(mask, axis=-1)
    experts = {n: params["layers"][n] for n in EXPERT_LEAVES}
    rest = {n: w for n, w in params["layers"].items() if n not in EXPERT_LEAVES}

    def layer(carry, xs):
        x, cache = carry
        lp, l = xs
        with jax.named_scope("qkv"):
            h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
            q = _proj("bsd,dhk->bshk", h, lp["wq"], aq)
            k = _proj("bsd,dhk->bshk", h, lp["wk"], aq)
            v = _proj("bsd,dhk->bshk", h, lp["wv"], aq)
            q = _apply_rope(_rmsnorm(q, lp["q_norm"], cfg.norm_eps), *rope)
            k = _apply_rope(_rmsnorm(k, lp["k_norm"], cfg.norm_eps), *rope)
        cache = _write_kv(cache, k, v, l, write_index, cache_rows)
        with jax.named_scope("dsa_in"):
            q_idx, k_idx, w_idx = _indexer_inputs(h, lp, irope, aq, cfg)
            cache = dict(cache, ki=_write_rows(
                cache["ki"], k_idx.swapaxes(1, 2), l, write_index, cache_rows))
        with jax.named_scope("dsa_select"):
            if stacked_attention_fn is not None:
                sel, last_scores = stacked_attention_fn.select(
                    q_idx, w_idx, cache, l)
                last_sel = (sel[:, -1] if sel.ndim == 3 else sel)[:, :C]
                last_scores = last_scores[:, :C]
            else:
                keys = jax.lax.dynamic_index_in_dim(cache["ki"], l, 0, False)
                if cache_rows is not None:
                    keys = keys[cache_rows]
                scores = index_scores_xla(q_idx, w_idx, keys,
                                          cfg.index_sum_dtype)
                sel = select_xla(scores, mask, cfg.index_topk)
                last_sel = sel[:, -1]
                last_scores = jnp.where(mask[:, -1], scores[:, -1], -jnp.inf)
            cache = dict(
                cache,
                sel=_write_rows(cache["sel"], last_sel.astype(jnp.int8), l, 0,
                                cache_rows),
                sel_scores=_write_rows(cache["sel_scores"], last_scores, l,
                                       0, cache_rows),
                sel_q=_write_rows(cache["sel_q"], q_idx[:, -1], l, 0,
                                  cache_rows),
                sel_w=_write_rows(cache["sel_w"], w_idx[:, -1], l, 0,
                                  cache_rows))
        if stacked_attention_fn is not None:
            with jax.named_scope("attn"):
                attn = stacked_attention_fn.attend(q, cache, l, sel)
        else:
            attn = _cache_attention(q, cache, l, mask & sel, cfg.q_per_kv,
                                    rows=cache_rows)
        with jax.named_scope("attn_out"):
            x = x + _proj("bshk,hkd->bsd", attn, lp["wo"], aq)
        z = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        flat = z.reshape(B * S, cfg.dim)

        def picks():
            return route(
                jnp.einsum("td,de->te", flat.astype(jnp.float32),
                           lp["router"].astype(jnp.float32)),
                cfg.num_experts_per_tok)

        routed, cache = expert_layer(flat, picks, valid, experts, l, cache,
                                     cfg, experts_fn, rows=B,
                                     cache_rows=cache_rows)
        return (x + routed.reshape(B, S, cfg.dim).astype(x.dtype), cache), None

    (x, cache), _ = jax.lax.scan(layer, (x, cache),
                                 (rest, jnp.arange(cfg.n_layers)))
    with jax.named_scope("lm_head"):
        if last_only:
            x = x[:, -1:, :]
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head_logits(x, params, cfg)
    return logits, cache


def forward_dense(params: dict, cfg: KeyeConfig, tokens,
                  positions=None) -> jax.Array:
    """Cache-free causal forward of whole sequences [B, S] with no kernel:
    logits [B, S, vocab] float32. (Keys pass through caches of exactly S
    slots.) ``positions`` [B, S, 3] where the components differ."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    logits, _ = forward(params, cfg, tokens, positions,
                        init_cache(cfg, B, S), 0, mask)
    return logits


# -- the engine's seam (models/family.py) -------------------------------------


def _kernels_supported(cfg: KeyeConfig, interpret: bool) -> bool:
    # a head is one lane tile of the cache, an indexer head half of one
    return interpret or (cfg.head_dim == 128 and cfg.index_head_dim % 64 == 0)


def _attention_supported(cfg: KeyeConfig, S: int, C: int):
    from ..ops.sparse_attention import _QUERY_BLOCK, _SELECT_TILE

    # whole query tiles; the key blocks may end past the cache
    whole = all(S % min(S, t) == 0 for t in (_SELECT_TILE, _QUERY_BLOCK))
    return whole, True


def _prefill_attention(cfg: KeyeConfig, mesh, interpret: bool, pad_lens,
                       layer_window, q_offset: int = 0, cache_rows=None):
    """The prefill's pair of kernels for queries at cache slots
    ``q_offset ..`` (``forward``'s ``stacked_attention_fn``)."""
    from ..ops.sparse_attention import dsa_index_select, dsa_prefill_attention

    def select(q_idx, w_idx, cache, layer_idx):
        return dsa_index_select(
            q_idx, w_idx, cache, layer_idx, pad_lens, q_offset, cache_rows,
            topk=cfg.index_topk, sum_dtype=cfg.index_sum_dtype,
            interpret=interpret)

    def attend(q, cache, layer_idx, sel):
        return dsa_prefill_attention(
            q, cache, layer_idx, sel, pad_lens, q_offset, cache_rows,
            interpret=interpret)

    return SimpleNamespace(select=select, attend=attend)


def _decode_attention(cfg: KeyeConfig, mesh, interpret: bool, pad_lens,
                      S: int, t, layer_window):
    """The pair of kernels of decode step ``t`` after a prompt bucket of
    ``S``: its token sits at cache slot ``S + t``."""
    from ..ops.sparse_attention import (
        dsa_decode_attention,
        dsa_index_select_decode,
    )

    def select(q_idx, w_idx, cache, layer_idx):
        return dsa_index_select_decode(
            q_idx[:, 0], w_idx[:, 0], cache, layer_idx, pad_lens, S + t,
            topk=cfg.index_topk, sum_dtype=cfg.index_sum_dtype,
            interpret=interpret)

    def attend(q, cache, layer_idx, sel):
        return dsa_decode_attention(
            q[:, 0], cache, layer_idx, sel, pad_lens, S + t,
            interpret=interpret)[:, None]

    return SimpleNamespace(select=select, attend=attend)


def prefill_counts(cfg: KeyeConfig, pad_lens, spans, cache_len) -> dict:
    """What one dispatch's prefill scored, from the pads it was packed
    with, x layers: ``dsa_keys_visible`` (a real query's visible keys,
    summed), ``dsa_index_scores_needed`` (those x indexer heads) against
    ``dsa_index_scores_computed`` (the blocks the selection kernel scored),
    ``dsa_attention_scores_selected`` (min(visible, topk) x query heads)
    against ``dsa_attention_scores_computed`` (the blocks the MASKED
    attention scored)."""
    from ..ops.sparse_attention import (
        _KEY_BLOCK,
        _QUERY_BLOCK,
        _SELECT_TILE,
        prefill_score_counts,
    )

    n = prefill_score_counts(pad_lens, spans, cfg.index_topk, _QUERY_BLOCK,
                             _SELECT_TILE, min(_KEY_BLOCK, cache_len))
    L, Hi, H = cfg.n_layers, cfg.index_n_heads, cfg.n_heads
    return {"dsa_keys_visible": n["visible"] * L,
            "dsa_index_scores_needed": n["visible"] * Hi * L,
            "dsa_index_scores_computed": n["index_computed"] * Hi * L,
            "dsa_attention_scores_selected": n["selected"] * H * L,
            "dsa_attention_scores_computed": n["attention_computed"] * H * L}


def row_record(cache: dict) -> dict:
    """What a parity check may see of the position just scored: every
    layer's expert picks [L, B, k] and every layer's selection — ``sel``
    [L, B, C] (1 where the position keeps the slot), the index scores it
    was made from, ``sel_scores`` [L, B, C], and THEIR operands, the
    position's indexer queries ``sel_q`` [L, B, Hi, di] and head weights
    ``sel_w`` [L, B, Hi] (with the cached indexer keys a check recomputes
    the scores exactly: what is left is the kernel's own sums). The first
    layer's carries one product's rounding, the last everything before it;
    a reference needs them all to take the program's sets where a tie was
    broken its way."""
    return {name: cache[name] for name in (
        "picks", "sel", "sel_scores", "sel_q", "sel_w")}


def _forward_kwargs(cfg: KeyeConfig, kernels: bool, interpret: bool):
    if not kernels:
        return {}   # flash=False: the XLA selection and dense_experts
    return {"experts_fn": functools.partial(
        grouped_experts, cfg=cfg, interpret=interpret)}


def _family():
    from .family import Family

    carries = (
        "this family's state holds every layer's indexer keys [L, B, 64, C] "
        "beside the keys and values, the expert counters and picks, and the "
        "last position's selection")
    return Family(
        name="keye", forward=forward, init_cache=init_cache,
        init_params=init_params, kernels_supported=_kernels_supported,
        attention_supported=_attention_supported,
        prefill_attention=_prefill_attention,
        decode_attention=_decode_attention,
        prefill_counts=prefill_counts,
        # one row of a 2,048-token chunk a piece, as the dense skeleton's
        # (models/llama.py): the selection's mask is 36 MB a row and chunk,
        # and a piece wholly under its rows' pads is not run
        prefill_piece_tokens=2048,
        forward_kwargs=_forward_kwargs, counters=counters,
        row_record=row_record,
        missing={
            "slot loop": (
                "the slot programs (backend/inflight.py, engine._make_slot_*"
                ", _make_adopt_fn) scatter every leaf of a joined batch's "
                "cache on its second axis as [L, B, KV, C, hd] keys and "
                "values and return no counters; an indexer-key cache [L, B, "
                "64, C] would have to be adopted, evicted and filled row by "
                "row beside them, and the segment step would have to select "
                "per row at ragged fills: " + carries),
            "prefix cache": (
                "cache/radix.py and cache/store.py keep [N, L, KV, BLK, hd] "
                "keys and values by block and the resume program (engine."
                "_prepare_resume) seeds a KV cache alone; a resumed prefix "
                "needs its indexer keys too — a third leaf [N, L, 64, BLK] "
                "in every block, gathered and inserted with it — or every "
                "later query would select among zeros: " + carries),
            "mesh": (
                "parallel/sharding.py has no specs for the indexer's "
                "projections, its LayerNorm and head weights, the router "
                "and the stacked experts, no expert axis, and shards a "
                "cache's KV heads where the selection is ONE set a token "
                "for all heads (every shard would need the whole indexer "
                "cache or the mask exchanged)"),
            "speculative decoding": (
                "the verify step (backend/engine.py _make_spec_fn) writes "
                "the cache at per-row slots and scores several draft "
                "positions a row through ops/decode_attention.py's verify "
                "kernel; each draft position would need its own selection "
                "at its own fill, and a rejected draft's indexer keys "
                "rolled back: " + carries),
            "long-context backend": (
                "the ring prefill (backend/long_context.py) runs "
                "models.llama.cache_free_block and passes keys and values "
                "between shards; a query's top-k is over ALL shards' keys, "
                "so the index scores of every shard would have to be "
                "gathered (or a threshold agreed on) before any shard "
                "attends, and there is no expert layer there"),
            "image inputs": (
                "no vision tower is built (models/, serve/): the decoder "
                "takes three-component positions, and nothing produces "
                "patch embeddings or their (t, h, w) positions"),
        },
    )


FAMILY = _family()
