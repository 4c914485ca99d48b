"""DeepSeek-V2 family in functional JAX: latent attention (MLA) and sparse
experts with shared experts, for the one-shot generation program.

A second model family beside ``models/llama.py``. It reuses that module's
primitives (``_proj``, ``_rmsnorm``, ``_embed_lookup``, ``_lm_head_logits``,
``_apply_rope``, ``_mlp_act``), the ``{"q", "s"}`` int8 leaves of
``models/quant.py`` and the expert layer of ``models/experts.py`` (picks ->
experts held here -> counters -> grouped product; ``models/smallthinker.py``
runs the same); what it owns is the config, the parameters, the cache, its
routing rule, the shared experts, the block and ``forward``. ``FAMILY`` at the end is what the engine's seam
(``backend/family.py``) picks up for a ``DeepseekV2Config``.

The layer (``benchmarks/reference_deepseek_v2.py`` is the same equations in
plain float32):

- **MLA.** ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> heads of
  ``[q_nope | q_rope]``; ``[c_kv | k_rope] = x W_kva``, ``c_kv =
  RMSNorm(c_kv)``; RoPE (YaRN) on ``q_rope`` and on the one ``k_rope`` all
  heads share; per head ``k_nope = c_kv W_kb``, ``v = c_kv W_vb``; ``score =
  (q_nope k_nope + q_rope k_rope) scale``. The cache holds ``(c_kv,
  k_rope)`` only: ``[L, B, C, kv_lora_rank + qk_rope_head_dim]``. Prefill
  hands the kernel the latent rows and ``W_kb`` / ``W_vb``, a few batch
  rows at a time, and the kernel expands a key block's keys and values in
  VMEM: none is written to HBM (``prefill_attention``; the dense XLA path
  of small sizes expands them by einsum, ``_expanded_attention``); decode
  is ABSORBED (``decode_attention``):
  ``q_lat = q_nope W_kb^T``, attention of all heads over the latent rows,
  ``out = o_lat W_vb`` — keys and values are never expanded in a step.
- **FFN.** The first ``first_k_dense_replace`` layers are a dense SwiGLU.
  The others route: ``s = softmax(x W_r)`` over all ``n_routed_experts``,
  group-limited greedy (best ``topk_group`` of ``n_group`` groups by each
  group's largest score, then the best ``num_experts_per_tok`` inside
  them), weights ``s * routed_scaling_factor`` not renormalised; plus the
  shared experts, one SwiGLU every token passes.
- **Expert parallelism.** A config names the experts this chip holds
  (``expert_offset``, ``experts_held``). The router keeps all its outputs,
  groups and picks; a pick outside the held range adds nothing here, and the
  partial sum plus the shared experts is the layer's result on this chip
  (the exchange that would add the other chips' parts is
  ``parallel/``'s to build). Nothing is dropped: the grouped product
  (``ops/expert_matmul.py``) has no capacity.

Departures from the published code: rotate-half RoPE pairing as the rest of
the repo (the published code permutes interleaved pairs first — a fixed
column permutation of ``W_qb``/``W_kva`` that a checkpoint converter
applies; seeded weights make it the identity); ``kv_b_proj`` is held as its
two halves ``wk_b``/``wv_b``; the auxiliary routing losses are training-only
and absent.

State a program carries through ``forward`` (``init_cache``): the latent
cache, the expert counters (``expert_tokens`` per expert layer and held
expert, ``slots_routed``, ``slots_held``), summed on the device, and the
routers' picks for the last token of the latest forward (``picks``).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from .experts import (  # noqa: F401  (dense_experts: a name tests take here)
    EXPERT_LEAVES,
    _EXPERT_PIECE_TOKENS,
    by_rows,
    counters,
    dense_experts,
    expert_layer,
    grouped_experts,
    init_expert_state,
    last_picks,
)
from .llama import (
    _apply_rope,
    _embed_lookup,
    _lm_head_logits,
    _mlp_act,
    _proj,
    _rmsnorm,
)
from .llama import yarn_inv_freq as _yarn_inv_freq  # this module's takes cfg


@dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102_400
    dim: int = 5120
    n_layers: int = 60
    n_heads: int = 128
    # every head has its own keys and values once expanded; the CACHE holds
    # one latent row a token whatever this says
    n_kv_heads: int = 128
    head_dim: int = 192            # qk_nope_head_dim + qk_rope_head_dim
    intermediate: int = 12_288     # the dense layers' SwiGLU
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 16_384
    tie_embeddings: bool = False
    act: str = "silu"
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    moe_intermediate: int = 1536
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    first_k_dense_replace: int = 1
    # YaRN (rope_scaling)
    rope_factor: float = 40.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    # expert parallelism: the experts this chip holds (0 = all of them)
    expert_offset: int = 0
    experts_held: int = 0
    # W8A8 on multi-token forwards, as LlamaConfig's; the engine sets it
    w8a8_prefill: bool = False
    dtype: Any = field(default=jnp.bfloat16)

    def __post_init__(self):
        if self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError("head_dim is qk_nope_head_dim + qk_rope_head_dim")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")
        if self.expert_offset + self.n_held > self.n_routed_experts:
            raise ValueError("the held experts run past n_routed_experts")

    @property
    def n_held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def n_dense_layers(self) -> int:
        return min(self.first_k_dense_replace, self.n_layers)

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.head_dim ** -0.5 * m * m


def deepseek_v2(**kw) -> DeepseekV2Config:
    """deepseek-ai/DeepSeek-V2 ``config.json``, uncut."""
    return DeepseekV2Config(**kw)


def tiny_deepseek(**kw) -> DeepseekV2Config:
    """Small config for hermetic CPU tests: every mechanism, tiny widths."""
    base = dict(
        vocab_size=384, dim=64, n_layers=3, n_heads=4, n_kv_heads=4,
        head_dim=24, intermediate=128, max_seq_len=256, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_intermediate=32, n_routed_experts=16,
        n_shared_experts=2, num_experts_per_tok=3, n_group=4, topk_group=2,
        rope_original_max_len=64, rope_factor=4.0, dtype=jnp.float32,
    )
    base.update(kw)
    return DeepseekV2Config(**base)


# -- YaRN ---------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: DeepseekV2Config) -> jax.Array:
    """Inverse frequencies of the rotated ``qk_rope_head_dim``
    (``models.llama.yarn_inv_freq`` at this config's numbers)."""
    return _yarn_inv_freq(
        cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
        cfg.rope_original_max_len, cfg.rope_beta_fast, cfg.rope_beta_slow)


def rope_cos_sin(cfg: DeepseekV2Config, positions: jax.Array):
    """positions [B, S] -> cos/sin [B, S, qk_rope_head_dim / 2] float32."""
    angles = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg)
    m = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return jnp.cos(angles) * m, jnp.sin(angles) * m


# -- parameters and state -----------------------------------------------------

_EXPERTS = EXPERT_LEAVES


def init_params(key: jax.Array, cfg: DeepseekV2Config) -> dict:
    """Random init. Two stacks of layers, each on a leading layer dim:
    ``dense`` (the leading dense layers) and ``layers`` (the expert
    layers, which hold only the experts this chip holds)."""
    D, H, I = cfg.dim, cfg.n_heads, cfg.intermediate
    F, E = cfg.moe_intermediate, cfg.n_held
    Fs = cfg.n_shared_experts * F
    keys = iter(jax.random.split(key, 40))

    def norm(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale
                ).astype(cfg.dtype)

    def attention(L):
        return {
            "attn_norm": jnp.ones((L, D), cfg.dtype),
            "wq_a": norm((L, D, cfg.q_lora_rank)),
            "q_norm": jnp.ones((L, cfg.q_lora_rank), cfg.dtype),
            "wq_b": norm((L, cfg.q_lora_rank, H, cfg.head_dim)),
            "wkv_a": norm((L, D, cfg.latent_width)),
            "kv_norm": jnp.ones((L, cfg.kv_lora_rank), cfg.dtype),
            "wk_b": norm((L, cfg.kv_lora_rank, H, cfg.qk_nope_head_dim)),
            "wv_b": norm((L, cfg.kv_lora_rank, H, cfg.v_head_dim)),
            "wo": norm((L, H, cfg.v_head_dim, D)),
            "mlp_norm": jnp.ones((L, D), cfg.dtype),
        }

    Ld, Lm = cfg.n_dense_layers, cfg.n_expert_layers
    return {
        "embed": norm((cfg.vocab_size, D)),
        "dense": {
            **attention(Ld),
            "w_gate": norm((Ld, D, I)), "w_up": norm((Ld, D, I)),
            "w_down": norm((Ld, I, D)),
        },
        "layers": {
            **attention(Lm),
            # the router keeps every output: routing is over all experts
            "router": norm((Lm, D, cfg.n_routed_experts)),
            "we_gate": norm((Lm, E, D, F)), "we_up": norm((Lm, E, D, F)),
            "we_down": norm((Lm, E, F, D)),
            "ws_gate": norm((Lm, D, Fs)), "ws_up": norm((Lm, D, Fs)),
            "ws_down": norm((Lm, Fs, D)),
        },
        "final_norm": jnp.ones((D,), cfg.dtype),
        "lm_head": norm((D, cfg.vocab_size)),
    }


def init_cache(cfg: DeepseekV2Config, batch: int, cache_len: int, *,
               quantized: bool = False) -> dict:
    """What a program carries: the latent cache ``[L, B, C, rank + rope]``
    and the expert counters."""
    if quantized:
        raise ValueError("the latent cache has no int8 form")
    return {
        "latent": jnp.zeros(
            (cfg.n_layers, batch, cache_len, cfg.latent_width), cfg.dtype),
        **init_expert_state(cfg.n_expert_layers, cfg.n_held, batch,
                            cfg.num_experts_per_tok),
    }


# -- routing and experts ------------------------------------------------------


def route(scores: jax.Array, cfg: DeepseekV2Config):
    """Group-limited greedy routing. ``scores`` [T, n_routed_experts] are
    softmax outputs; returns (expert ids [T, k] int32, weights [T, k]):
    the ``num_experts_per_tok`` best experts inside the ``topk_group`` best
    groups, a group scored by its largest member; weights are the scores
    times ``routed_scaling_factor``, not renormalised."""
    T, E = scores.shape
    G = cfg.n_group
    group_best = jnp.max(scores.reshape(T, G, E // G), axis=-1)
    _, keep = jax.lax.top_k(group_best, cfg.topk_group)          # [T, kg]
    group_kept = jnp.any(
        keep[:, :, None] == jnp.arange(G)[None, None, :], axis=1)  # [T, G]
    kept = jnp.repeat(group_kept, E // G, axis=1)
    weights, ids = jax.lax.top_k(
        jnp.where(kept, scores, 0.0), cfg.num_experts_per_tok)
    return ids.astype(jnp.int32), weights * cfg.routed_scaling_factor


def _expert_ffn(h, lp, experts, slot, valid, cache, cfg: DeepseekV2Config,
                aq: bool, experts_fn):
    """Routed experts held here (``models/experts.py``, under this family's
    routing rule) + shared experts, and the counters."""
    B, S, D = h.shape
    x = h.reshape(B * S, D)

    def picks():
        logits = jnp.einsum(
            "td,de->te", x.astype(jnp.float32),
            lp["router"].astype(jnp.float32))
        return route(jax.nn.softmax(logits, axis=-1), cfg)

    routed, cache = expert_layer(
        x, picks, valid, experts, slot, cache, cfg,
        experts_fn, rows=B)
    with jax.named_scope("shared_experts"):
        gate = _proj("bsd,di->bsi", h, lp["ws_gate"], aq)
        up = _proj("bsd,di->bsi", h, lp["ws_up"], aq)
        shared = _proj("bsi,id->bsd", _mlp_act(gate, cfg.act) * up,
                       lp["ws_down"], aq)
    return routed.reshape(B, S, D).astype(h.dtype) + shared, cache


# -- attention ----------------------------------------------------------------
#
# An attention function takes a layer's compressed queries and gives the
# layer's attention output, projected: ``attend(c_q, rope, cache, layer_idx,
# lp, aq, gate=None) -> [B, S, D]`` (``gate``: ``_project_out``). It owns the up-projection of the queries, the
# attention and the output projection, so that the prefill can do all three
# for a few batch rows at a time: a chunk's queries (24 x 1024 x 128 x 192)
# and its output would otherwise be whole arrays of a gigabyte each. The
# scopes ``q_lora``, ``attn`` and ``attn_out`` are set here.


class LatentAttention(NamedTuple):
    attend: Callable
    # (S) -> [B, S] bool: which of the call's tokens are real (not under a
    # row's left pad); None where the caller's mask says
    real_tokens: Callable | None = None


def _leaf(w):
    """(values, per-channel scale or None) of a plain or int8 leaf."""
    return (w["q"], w["s"]) if isinstance(w, dict) else (w, None)


def _queries(c_q, rope, lp, aq: bool, cfg: DeepseekV2Config):
    """c_q [B, S, r] -> (q_nope [B, H, S, dn], q_rope [B, H, S, dr])."""
    with jax.named_scope("q_lora"):
        q = _proj("bsr,rhk->bshk", c_q, lp["wq_b"], aq)
        dn = cfg.qk_nope_head_dim
        q_rope = _apply_rope(q[..., dn:], *rope)
        return q[..., :dn].transpose(0, 2, 1, 3), q_rope.transpose(0, 2, 1, 3)


def _project_out(attn, lp, aq: bool, gate=None):
    """attn [B, H, S, dv] -> [B, S, D] through ``wo``; ``gate`` [B, S, H]
    float32 or None: a factor a token and head on the attention's output
    before the projection (``models/ling.py``'s head-wise gate; this
    family has none)."""
    with jax.named_scope("attn_out"):
        attn = attn.transpose(0, 2, 1, 3)
        if gate is not None:
            attn = (attn.astype(jnp.float32) * gate[..., None]
                    ).astype(attn.dtype)
        return _proj("bshk,hkd->bsd", attn, lp["wo"], aq)


def _scaled(x, s):
    """x [B, H, S, k] times an int8 leaf's scale s [H, k] a head and
    channel; a plain leaf has none."""
    if s is None:
        return x
    return (x.astype(jnp.float32) * s[None, :, None, :]).astype(x.dtype)


def _expanded_attention(q_nope, q_rope, lat, lp, cfg, attention):
    """Attention over keys and values expanded from latent rows ``lat``
    [B, T, rank + rope]; ``attention(q_nope, q_rope, k_nope, k_rope, v)``.
    An int8 ``wk_b``/``wv_b`` scale lies on a channel the attention keeps,
    so it multiplies the queries (keys) and the output (values): the
    expansion itself is one product with no epilogue."""
    c, kr = lat[..., :cfg.kv_lora_rank], lat[..., cfg.kv_lora_rank:]
    (wk, sk), (wv, sv) = _leaf(lp["wk_b"]), _leaf(lp["wv_b"])
    k_nope = jnp.einsum("btc,chk->bhtk", c, wk.astype(c.dtype))
    v = jnp.einsum("btc,chk->bhtk", c, wv.astype(c.dtype))
    return _scaled(attention(_scaled(q_nope, sk), q_rope, k_nope, kr, v), sv)


def dense_attention(cfg: DeepseekV2Config, mask) -> LatentAttention:
    """Masked attention over keys and values expanded from the WHOLE latent
    cache of a layer: the dense XLA path, for small sizes. mask [B, S, C]."""

    def attention(q_nope, q_rope, k_nope, k_rope, v):
        s = jnp.einsum("bhsk,bhtk->bhst", q_nope, k_nope,
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bhsk,btk->bhst", q_rope, k_rope,
                           preferred_element_type=jnp.float32)
        s = jnp.where(mask[:, None], s * cfg.softmax_scale,
                      jnp.finfo(jnp.float32).min)
        return jnp.einsum("bhst,bhtk->bhsk",
                          jax.nn.softmax(s, axis=-1).astype(v.dtype), v)

    def attend(c_q, rope, cache, layer_idx, lp, aq, gate=None):
        q_nope, q_rope = _queries(c_q, rope, lp, aq, cfg)
        with jax.named_scope("attn"):
            lat = jax.lax.dynamic_index_in_dim(
                cache["latent"], layer_idx, 0, keepdims=False)
            attn = _expanded_attention(q_nope, q_rope, lat, lp, cfg, attention)
        return _project_out(attn, lp, aq, gate)

    return LatentAttention(attend)


def _rows_a_piece(cfg: DeepseekV2Config, B: int, S: int) -> int:
    """Batch rows one piece of the prefill attention takes: as many as keep
    its arrays — S queries a head (projected, split, rotated) and the
    output; the kernel expands keys and values itself — under ~0.8 GB.
    Widths count in whole lanes of 128, as the device lays them out. (Four
    rows of the cell's 24: at six the program's temporaries were 114 MB more
    and the dispatch no faster; PERF.md section 6, PR 44.)"""
    lanes = lambda w: -(-w // 128) * 128  # noqa: E731
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    row = cfg.n_heads * jnp.dtype(cfg.dtype).itemsize * S * (
        lanes(cfg.head_dim) + lanes(dn) + lanes(dr) + lanes(dv))
    limit = max(1, int(0.8e9) // row)
    return max(r for r in range(1, B + 1) if B % r == 0 and r <= limit)


def prefill_attention(cfg: DeepseekV2Config, pad_lens, q_offset: int, *,
                      interpret: bool) -> LatentAttention:
    """The prefill attention of one chunk whose queries start at cache slot
    ``q_offset``: the layer's ``wk_b`` / ``wv_b`` are laid out a head once,
    then for a few batch rows at a time the queries are projected up and go
    through ``mla_prefill_attention``, which reads the rows' latent slots
    from the stacked cache in place (a layer's rows sliced out for it were
    226 MB at 24 rows of 8,192, a chunk and layer) and expands a key
    block's keys and values in VMEM (whole in HBM they would be 12.9 GB a
    layer), and the output is projected. An int8
    leaf's scale lies on a channel the attention keeps, so it multiplies
    the queries (keys) and the output (values): ``_expanded_attention``'s
    rule."""
    from ..ops.mla_attention import mla_prefill_attention

    def attend(c_q, rope, cache, layer_idx, lp, aq, gate=None):
        B, S, _ = c_q.shape
        latent = cache["latent"]
        (wk, sk), (wv, sv) = _leaf(lp["wk_b"]), _leaf(lp["wv_b"])
        with jax.named_scope("attn"):
            # [rank, H, k] -> a head's [rank, k] block, in the latent's type
            wk, wv = (w.astype(latent.dtype).transpose(1, 0, 2)
                      for w in (wk, wv))
        R = _rows_a_piece(cfg, B, S)

        def piece(args, first_row=0):
            c_q, cos, sin, pads, *gated = args
            q_nope, q_rope = _queries(c_q, (cos, sin), lp, aq, cfg)
            with jax.named_scope("attn"):
                attn = _scaled(mla_prefill_attention(
                    _scaled(q_nope, sk), q_rope, latent, wk, wv, pads,
                    scale=cfg.softmax_scale, q_offset=q_offset,
                    layer_idx=layer_idx, row_offset=first_row,
                    interpret=interpret), sv)
            return _project_out(attn, lp, aq, *gated)

        args = (c_q, *rope, pad_lens) + (() if gate is None else (gate,))
        if R == B:
            return piece(args)
        pieces = tuple(a.reshape((B // R, R) + a.shape[1:]) for a in args)
        out = jax.lax.map(lambda xs: piece(*xs),
                          (pieces, jnp.arange(0, B, R)))
        return out.reshape((B,) + out.shape[2:])

    def real_tokens(S: int):
        return (q_offset + jnp.arange(S))[None, :] >= pad_lens[:, None]

    return LatentAttention(attend, real_tokens)


def decode_attention(cfg: DeepseekV2Config, pad_lens, S: int, t, *,
                     interpret: bool) -> LatentAttention:
    """One absorbed decode step (step ``t`` after a prompt bucket of ``S``:
    its token sits at cache slot ``S + t``): the query goes through
    ``wk_b``, the kernel attends over the latent rows, the result goes
    through ``wv_b``. Keys and values are never expanded."""
    from ..ops.mla_attention import mla_decode_attention

    def attend(c_q, rope, cache, layer_idx, lp, aq, gate=None):
        q_nope, q_rope = _queries(c_q, rope, lp, aq, cfg)   # [B, H, 1, *]
        with jax.named_scope("attn"):
            (wk, sk), (wv, sv) = _leaf(lp["wk_b"]), _leaf(lp["wv_b"])
            qn = q_nope[:, :, 0]
            if sk is not None:
                qn = (qn.astype(jnp.float32) * sk[None]).astype(qn.dtype)
            q_lat = jnp.einsum("bhk,chk->bhc", qn, wk.astype(qn.dtype))
            o_lat = mla_decode_attention(
                q_lat, q_rope[:, :, 0], cache["latent"], layer_idx,
                pad_lens, S + t, scale=cfg.softmax_scale,
                rank=cfg.kv_lora_rank, interpret=interpret)
            attn = jnp.einsum("bhc,chk->bhk", o_lat, wv.astype(o_lat.dtype))
            if sv is not None:
                attn = (attn.astype(jnp.float32) * sv[None]).astype(attn.dtype)
        return _project_out(attn[:, :, None], lp, aq, gate)

    def real_tokens(n: int):
        return jnp.broadcast_to((S + t >= pad_lens)[:, None],
                                (pad_lens.shape[0], n))

    return LatentAttention(attend, real_tokens)


# -- the block and forward ----------------------------------------------------


def _latent_rows(c_kv, k_rope, dtype):
    """What the cache keeps of a token: its normalised latent and its one
    rotated key, side by side."""
    return jnp.concatenate([c_kv, k_rope], axis=-1).astype(dtype)


def _block(x, lp, layer_idx, slot, rope, attention: LatentAttention, valid,
           cache, write_index, cfg: DeepseekV2Config, experts=None,
           experts_fn=None):
    """One layer; ``slot`` is the layer's index among the expert layers (and
    ``experts`` their stacked expert weights), or None for a dense layer. The ``jax.named_scope`` names here and in the
    attention functions are metadata a device trace is read by (README
    "Device time by layer")."""
    aq = cfg.w8a8_prefill and x.shape[1] > 1
    rank = cfg.kv_lora_rank
    h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("q_lora"):
        c_q = _rmsnorm(_proj("bsd,dr->bsr", h, lp["wq_a"], aq),
                       lp["q_norm"], cfg.norm_eps)
    with jax.named_scope("kv_latent"):
        kv = _proj("bsd,dw->bsw", h, lp["wkv_a"], aq)
        c_kv = _rmsnorm(kv[..., :rank], lp["kv_norm"], cfg.norm_eps)
        k_rope = _apply_rope(kv[..., None, rank:], *rope)[:, :, 0]
    with jax.named_scope("kv_write"):
        latent = jax.lax.dynamic_update_slice(
            cache["latent"],
            _latent_rows(c_kv, k_rope, cache["latent"].dtype)[None],
            (layer_idx, 0, write_index, 0))
        # rows stay rows: left to itself the compiler lays the prefill's
        # cache out with the sequence innermost (576 is not whole lanes,
        # 8448 is) and the decode kernel's row-major, and copies the whole
        # cache between the phases: a second cache alive at the hand-over
        cache = dict(cache, latent=with_layout_constraint(
            latent, Layout(major_to_minor=(0, 1, 2, 3))))
    x = x + attention.attend(c_q, rope, cache, layer_idx, lp, aq)

    h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    if slot is None:
        def mlp(h):
            with jax.named_scope("mlp"):
                gate = _proj("bsd,di->bsi", h, lp["w_gate"], aq)
                up = _proj("bsd,di->bsi", h, lp["w_up"], aq)
                return _proj("bsi,id->bsd", _mlp_act(gate, cfg.act) * up,
                             lp["w_down"], aq)

        return x + by_rows(mlp, h, _EXPERT_PIECE_TOKENS), cache
    out, cache = _expert_ffn(h, lp, experts, slot, valid, cache, cfg, aq,
                             experts_fn)
    return x + out, cache


def forward(params: dict, cfg: DeepseekV2Config, tokens, positions, cache,
            write_index, mask, *, last_only: bool = False,
            stacked_attention_fn: LatentAttention | None = None,
            experts_fn=None):
    """Run the decoder over ``tokens`` [B, S] written at cache slots
    ``write_index ..``; returns (logits [B, S, vocab] float32, cache).

    ``stacked_attention_fn`` is the phase's attention over the stacked
    latent cache (``prefill_attention`` or ``decode_attention`` above); None
    is the dense XLA attention under ``mask`` [B, S, C].
    ``experts_fn(x, local, weights, experts, slot)`` is the routed experts'
    product (``grouped_experts``); None is ``dense_experts``."""
    attention = stacked_attention_fn or dense_attention(cfg, mask)
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    with jax.named_scope("q_lora"):   # the rope tables every layer reads
        rope = rope_cos_sin(cfg, positions)
    # a token under a row's left pad is routed nowhere and counted nowhere.
    # The kernels' functions know the pads; the dense path reads its mask
    # (such a token attends nothing)
    if attention.real_tokens is not None:
        valid = attention.real_tokens(tokens.shape[1])
    else:
        valid = jnp.any(mask, axis=-1)
    Ld = cfg.n_dense_layers

    def dense_step(carry, xs):
        h, cache = carry
        lp, li = xs
        h, cache = _block(h, lp, li, None, rope, attention, valid, cache,
                          write_index, cfg)
        return (h, cache), None

    def expert_step(carry, xs):
        h, cache = carry
        lp, slot = xs
        h, cache = _block(h, lp, Ld + slot, slot, rope, attention, valid,
                          cache, write_index, cfg, experts, experts_fn)
        return (h, cache), None

    # the experts stay out of the scan's slices: the grouped product reads
    # the stack in place, by the layer's index
    experts = {n: params["layers"][n] for n in _EXPERTS}
    scanned = {n: w for n, w in params["layers"].items() if n not in _EXPERTS}
    carry = (x, cache)
    if Ld:
        carry, _ = jax.lax.scan(
            dense_step, carry, (params["dense"], jnp.arange(Ld)))
    if cfg.n_expert_layers:
        carry, _ = jax.lax.scan(
            expert_step, carry,
            (scanned, jnp.arange(cfg.n_expert_layers)))
    x, cache = carry
    with jax.named_scope("lm_head"):
        if last_only:
            x = x[:, -1:, :]
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head_logits(x, params, cfg)
    return logits, cache


def forward_dense(params: dict, cfg: DeepseekV2Config, tokens) -> jax.Array:
    """Cache-free causal forward of whole sequences [B, S] with no kernel:
    logits [B, S, vocab] float32. (The latent rows still pass through a
    cache of exactly S slots: that is where this family's keys live.)"""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    logits, _ = forward(params, cfg, tokens, positions,
                        init_cache(cfg, B, S), 0, mask)
    return logits


# -- the engine's seam (models/family.py) -------------------------------------


def prefill_counts(cfg: DeepseekV2Config, pad_lens, spans,
                   cache_len=None) -> dict:
    """What the prefill kernel of one dispatch expanded from the latent, a
    head, from the pads it was packed with: ``latent_keys_expanded`` (the
    keys of the key blocks its computed tiles read: every chunk expands
    again what the chunks before it wrote) and ``latent_keys_real`` (the
    keys the rows have: what expanding each once would take), both x
    layers. ``spans`` are the prefill's query spans [lo, hi) over the
    bucket; a span's call sees the ``hi`` keys before its end."""
    import numpy as np

    from ..ops.mla_attention import prefill_tile_classes

    expanded = sum(
        prefill_tile_classes(pad_lens, hi - lo, hi, lo)["keys_expanded"]
        for lo, hi in spans)
    real = np.clip(spans[-1][1] - np.asarray(pad_lens, np.int64), 0, None)
    return {"latent_keys_expanded": expanded * cfg.n_layers,
            "latent_keys_real": int(real.sum()) * cfg.n_layers}


def _forward_kwargs(cfg: DeepseekV2Config, kernels: bool, interpret: bool):
    if not kernels:
        return {}   # flash=False: dense attention and dense_experts

    return {"experts_fn": functools.partial(
        grouped_experts, cfg=cfg, interpret=interpret)}


def _family():
    from .family import Family

    slot_loop = (
        "the slot programs (backend/inflight.py, engine._make_slot_*, "
        "_make_adopt_fn) read and scatter [L, B, KV, C, hd] keys and "
        "values and know no latent cache")
    return Family(
        name="deepseek-v2", forward=forward, init_cache=init_cache,
        init_params=init_params,
        # the MLA kernels take the published widths (128 + 64 / 128 / 512)
        # and, interpreted, any
        kernels_supported=lambda cfg, interpret: True,
        attention_supported=lambda cfg, S, C: (True, True),
        prefill_attention=lambda cfg, mesh, interpret, pad_lens, window,
        q_offset=0: prefill_attention(
            cfg, pad_lens, q_offset, interpret=interpret),
        decode_attention=lambda cfg, mesh, interpret, pad_lens, S, t,
        window: decode_attention(cfg, pad_lens, S, t, interpret=interpret),
        int8_cache=False, prefill_counts=prefill_counts,
        forward_kwargs=_forward_kwargs, counters=counters,
        row_record=last_picks,
        missing={
            "slot loop": slot_loop,
            "prefix cache": (
                "the block pool's slabs (cache/store.py) are "
                "[N, L, KV, BLK, hd] keys and values; a latent row has no "
                "KV heads"),
            "mesh": (
                "parallel/sharding.py has no expert axis and no exchange "
                "of the experts' partial sums, and shards a cache by KV "
                "heads the latent cache does not have"),
            "speculative decoding": (
                "the verify step writes per-row cache slots and runs the "
                "GQA verify kernel"),
            "long-context backend": (
                "the ring prefill and the sequence-sharded decode stream "
                "keys and values per KV head, not latent rows"),
        },
    )


FAMILY = _family()
