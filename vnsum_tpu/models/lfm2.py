"""LFM2-MoE family in functional JAX: gated short-convolution layers beside
a few rotary, QK-normed grouped-query attention layers, each followed by a
feed-forward — dense on the leading layers, sparse experts chosen by
sigmoid score + bias after them — for the one-shot generation program.

A seventh family behind ``models/family.py``, and the first whose only state
in most layers is a tail of TWO tokens: no recurrence, no keys, no values.
Its attention layers are ``models/llama.py``'s (the ``[L, B, KV, C, hd]``
cache over those layers only, ``_write_kv``, ``_cache_attention``, the two
flash kernels, ``_apply_rope`` and the per-head ``_rmsnorm`` of QK-norm),
its expert layer ``models/experts.py``'s in the three-matrix form under
that module's ``sigmoid_route`` (shared with ``models/nemotron_h.py``), its
convolution ``models/mamba_mixer.py``'s ``causal_conv`` with no bias and no
activation. What it owns is the config, the parameters, the state and
``forward``. ``FAMILY`` at the end is what the engine's seam picks up for
an ``Lfm2Config``.

The equations (``benchmarks/reference_lfm2.py`` is the same in plain
float32, whole sequences), ``u = RMSNorm(h; g)``:

- **Stack.** ``h = E[token]``. Layer ``l``: ``h = h + Op_l(RMSNorm(h))``,
  then ``h = h + FF_l(RMSNorm(h))``. ``Op_l`` by ``layer_types[l]``;
  ``FF_l`` dense for ``l < num_dense_layers``, sparse after. Final
  ``RMSNorm``, logits ``h E^T`` (tied).
- **conv.** ``b, c, x = u W_b, u W_c, u W_x`` (in_proj's three parts IN
  THIS ORDER, a leaf each); ``y = b * x``; ``z_t = sum_j w[:, j] *
  y_{t-(K-1)+j}`` depth-wise, causal, NO bias, NO activation, ``y`` zero
  before the row's first real token; ``o = (c * z) W_out``. What a layer
  keeps is ``y``'s last ``K - 1`` positions: the gated product, not the
  projection's output.
- **full_attention.** q ``[H, hd]``, k, v ``[KV, hd]``, no bias;
  ``RMSNorm`` over each head's dims of q and k (one weight ``[hd]`` for
  all heads) BEFORE the rotary; rotary over all of ``head_dim``,
  half-split; causal GQA ``softmax(q k^T / sqrt(hd)) v``; ``W_o``.
- **dense FF.** ``W_2 (silu(W_1 u) * W_3 u)``.
- **sparse FF.** ``s = sigmoid(u W_r)`` float32 over all experts; ``ids =
  top_k(s + b_e)`` (``expert_bias`` steers the CHOICE, never the weight);
  ``w = s[ids] / (sum(s[ids]) + 1e-6) * routed_scaling_factor``;
  ``sum_e w_e W_2e (silu(W_1e u) * W_3e u)``. No shared expert.
- **Left pads.** At a pad position (``mask``'s query row all False) ``u``
  is zeroed before in_proj, so ``b = c = x = y = 0`` (no bias anywhere in
  the operator): the tail is exactly zero when the row's first real token
  arrives, whatever the pad's length and however many prefill chunks or
  row pieces it spans. A pad token is routed nowhere and counted nowhere.

State a program carries (``init_cache``), three kinds side by side:
llama's cache over the attention layers alone; ``conv`` ``[conv layers, B,
K - 1, D]``, every convolution layer's tail, in ``state_dtype`` (the
activations' type); the sparse layers' expert counters and picks
(``init_expert_state`` with ``decode_touched``).

The stack is traced a few bodies whatever the depth (``_plan``): runs of
the pattern that repeat are a ``lax.scan`` over their repeats, each run of
one kind inside a period a scan of its own. The published 24 layers are
``[c]x2`` (dense), ``[A c c c]x4``, ``[A c c]x2``: five layer bodies.
"""
from __future__ import annotations

import functools
import types
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
    sigmoid_route,
)
from .llama import (
    _apply_rope,
    _attention_supported,
    _cache_attention,
    _cache_write,
    _decode_attention,
    _embed_lookup,
    _kernels_supported,
    _lm_head_logits,
    _mlp_act,
    _prefill_attention,
    _proj,
    _rmsnorm,
    _write_kv,
    init_kv_cache,
)
from .granite_hybrid import _runs   # the runs of one kind in a period
from .mamba_mixer import causal_conv

# LiquidAI/LFM2-8B-A1B layer_types: attention at 2, 6, 10, 14, 18, 21
PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))
_KINDS = ("conv", "full_attention")
# the published code's epsilon under the routing weights' sum
ROUTE_DENOMINATOR_EPS = 1e-6


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65_536
    dim: int = 2048
    n_layers: int = 24
    # "conv" | "full_attention" per layer; a model cut in depth takes the
    # leading ``n_layers`` of the published 24
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    conv_L_cache: int = 3             # the convolution's taps
    conv_bias: bool = False
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    num_dense_layers: int = 2
    intermediate: int = 7168          # the leading dense layers' width
    moe_intermediate: int = 1792
    n_routed_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    # what ``models/experts.py`` asks of a config: the experts this chip
    # holds of each sparse layer (None: all of them) from ``expert_offset``
    n_held: int | None = None
    expert_offset: int = 0
    max_seq_len: int = 128_000
    tie_embeddings: bool = True
    act: str = "silu"
    # W8A8 on multi-token forwards, as LlamaConfig's; the engine sets it
    w8a8_prefill: bool = False
    dtype: Any = field(default=jnp.bfloat16)
    # the convolution tail's type (None: the activations'); anything
    # narrower is a precision cut a parity check has to see
    state_dtype: Any = None

    def __post_init__(self):
        layout = tuple(self.layer_types)[:self.n_layers]
        if len(layout) != self.n_layers or set(layout) - set(_KINDS):
            raise ValueError(
                f"layer_types needs {self.n_layers} entries of 'conv' or "
                f"'full_attention', got {tuple(self.layer_types)}")
        object.__setattr__(self, "layer_types", layout)
        if self.conv_bias:
            raise ValueError(
                "conv_bias true: this family builds the convolution with no "
                "bias (a bias would leak through a row's left pad)")
        if not (self.norm_topk_prob and self.use_expert_bias):
            raise ValueError(
                "this family builds the router with use_expert_bias and "
                "norm_topk_prob true")
        if not 0 <= self.num_dense_layers <= self.n_layers:
            raise ValueError(
                f"num_dense_layers {self.num_dense_layers} past the depth "
                f"{self.n_layers}")
        if self.n_held is None:
            object.__setattr__(self, "n_held", self.n_routed_experts)
        if self.state_dtype is None:
            object.__setattr__(self, "state_dtype", self.dtype)
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        if (self.n_routed_experts % self.n_held
                or self.expert_offset % self.n_held
                or self.expert_offset + self.n_held > self.n_routed_experts):
            raise ValueError(
                f"n_held {self.n_held} from expert_offset "
                f"{self.expert_offset} is no whole share of "
                f"{self.n_routed_experts} experts")

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def n_conv(self) -> int:
        return self.layer_types.count("conv")

    @property
    def n_attention(self) -> int:
        return self.layer_types.count("full_attention")

    @property
    def n_sparse(self) -> int:
        return self.n_layers - self.num_dense_layers


def lfm2_8b_a1b(**kw) -> Lfm2Config:
    """LiquidAI/LFM2-8B-A1B ``config.json``, uncut."""
    return Lfm2Config(**kw)


def tiny_lfm2(**kw) -> Lfm2Config:
    """Small config for hermetic CPU tests: two dense layers and two
    periods ``A c c c`` after them, 8 experts top-2, 4 / 2 heads of 16. The
    vocabulary holds the byte tokenizer's 256 bytes and its special ids."""
    base = dict(
        vocab_size=384, dim=64, n_layers=10,
        layer_types=("conv", "conv") + ("full_attention", "conv", "conv",
                                        "conv") * 2,
        n_heads=4, n_kv_heads=2, head_dim=16, rope_theta=10_000.0,
        intermediate=128, moe_intermediate=32, n_routed_experts=8,
        num_experts_per_tok=2, max_seq_len=256, dtype=jnp.float32,
    )
    base.update(kw)
    return Lfm2Config(**base)


# -- parameters and state -----------------------------------------------------

# how far the seeded ``expert_bias`` spreads: enough to move more than a
# tenth of a seeded router's picks against a zero bias, so that "the bias
# left out" and "the bias in the weight" are faults a check sees
_BIAS_SPREAD = 0.05


def float_leaves(key: jax.Array, cfg: Lfm2Config) -> dict:
    """{group: {leaf: array}} of the leaves ``models/quant.py``'s direct
    int8 init must not draw its own way, float32 whatever the weights'
    type: the taps ``U[-1/sqrt(K), 1/sqrt(K)]`` (a depth-wise ``Conv1d``'s
    default), the router ``N(0, 0.02)`` and ``expert_bias``; and the
    QK-norm's weights ``U[0.5, 1.5]`` in the activations' type — at a
    weight of one a norm and a rotary commute, and "the rotary first"
    would be a fault no check could see."""
    kw, kr, kb, kq, kk = jax.random.split(key, 5)
    K, Ls, E = cfg.conv_L_cache, cfg.n_sparse, cfg.n_routed_experts
    bound = K ** -0.5
    head = (cfg.n_attention, cfg.head_dim)
    return {
        "attn": {
            "q_norm": jax.random.uniform(kq, head, jnp.float32, 0.5, 1.5
                                         ).astype(cfg.dtype),
            "k_norm": jax.random.uniform(kk, head, jnp.float32, 0.5, 1.5
                                         ).astype(cfg.dtype)},
        "conv": {"conv_w": jax.random.uniform(
            kw, (cfg.n_conv, cfg.dim, K), jnp.float32, -bound, bound)},
        "layers": {
            "router": jax.random.normal(kr, (Ls, cfg.dim, E), jnp.float32)
            * 0.02,
            "expert_bias": jax.random.normal(kb, (Ls, E), jnp.float32)
            * _BIAS_SPREAD,
        },
    }


def init_params(key: jax.Array, cfg: Lfm2Config) -> dict:
    """Random init, stacked by kind: the convolution operators under
    ``conv``, the attention operators under ``attn``, the leading dense
    feed-forwards under ``dense``, the routers and experts under
    ``layers``; each group with the norm before it."""
    D, Lc, La = cfg.dim, cfg.n_conv, cfg.n_attention
    Ld, Ls = cfg.num_dense_layers, cfg.n_sparse
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    F, Fe, E = cfg.intermediate, cfg.moe_intermediate, cfg.n_held
    keys = iter(jax.random.split(key, 24))

    def norm(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale
                ).astype(cfg.dtype)

    floats = float_leaves(next(keys), cfg)
    return {
        "embed": norm((cfg.vocab_size, D)),
        "conv": {
            "op_norm": jnp.ones((Lc, D), cfg.dtype),
            # in_proj, a product a part (B | C | x): no slice of a chunk's
            # 3 D-wide output
            "in_b": norm((Lc, D, D)), "in_c": norm((Lc, D, D)),
            "in_x": norm((Lc, D, D)), "out_proj": norm((Lc, D, D)),
            **floats["conv"],
        },
        "attn": {
            "op_norm": jnp.ones((La, D), cfg.dtype),
            "wq": norm((La, D, H, hd)), "wk": norm((La, D, KV, hd)),
            "wv": norm((La, D, KV, hd)), "wo": norm((La, H, hd, D)),
            **floats["attn"],
        },
        "dense": {
            "ffn_norm": jnp.ones((Ld, D), cfg.dtype),
            "w_gate": norm((Ld, D, F)), "w_up": norm((Ld, D, F)),
            "w_down": norm((Ld, F, D)),
        },
        "layers": {
            "ffn_norm": jnp.ones((Ls, D), cfg.dtype),
            **floats["layers"],
            "we_gate": norm((Ls, E, D, Fe)), "we_up": norm((Ls, E, D, Fe)),
            "we_down": norm((Ls, E, Fe, D)),
        },
        "final_norm": jnp.ones((D,), cfg.dtype),
    }


def init_cache(cfg: Lfm2Config, batch: int, cache_len: int, *,
               quantized: bool = False) -> dict:
    """What a program carries: llama's KV cache over the attention layers
    alone, every convolution layer's tail (channels on the lanes), the
    sparse layers' expert counters and picks."""
    attention = types.SimpleNamespace(
        n_layers=cfg.n_attention, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, dtype=cfg.dtype)
    return {
        **init_kv_cache(attention, batch, cache_len, quantized=quantized),
        "conv": jnp.zeros((cfg.n_conv, batch, cfg.conv_L_cache - 1, cfg.dim),
                          cfg.state_dtype),
        **init_expert_state(cfg.n_sparse, cfg.n_held, batch,
                            cfg.num_experts_per_tok, decode_touched=True),
    }


# -- the operators, the feed-forwards and forward -----------------------------


def _conv_operator(u, lp, slot, cache, cfg: Lfm2Config, cache_rows=None):
    """The gated short convolution over u [B, S, D] (normed, zero under the
    pad) at convolution slot ``slot`` of the state. ``cache_rows`` [B]: u
    is a row piece and row b's tail lives at the state's batch row
    ``cache_rows[b]``, read and written there in place. The
    ``jax.named_scope`` names are metadata a device trace is read by
    (README "Device time by layer")."""
    aq = cfg.w8a8_prefill and u.shape[1] > 1
    with jax.named_scope("shortconv_in"):
        b = _proj("bsd,de->bse", u, lp["in_b"], aq)
        c = _proj("bsd,de->bse", u, lp["in_c"], aq)
        x = _proj("bsd,de->bse", u, lp["in_x"], aq)
    with jax.named_scope("shortconv"):
        tail = jax.lax.dynamic_index_in_dim(cache["conv"], slot, 0, False)
        if cache_rows is not None:
            tail = tail[cache_rows]
        # what the tail holds is what the taps read: the gated product in
        # the state's type, whether it came from this chunk or the last
        y = (b * x).astype(cache["conv"].dtype)
        z, tail = causal_conv(y, tail, lp["conv_w"], None, None)
        gated = (c.astype(jnp.float32) * z).astype(u.dtype)
        conv = _cache_write(cache["conv"], tail, slot, 0, cache_rows)
    with jax.named_scope("shortconv_out"):
        out = _proj("bse,ed->bsd", gated, lp["out_proj"], aq)
    return out, dict(cache, conv=conv)


def _attention_operator(u, lp, slot, rope, mask, cache, write_index,
                        cfg: Lfm2Config, stacked_attention_fn,
                        cache_rows=None):
    aq = cfg.w8a8_prefill and u.shape[1] > 1
    with jax.named_scope("qkv"):
        q = _proj("bsd,dhk->bshk", u, lp["wq"], aq)
        k = _proj("bsd,dhk->bshk", u, lp["wk"], aq)
        v = _proj("bsd,dhk->bshk", u, lp["wv"], aq)
        # over each head's dims, before the rotary
        q = _apply_rope(_rmsnorm(q, lp["q_norm"], cfg.norm_eps), *rope)
        k = _apply_rope(_rmsnorm(k, lp["k_norm"], cfg.norm_eps), *rope)
    cache = _write_kv(cache, k, v, slot, write_index, cache_rows)
    attn = _cache_attention(q, cache, slot, mask, cfg.q_per_kv, None,
                            stacked_attention_fn, cache_rows)
    with jax.named_scope("attn_out"):
        return _proj("bshk,hkd->bsd", attn, lp["wo"], aq), cache


def _dense_ffn(x, lp, cfg: Lfm2Config):
    """The leading layers' SwiGLU over the normed x [B, S, D] (the caller
    adds the residual, as for ``_sparse_ffn``)."""
    aq = cfg.w8a8_prefill and x.shape[1] > 1
    u = _rmsnorm(x, lp["ffn_norm"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        gate = _proj("bsd,di->bsi", u, lp["w_gate"], aq)
        up = _proj("bsd,di->bsi", u, lp["w_up"], aq)
        return _proj("bsi,id->bsd", _mlp_act(gate, cfg.act) * up,
                     lp["w_down"], aq)


def _sparse_ffn(x, lp, experts, slot, valid, cache, cfg: Lfm2Config,
                experts_fn, cache_rows=None):
    """The routed experts (``models/experts.py``, under ``sigmoid_route``)
    over x [B, S, D], and the counters."""
    B, S, D = x.shape
    flat = _rmsnorm(x, lp["ffn_norm"], cfg.norm_eps).reshape(B * S, D)

    def picks():
        return sigmoid_route(
            jnp.einsum("td,de->te", flat.astype(jnp.float32),
                       lp["router"].astype(jnp.float32)),
            lp["expert_bias"].astype(jnp.float32), cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, ROUTE_DENOMINATOR_EPS)

    routed, cache = expert_layer(flat, picks, valid, experts, slot, cache,
                                 cfg, experts_fn, rows=B,
                                 cache_rows=cache_rows)
    return routed.reshape(B, S, D).astype(x.dtype), cache


def _plan(kinds: tuple) -> list:
    """[(first layer, period, repeats)] covering ``kinds`` (one hashable a
    layer): from the left, the run of layers whose period repeats over the
    most layers, else the layers one at a time. A block is traced once and
    scanned over its repeats."""
    blocks, i, L = [], 0, len(kinds)
    while i < L:
        best = (1, 1)
        for p in range(1, (L - i) // 2 + 1):
            r = 1
            while kinds[i + r * p:i + (r + 1) * p] == kinds[i:i + p]:
                r += 1
            if r > 1 and p * r > best[0] * best[1]:
                best = (p, r)
        blocks.append((i, kinds[i:i + best[0]], best[1]))
        i += best[0] * best[1]
    return blocks


def forward(params: dict, cfg: Lfm2Config, tokens, positions, cache,
            write_index, mask, *, last_only: bool = False,
            stacked_attention_fn=None, experts_fn=None, cache_rows=None):
    """Run the decoder over ``tokens`` [B, S] written at cache slots
    ``write_index ..``; returns (logits [B, S, vocab] float32, state).

    ``stacked_attention_fn(q, cache, layer_idx)`` is the phase's kernel over
    the stacked cache of the attention layers (llama's); None is the dense
    XLA attention under ``mask`` [B, S, C]. ``experts_fn(x, local, weights,
    experts, slot)`` is the routed experts' product (``grouped_experts``);
    None is ``dense_experts``.

    ``cache_rows`` [B] int32: the tokens are a row piece of a batch whose
    state holds more rows (the engine's prefill, ``Family.
    prefill_piece_tokens``) and row b of them lives at the state's batch
    row ``cache_rows[b]`` — keys, values, convolution tail and picks
    written and read there in place, the state's other rows left as they
    are. A (row, chunk) piece that is all left pad need not run: under the
    pad the operator's input is zeroed and nothing in it has a bias, so the
    tail stays the zeros it came as."""
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    with jax.named_scope("qkv"):  # the rope table the attention layers read
        half = cfg.head_dim // 2
        inv = 1.0 / (cfg.rope_theta ** (
            jnp.arange(0, half, dtype=jnp.float32) / half))
        angles = positions[..., None].astype(jnp.float32) * inv
        rope = (jnp.cos(angles), jnp.sin(angles))
    # a token under a row's left pad: its query row of the mask is all False
    valid = jnp.any(mask, axis=-1)
    # the experts stay out of the layers' slices: the grouped product reads
    # the stack in place, by the sparse layer's index
    experts = {n: params["layers"][n] for n in EXPERT_LEAVES}
    sparse = {n: w for n, w in params["layers"].items()
              if n not in EXPERT_LEAVES}
    Ld = cfg.num_dense_layers

    def one(tree, i):
        """Layer i of a stacked group, read where it is used: the slice
        fuses into the products that consume it (handing a run's layers to
        an inner scan as its own arrays copies them)."""
        return jax.tree.map(
            lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False),
            tree)

    def layer(carry, kind, op_slot, l):
        """One layer of ``kind`` (operator, dense?) at slot ``op_slot`` of
        its operator's group; ``l`` its index in the stack."""
        x, cache = carry
        op, dense = kind
        group = params["conv" if op == "conv" else "attn"]
        lp = one(group, op_slot)
        u = _rmsnorm(x, lp["op_norm"], cfg.norm_eps)
        if op == "conv":
            u = jnp.where(valid[..., None], u, jnp.zeros_like(u))
            out, cache = _conv_operator(u, lp, op_slot, cache, cfg,
                                        cache_rows)
        else:
            out, cache = _attention_operator(
                u, lp, op_slot, rope, mask, cache, write_index, cfg,
                stacked_attention_fn, cache_rows)
        x = x + out.astype(x.dtype)
        if dense:
            return x + _dense_ffn(x, one(params["dense"], l), cfg), cache
        out, cache = _sparse_ffn(x, one(sparse, l - Ld), experts, l - Ld,
                                 valid, cache, cfg, experts_fn, cache_rows)
        return x + out, cache

    kinds = tuple((op, l < Ld) for l, op in enumerate(cfg.layer_types))
    # operator slots before each layer: conv layers before it, or attention
    before = [sum(k[0] == kinds[l][0] for k in kinds[:l])
              for l in range(cfg.n_layers)]
    carry = (x, cache)
    for first, period, repeats in _plan(kinds):
        P = len(period)

        def period_step(carry, p, first=first, period=period, P=P):
            for kind, j0, count in _runs(period):
                # an operator's slots in one period of this block
                per = sum(k[0] == kind[0] for k in period)

                def step(carry, j, kind=kind, j0=j0, per=per):
                    return layer(
                        carry, kind, before[first + j0] + p * per + j,
                        first + p * P + j0 + j), None

                if count == 1:
                    carry, _ = step(carry, 0)
                else:
                    carry, _ = jax.lax.scan(step, carry, jnp.arange(count))
            return carry, None

        if repeats == 1:
            carry, _ = period_step(carry, 0)
        else:
            carry, _ = jax.lax.scan(period_step, carry, jnp.arange(repeats))
    x, cache = carry
    with jax.named_scope("lm_head"):
        if last_only:
            x = x[:, -1:, :]
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head_logits(x, params, cfg)
    return logits, cache


def forward_dense(params: dict, cfg: Lfm2Config, tokens) -> jax.Array:
    """Cache-free causal forward of whole sequences [B, S] with no kernel:
    logits [B, S, vocab] float32. (Keys and values still pass through a
    cache of exactly S slots, the convolution through a tail from zero.)"""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    logits, _ = forward(params, cfg, tokens, positions,
                        init_cache(cfg, B, S), 0, mask)
    return logits


# -- the engine's seam (models/family.py) -------------------------------------

# the fewest tokens a row piece of the prefill holds: four rows of a
# 2,048-token chunk, so that each of 32 experts sees ~1,024 rows of a piece
# (4 picks a token) and a piece's temporaries stay a fixed size whatever
# the batch
PREFILL_PIECE_TOKENS = 8192


def piece_rows(B: int, n: int) -> int:
    """Rows of a batch of B one piece of an n-token prefill chunk holds, as
    ``TpuBackend._prefill_piece_rows`` reckons it; 0 = the whole batch."""
    return next((R for R in range(1, B + 1)
                 if B % R == 0 and R * n >= PREFILL_PIECE_TOKENS), 0)


def prefill_counts(cfg: Lfm2Config, pad_lens, spans, cache_len=None) -> dict:
    """What the convolution operators of one dispatch's prefill saw, from
    the pads it was packed with: real prompt tokens x convolution layers,
    and the tokens of the (row piece, chunk) forwards that ran x
    convolution layers — a piece whose rows hold nothing but left pad in a
    chunk is not run (``TpuBackend._prefill_forward``) and counts as not
    computed. ``spans`` are the prefill's query spans [lo, hi) over the
    bucket; the operator reads no cache, so ``cache_len`` is taken and not
    read."""
    import numpy as np

    pads = np.asarray(pad_lens, np.int64)
    B = len(pads)
    real = computed = 0
    for lo, hi in spans:
        real += int(((hi - lo) - np.clip(pads - lo, 0, hi - lo)).sum())
        R = piece_rows(B, hi - lo)
        dead = int((pads >= hi).sum()) // R * R if R else 0
        computed += (B - dead) * (hi - lo)
    return {"conv_tokens_real": real * cfg.n_conv,
            "conv_tokens_computed": computed * cfg.n_conv}


def row_record(cache: dict) -> dict:
    """What a parity check may see of the position just scored: the first
    and the last convolution layer's tail [2, B, K - 1, D] — the first
    carries one product's rounding, the last everything before it — and
    the routers' picks [sparse layers, B, k]."""
    return {"tail": jnp.stack([cache["conv"][0], cache["conv"][-1]]),
            "picks": cache["picks"]}


def _forward_kwargs(cfg: Lfm2Config, kernels: bool, interpret: bool):
    if not kernels:
        return {}   # flash=False: dense attention and dense_experts
    return {"experts_fn": functools.partial(
        grouped_experts, cfg=cfg, interpret=interpret)}


def _family():
    from .family import Family

    carries_state = (
        "this family's state holds every convolution layer's two-token "
        "tail and the sparse layers' expert counters and picks beside the "
        "keys and values of its few attention layers")
    return Family(
        name="lfm2", forward=forward, init_cache=init_cache,
        init_params=init_params, kernels_supported=_kernels_supported,
        attention_supported=_attention_supported,
        prefill_attention=_prefill_attention,
        decode_attention=_decode_attention, counts_prefill_blocks=True,
        attention_layers=lambda cfg: cfg.n_attention,
        prefill_counts=prefill_counts,
        prefill_piece_tokens=PREFILL_PIECE_TOKENS,
        forward_kwargs=_forward_kwargs, counters=counters,
        row_record=row_record,
        missing={
            "slot loop": (
                "the slot programs (backend/inflight.py, engine._make_slot_*"
                ", _make_adopt_fn) fill one row at a time, scatter every "
                "leaf of a joined batch's cache on its second axis, as keys "
                "and values, and return no counters; adopting and evicting "
                "a row would have to move a tail-only state they do not "
                "carry: " + carries_state),
            "prefix cache": (
                "cache/radix.py and cache/store.py slice keys and values "
                "by block at any token and the resume program returns the "
                "final cache in the counters' place; a convolution resumes "
                "only from a snapshot of its tail taken at the block's "
                "boundary, and none is kept: " + carries_state),
            "mesh": (
                "parallel/sharding.py has no specs for the convolution "
                "operator's parameters (in_proj's parts, the taps, "
                "out_proj), the router, its bias and the stacked experts, "
                "for the tails, no expert axis and no exchange of the "
                "experts' partial sums"),
            "speculative decoding": (
                "a rejected draft has to roll every convolution layer's "
                "tail back to the last accepted token, and the verify step "
                "keeps no tail per position and hands a KV cache alone from "
                "step to step: " + carries_state),
            "long-context backend": (
                "the ring prefill runs models.llama.cache_free_block and "
                "passes keys and values between shards; a convolution would "
                "have to hand its tail from shard to shard in order, and "
                "the block has no expert layer"),
        },
    )


FAMILY = _family()
