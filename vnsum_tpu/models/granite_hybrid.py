"""Granite-4.0-H family in functional JAX: Mamba-2 layers beside a few
position-free grouped-query attention layers, each followed by a dense
SwiGLU, for the one-shot generation program.

A fifth family behind ``models/family.py``, and the first whose state
between steps is not keys and values alone. Its attention layers are
``models/llama.py``'s — the ``[L, B, KV, C, hd]`` cache over those layers
only (``init_kv_cache``, int8 with per-token scales), ``_write_kv``,
``_cache_attention`` and the two flash kernels, at 64-wide heads — and its
Mamba-2 mixer is ``models/mamba_mixer.py``'s (shared with
``models/nemotron_h.py``; one group of B and C here), over
``ops/ssd_scan.py``. What it owns is the config, the parameters, the
state and ``forward``. ``FAMILY`` at the
end is what the engine's seam picks up for a ``GraniteHybridConfig``.

The equations (``benchmarks/reference_granite_h.py`` is the same in plain
float32, the recurrence token by token), for layer ``l`` with input ``x``:

- **Embedding** ``x0 = embedding_multiplier * E[token]``. **Every layer**
  ``x' = x + residual_multiplier * mixer(RMSNorm(x))``, then
  ``out = x' + residual_multiplier * W_d (silu(g) * u)`` with ``g, u`` the
  gate and up products of ``RMSNorm(x')``. **Final** ``RMSNorm``, logits
  ``(h E^T) / logits_scaling`` (the embedding is the head).
- **Attention mixer** (``layer_types[l] == "attention"``): q ``[H, hd]``,
  k, v ``[KV, hd]``, no bias, no rotary, no QK-norm; causal GQA with
  scores ``* attention_multiplier`` (1/64 at 64-wide heads, not
  1/sqrt(64): the queries are scaled by the ratio before the kernels,
  which divide by sqrt(hd)); ``a W_o``.
- **Mamba-2 mixer**: ``[z | xBC | dt] = h W_in`` (inner | inner + 2 N | heads;
  held as its three parts ``in_z``, ``in_xbc``, ``in_dt``).
  ``xBC_t = silu(b_c + sum_j w_c[:, j] * xBC_{t-3+j})`` per channel, zeros
  before the row's first real token. ``xBC`` splits into ``X [heads, P]``,
  ``B [N]``, ``C [N]``. ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``
  per head. ``H_t = exp(dt_t A) H_{t-1} + dt_t X_t (x) B_t``;
  ``Y_t = H_t C_t + D X_t``. Gate, then norm:
  ``y = RMSNorm(Y * silu(z)) * w_n`` over the whole inner width (one
  group); ``y W_out``.
- **Left pads.** The engine pads rows on the left and a recurrence runs
  through pads. At a pad position (``mask`` shows it: its query row is all
  False) ``h`` is zeroed before ``W_in`` and ``xBC`` again after the
  convolution, whose bias would leak: the state and the convolution's tail
  are exactly zero when the row's first real token arrives, whatever the
  pad's length and however many prefill chunks it spans.

State a program carries (``init_cache``), side by side:

- ``k, v, ks, vs``: llama's cache over the attention layers alone,
  ``[attention layers, B, KV, C, hd]``;
- ``conv``: the convolution's tail, the last ``d_conv - 1`` inputs of every
  Mamba layer, ``[mamba layers, B, d_conv - 1, inner + 2 N]`` in the
  activations' type (channels on the lanes: with the three tail positions
  last, the array's tiles would be 3 lanes of 128 full);
- ``ssm``: the recurrent state, ``[mamba layers, B, N, heads * P]`` float32
  (``ops/ssd_scan.py`` says why it is laid out so). Its size does not grow
  with the row.

The stack is a ``lax.scan`` over the periods of ``layer_types`` (ten
layers: five Mamba, one attention, four Mamba), each run of one kind inside
a period a scan of its own: a program traces two Mamba layers and one
attention layer whatever the depth.
"""
from __future__ import annotations

import types
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from .llama import (
    _attention_supported,
    _cache_attention,
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

from .mamba_mixer import (  # noqa: F401  (the names this module had)
    MAMBA_VECTORS,
    causal_conv,
    init_mamba_params,
    init_mamba_state,
    init_mamba_vectors,
    last_state,
    mamba_mixer,
    prefill_counts,
)

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100_352
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    intermediate: int = 8192          # shared_intermediate_size
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # "mamba" | "attention" per layer; empty = the published period of ten
    layer_types: tuple = ()
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    attention_multiplier: float = 0.015625
    norm_eps: float = 1e-5
    # published, and read by nothing: position_embedding_type is "nope"
    rope_theta: float = 10_000.0
    max_seq_len: int = 131_072
    tie_embeddings: bool = True
    act: str = "silu"
    # W8A8 on multi-token forwards, as LlamaConfig's; the engine sets it
    w8a8_prefill: bool = False
    dtype: Any = field(default=jnp.bfloat16)
    # the recurrent state's type: float32 as assumed; anything narrower is
    # a precision cut a parity check has to see
    state_dtype: Any = field(default=jnp.float32)

    def __post_init__(self):
        layout = tuple(self.layer_types) or tuple(
            _PERIOD[i % len(_PERIOD)] for i in range(self.n_layers))
        if len(layout) != self.n_layers or set(layout) - {"mamba",
                                                          "attention"}:
            raise ValueError(
                f"layer_types needs {self.n_layers} entries of 'mamba' or "
                f"'attention', got {layout}")
        object.__setattr__(self, "layer_types", layout)
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        if self.mamba_n_groups != 1:
            raise ValueError(
                "one group of B and C, and one norm group, is what this "
                f"family builds; mamba_n_groups={self.mamba_n_groups}")

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: X, B and C."""
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def n_attention(self) -> int:
        return self.layer_types.count("attention")

    @property
    def period(self) -> tuple:
        """The shortest run of kinds that ``layer_types`` repeats."""
        L = self.n_layers
        for p in range(1, L + 1):
            if L % p == 0 and self.layer_types == self.layer_types[:p] * (
                    L // p):
                return self.layer_types[:p]
        return self.layer_types


def granite_4_0_h_micro(**kw) -> GraniteHybridConfig:
    """ibm-granite/granite-4.0-h-micro ``config.json``, uncut."""
    return GraniteHybridConfig(**kw)


def tiny_granite_h(**kw) -> GraniteHybridConfig:
    """Small config for hermetic CPU tests: two periods with the attention
    layer inside, 4 heads of 16, a state of 16, scan chunks of 8."""
    base = dict(
        vocab_size=384, dim=64, n_layers=20, n_heads=4, n_kv_heads=2,
        head_dim=16, intermediate=128, mamba_n_heads=8, mamba_d_head=16,
        mamba_d_state=16, mamba_chunk_size=8, max_seq_len=256,
        dtype=jnp.float32,
    )
    base.update(kw)
    return GraniteHybridConfig(**base)


# -- parameters and state -----------------------------------------------------


def float_leaves(key: jax.Array, cfg: GraniteHybridConfig) -> dict:
    """{group: {leaf: array}} of the leaves ``models/quant.py``'s direct
    int8 init must not draw its own way."""
    return {"mamba": init_mamba_vectors(key, cfg)}


def init_params(key: jax.Array, cfg: GraniteHybridConfig) -> dict:
    """Random init: the Mamba layers stacked under ``mamba``, the attention
    layers under ``attn``, every layer's feed-forward under ``layers``."""
    L, Lm, La, D = cfg.n_layers, cfg.n_mamba, cfg.n_attention, cfg.dim
    H, KV, hd, F = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.intermediate
    keys = iter(jax.random.split(key, 16))

    def norm(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale
                ).astype(cfg.dtype)

    return {
        "embed": norm((cfg.vocab_size, D)),
        "mamba": init_mamba_params(norm, next(keys), cfg),
        "attn": {
            "mixer_norm": jnp.ones((La, D), cfg.dtype),
            "wq": norm((La, D, H, hd)), "wk": norm((La, D, KV, hd)),
            "wv": norm((La, D, KV, hd)), "wo": norm((La, H, hd, D)),
        },
        "layers": {
            "mlp_norm": jnp.ones((L, D), cfg.dtype),
            "w_gate": norm((L, D, F)), "w_up": norm((L, D, F)),
            "w_down": norm((L, F, D)),
        },
        "final_norm": jnp.ones((D,), cfg.dtype),
    }


def init_cache(cfg: GraniteHybridConfig, batch: int, cache_len: int, *,
               quantized: bool = False) -> dict:
    """What a program carries: llama's KV cache over the attention layers
    alone, every Mamba layer's convolution tail and recurrent state."""
    attention = types.SimpleNamespace(
        n_layers=cfg.n_attention, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, dtype=cfg.dtype)
    return {
        **init_kv_cache(attention, batch, cache_len, quantized=quantized),
        **init_mamba_state(cfg, batch),
    }


# -- the mixers and forward ---------------------------------------------------


def _attention_mixer(h, lp, slot, mask, cache, write_index,
                     cfg: GraniteHybridConfig, stacked_attention_fn,
                     cache_rows=None):
    aq = cfg.w8a8_prefill and h.shape[1] > 1
    with jax.named_scope("qkv"):
        # the kernels and the dense path divide by sqrt(hd); this family's
        # scale is attention_multiplier
        q = _proj("bsd,dhk->bshk", h, lp["wq"], aq) * jnp.asarray(
            cfg.attention_multiplier * cfg.head_dim ** 0.5, h.dtype)
        k = _proj("bsd,dhk->bshk", h, lp["wk"], aq)
        v = _proj("bsd,dhk->bshk", h, lp["wv"], aq)
    cache = _write_kv(cache, k, v, slot, write_index, cache_rows)
    attn = _cache_attention(q, cache, slot, mask, cfg.q_per_kv, None,
                            stacked_attention_fn, cache_rows)
    with jax.named_scope("attn_out"):
        return _proj("bshk,hkd->bsd", attn, lp["wo"], aq), cache


def _ffn(x, lp, cfg: GraniteHybridConfig):
    aq = cfg.w8a8_prefill and x.shape[1] > 1
    h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        gate = _proj("bsd,di->bsi", h, lp["w_gate"], aq)
        up = _proj("bsd,di->bsi", h, lp["w_up"], aq)
        down = _proj("bsi,id->bsd", _mlp_act(gate, cfg.act) * up,
                     lp["w_down"], aq)
    return x + down * jnp.asarray(cfg.residual_multiplier, x.dtype)


def _runs(kinds: tuple) -> list:
    """[(kind, first index, count)] of the runs of one kind in ``kinds``."""
    runs = []
    for i, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, i, 1])
    return [tuple(r) for r in runs]


def forward(params: dict, cfg: GraniteHybridConfig, tokens, positions, cache,
            write_index, mask, *, last_only: bool = False,
            stacked_attention_fn=None, scan_kernels: bool = False,
            interpret: bool = False, cache_rows=None):
    """Run the decoder over ``tokens`` [B, S] written at cache slots
    ``write_index ..``; returns (logits [B, S, vocab] float32, state).

    ``positions`` is taken and not read: nothing here encodes a position.
    ``stacked_attention_fn(q, cache, layer_idx)`` is the phase's kernel over
    the stacked cache of the attention layers (llama's); None is the dense
    XLA attention under ``mask`` [B, S, C]. ``scan_kernels`` runs the
    recurrence through ``ops/ssd_scan.py``'s kernels (``interpret``: on the
    CPU), else through their XLA forms. The scan stands where the state
    says: a prefill chunk continues the one before it.

    ``cache_rows`` [B] int32: the tokens are a row piece of a batch whose
    state holds more rows (the engine's prefill, ``Family.
    prefill_piece_tokens``) and row b of them lives at the state's batch
    row ``cache_rows[b]`` — keys, values, convolution tail and recurrent
    state written and read there in place, the state's other rows left as
    they are. A (row, chunk) piece that is all left pad need not run: under
    the pad the mixer's input is zeroed, ``in_proj`` has no bias and
    ``xBC`` is zeroed after the convolution, so tail and state stay the
    zeros they came as."""
    del positions
    res = cfg.residual_multiplier
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype) * jnp.asarray(
            cfg.embedding_multiplier, cfg.dtype)
    # a token under a row's left pad: its query row of the mask is all False
    valid = jnp.any(mask, axis=-1)

    def mamba_layer(x, cache, lp, ffn_lp, slot):
        h = _rmsnorm(x, lp["mixer_norm"], cfg.norm_eps)
        h = jnp.where(valid[..., None], h, jnp.zeros_like(h))
        out, cache = mamba_mixer(h, lp, slot, valid, cache, cfg,
                                  scan_kernels, interpret, cache_rows)
        return _ffn(x + out * jnp.asarray(res, x.dtype), ffn_lp, cfg), cache

    def attention_layer(x, cache, lp, ffn_lp, slot):
        h = _rmsnorm(x, lp["mixer_norm"], cfg.norm_eps)
        out, cache = _attention_mixer(h, lp, slot, mask, cache, write_index,
                                      cfg, stacked_attention_fn, cache_rows)
        return _ffn(x + out * jnp.asarray(res, x.dtype), ffn_lp, cfg), cache

    period = cfg.period
    per = {"mamba": period.count("mamba"),
           "attention": period.count("attention")}
    group = {"mamba": "mamba", "attention": "attn"}
    layer_of = {"mamba": mamba_layer, "attention": attention_layer}

    def one(tree, i):
        """Layer i of a stacked group, read where it is used: the slice
        fuses into the products that consume it. (Handing a period's or a
        run's layers to an inner scan as its own arrays copies them: 6 GB
        read and written a decode step, 15 ms of its 21 at four rows.)"""
        return jax.tree.map(
            lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False),
            tree)

    def period_step(carry, p):
        seen = {"mamba": 0, "attention": 0}
        for kind, first, count in _runs(period):

            def layer(carry, j, kind=kind, first=first, lo=seen[kind]):
                slot = p * per[kind] + lo + j
                return layer_of[kind](
                    *carry, one(params[group[kind]], slot),
                    one(params["layers"], p * len(period) + first + j),
                    slot), None

            if count == 1:
                carry, _ = layer(carry, 0)
            else:
                carry, _ = jax.lax.scan(layer, carry, jnp.arange(count))
            seen[kind] += count
        return carry, None

    (x, cache), _ = jax.lax.scan(
        period_step, (x, cache), jnp.arange(cfg.n_layers // len(period)))
    with jax.named_scope("lm_head"):
        if last_only:
            x = x[:, -1:, :]
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head_logits(x, params, cfg) / cfg.logits_scaling
    return logits, cache


def forward_dense(params: dict, cfg: GraniteHybridConfig,
                  tokens) -> jax.Array:
    """Cache-free causal forward of whole sequences [B, S] with no kernel:
    logits [B, S, vocab] float32. (Keys and values still pass through a
    cache of exactly S slots, the recurrence through a state from zero.)"""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    logits, _ = forward(params, cfg, tokens, positions,
                        init_cache(cfg, B, S), 0, mask)
    return logits


# -- the engine's seam (models/family.py) -------------------------------------


def _forward_kwargs(cfg: GraniteHybridConfig, kernels: bool, interpret: bool):
    if not kernels:
        return {}   # flash=False: dense attention and the scan's XLA forms
    return {"scan_kernels": True, "interpret": interpret}


def _family():
    from .family import Family

    carries_state = (
        "this family's state holds every Mamba layer's recurrent state and "
        "convolution tail beside the keys and values of its few attention "
        "layers")
    return Family(
        name="granite-hybrid", forward=forward, init_cache=init_cache,
        init_params=init_params, kernels_supported=_kernels_supported,
        attention_supported=_attention_supported,
        prefill_attention=_prefill_attention,
        decode_attention=_decode_attention, counts_prefill_blocks=True,
        attention_layers=lambda cfg: cfg.n_attention,
        prefill_counts=prefill_counts,
        # one row of a 2,048-token chunk a piece (PERF.md section 6, PR 51)
        prefill_piece_tokens=2048,
        forward_kwargs=_forward_kwargs, row_record=last_state,
        missing={
            "slot loop": (
                "the slot programs (backend/inflight.py, engine._make_slot_*"
                ", _make_adopt_fn) fill one row at a time and scatter every "
                "leaf of a joined batch's cache on its second axis, as keys "
                "and values; adopting, evicting and filling a row would "
                "have to move a recurrent state they do not carry: "
                + carries_state),
            "prefix cache": (
                "cache/radix.py and cache/store.py slice keys and values "
                "by block at any token; a recurrent state can be resumed "
                "only from a snapshot taken at a boundary, and none is "
                "kept: " + carries_state),
            "mesh": (
                "parallel/sharding.py has no specs for the mixer's "
                "parameters (in_proj's parts, the convolution, out_proj) or "
                "for the recurrent state and the convolution tail"),
            "speculative decoding": (
                "a rejected draft has to roll the recurrent state back to "
                "the last accepted token, and the verify step keeps no "
                "state per position: " + carries_state),
            "long-context backend": (
                "the ring prefill runs models.llama.cache_free_block and "
                "passes keys and values between shards; a recurrence would "
                "have to hand its state from shard to shard in order"),
        },
    )


FAMILY = _family()
