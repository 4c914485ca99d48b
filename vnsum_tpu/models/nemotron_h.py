"""Nemotron-H family in functional JAX: a stack whose every layer is ONE
mixer — a Mamba-2 mixer, sparse non-gated relu2 experts with a shared one,
or position-free grouped-query attention — for the one-shot generation
program.

A sixth family behind ``models/family.py``, and the first whose program
state holds a recurrent state AND expert counters AND keys and values. Its
Mamba-2 mixer is ``models/mamba_mixer.py``'s (shared with
``models/granite_hybrid.py``; eight groups of B and C here), its expert
layer ``models/experts.py``'s in the two-matrix form, its attention layers
``models/llama.py``'s — the ``[L, B, KV, C, hd]`` cache over those layers
only, ``_write_kv``, ``_cache_attention`` and the two flash kernels at 16
query heads a KV head. What it owns is the config, the parameters, the
state and ``forward``; its routing rule is ``models/experts.py``'s
``sigmoid_route`` (``route`` here), which ``models/lfm2.py`` calls too.
``FAMILY`` at the end is what the engine's seam picks up for a
``NemotronHConfig``.

The equations (``benchmarks/reference_nemotron_h.py`` is the same in plain
float32, the recurrence token by token):

- **Stack.** ``x0 = E[token]``. Layer ``l`` of kind ``layer_pattern[l]``
  (``M`` Mamba-2, ``E`` experts, ``*`` attention; published 23 / 23 / 6 of
  52, not periodic): ``x_{l+1} = x_l + mixer_l(RMSNorm_l(x_l))``. No
  feed-forward follows a mixer: the ``E`` layers ARE the feed-forward.
  Final ``RMSNorm``, logits ``h W_head`` (untied). No multipliers.
- **M.** ``models/mamba_mixer.py`` at ``mamba_n_groups`` groups: head ``h``
  reads B and C of group ``h // (heads / groups)``, and the gated RMSNorm
  runs over each group's run of the inner width.
- **\\*.** q ``[H, hd]``, k, v ``[KV, hd]``, no bias, NO position encoding
  (the Mamba layers carry position; ``rope_theta`` is published and read by
  nothing), causal GQA ``softmax(q k^T / sqrt(hd)) v``, ``a W_o``.
- **E.** ``s = sigmoid(h W_r)`` in float32 over all routed experts;
  ``ids = top_k(s + b)`` with ``b`` the ``e_score_correction_bias``, which
  steers the CHOICE and never the weight; ``w = s[ids] / sum(s[ids]) *
  routed_scaling_factor``; ``out = sum_e w_e W_down_e relu(W_up_e h)^2 +
  W_down_s relu(W_up_s h)^2`` (the shared expert, every token). No gate
  matrix anywhere. ``n_group`` 1 / ``topk_group`` 1 are published: the
  group limit keeps every expert, and no group logic is built.
- **Left pads.** As the shared mixer says; a pad position is routed nowhere
  and counted nowhere (``expert_layer``).

State a program carries (``init_cache``), side by side: llama's cache over
the attention layers alone; ``conv`` and ``ssm`` of the Mamba layers
(``init_mamba_state``); the sparse layers' expert counters and picks
(``init_expert_state`` with ``decode_touched``).

The stack is unrolled: the published pattern has no period, and each kind's
parameters are stacked on a leading dim of their own (``mamba``, ``attn``,
``layers`` for the sparse layers), read a layer at a time where they are
used.
"""
from __future__ import annotations

import functools
import types
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from .experts import (
    _EXPERT_PIECE_TOKENS,
    UNGATED_EXPERT_LEAVES,
    by_rows,
    counters,
    expert_layer,
    grouped_experts,
    init_expert_state,
    sigmoid_route as route,   # the rule, shared with models/lfm2.py
)
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
from .mamba_mixer import (
    init_mamba_params,
    init_mamba_state,
    init_mamba_vectors,
    last_state,
    mamba_mixer,
    prefill_counts,
)

# nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 hybrid_override_pattern
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
_GROUP = {"M": "mamba", "E": "layers", "*": "attn"}


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131_072
    dim: int = 2688
    n_layers: int = 52
    # one letter a layer: M Mamba-2, E experts, * attention; a model cut in
    # depth takes the leading ``n_layers`` of it
    layer_pattern: str = PUBLISHED_PATTERN
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 8
    mamba_d_conv: int = 4
    # how the scan is computed, not another model: the kernel's chunk
    mamba_chunk_size: int = 128
    # published ``intermediate_size``, and read by nothing: no layer has a
    # dense feed-forward (the same width as an expert's under another name)
    intermediate: int = 1856
    moe_intermediate: int = 1856
    shared_intermediate: int = 3712
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    # what ``models/experts.py`` asks of a config: the experts this chip
    # holds of each sparse layer (None: all of them) from ``expert_offset``
    n_held: int | None = None
    expert_offset: int = 0
    norm_eps: float = 1e-5
    # published, and read by nothing: the attention layers encode no position
    rope_theta: float = 10_000.0
    max_seq_len: int = 262_144
    tie_embeddings: bool = False
    # the experts' and the shared expert's activation: no gate, relu squared
    act: str = "relu2"
    # W8A8 on multi-token forwards, as LlamaConfig's; the engine sets it
    w8a8_prefill: bool = False
    dtype: Any = field(default=jnp.bfloat16)
    # the recurrent state's type: float32 as assumed; anything narrower is
    # a precision cut a parity check has to see
    state_dtype: Any = field(default=jnp.float32)

    def __post_init__(self):
        pattern = self.layer_pattern[:self.n_layers]
        if len(pattern) != self.n_layers or set(pattern) - set(_GROUP):
            raise ValueError(
                f"layer_pattern needs {self.n_layers} letters of M, E and "
                f"*, got {self.layer_pattern!r}")
        object.__setattr__(self, "layer_pattern", pattern)
        if self.n_held is None:
            object.__setattr__(self, "n_held", self.n_routed_experts)
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_groups must divide mamba_n_heads")
        if self.expert_offset + self.n_held > self.n_routed_experts:
            raise ValueError("the experts held end past the last expert")

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def moe_stored(self) -> int:
        """The width the routed experts are STORED at: ``moe_intermediate``
        rounded up to whole lanes (1,856 -> 1,920), the columns of ``we_up``
        and the rows of ``we_down`` past the expert's own width zero.
        relu(0)^2 = 0 and a zero row adds nothing, so the mathematics is at
        ``moe_intermediate``; a stack whose last dim is not whole lanes is
        copied whole into a padded layout at every call of the product
        kernel (4.9 GB of temporaries at the published widths)."""
        return -(-self.moe_intermediate // 128) * 128

    @property
    def n_mamba(self) -> int:
        return self.layer_pattern.count("M")

    @property
    def n_sparse(self) -> int:
        return self.layer_pattern.count("E")

    @property
    def n_attention(self) -> int:
        return self.layer_pattern.count("*")


def nemotron_3_nano_30b_a3b(**kw) -> NemotronHConfig:
    """nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``, uncut."""
    return NemotronHConfig(**kw)


def tiny_nemotron_h(**kw) -> NemotronHConfig:
    """Small config for hermetic CPU tests: all three kinds in an order with
    no period, 2 groups of B and C, an expert width that is not whole lanes
    of anything (24), 4 query heads a KV head, 16 experts top-4."""
    base = dict(
        vocab_size=384, dim=64, n_layers=9, layer_pattern="MEM*EMEME",
        n_heads=8, n_kv_heads=2, head_dim=16, mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
        mamba_chunk_size=8, moe_intermediate=24, shared_intermediate=48,
        n_routed_experts=16, num_experts_per_tok=4, max_seq_len=256,
        dtype=jnp.float32,
    )
    base.update(kw)
    return NemotronHConfig(**base)


# -- parameters and state -----------------------------------------------------

# how far the seeded ``e_score_correction_bias`` spreads: enough to move
# more than a tenth of a seeded router's picks against a zero bias, so that
# "the bias left out" and "the bias in the weight" are faults a check sees
_BIAS_SPREAD = 0.05


def init_router(key: jax.Array, cfg: NemotronHConfig) -> dict:
    """The router and its correction bias, float32 whatever the weights'
    type: the scores decide a top-k."""
    kr, kb = jax.random.split(key)
    Ls, E = cfg.n_sparse, cfg.n_routed_experts
    return {
        "router": jax.random.normal(kr, (Ls, cfg.dim, E), jnp.float32) * 0.02,
        "router_bias": jax.random.normal(kb, (Ls, E), jnp.float32)
        * _BIAS_SPREAD,
    }


def float_leaves(key: jax.Array, cfg: NemotronHConfig) -> dict:
    """{group: {leaf: array}} of the leaves ``models/quant.py``'s direct
    int8 init must not draw its own way: the scan's sensitive leaves, the
    router and its bias."""
    km, kr = jax.random.split(key)
    return {"mamba": init_mamba_vectors(km, cfg),
            "layers": init_router(kr, cfg)}


def zero_padding(groups: dict, cfg: NemotronHConfig) -> dict:
    """``groups`` with the routed experts' padding zeroed: the columns of
    ``we_up`` and the rows of ``we_down`` from ``moe_intermediate`` to
    ``moe_stored``, plain or int8 ``{"q", "s"}`` leaves
    (``models/quant.py``'s direct int8 init draws every stored value and
    calls this)."""
    real = jnp.arange(cfg.moe_stored) < cfg.moe_intermediate

    def zeroed(leaf, mask):
        if isinstance(leaf, dict):
            return dict(leaf, q=jnp.where(mask, leaf["q"], 0))
        return jnp.where(mask, leaf, 0)

    layers = groups["layers"]
    return dict(groups, layers=dict(
        layers, we_up=zeroed(layers["we_up"], real),
        we_down=zeroed(layers["we_down"], real[:, None])))


def init_params(key: jax.Array, cfg: NemotronHConfig) -> dict:
    """Random init: each kind's layers stacked on a leading dim of their
    own — ``mamba``, ``attn`` and, for the sparse layers, ``layers``."""
    D, La, Ls = cfg.dim, cfg.n_attention, cfg.n_sparse
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    F, Fs, E = cfg.moe_stored, cfg.shared_intermediate, cfg.n_held
    keys = iter(jax.random.split(key, 24))

    def norm(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale
                ).astype(cfg.dtype)

    return zero_padding({
        "embed": norm((cfg.vocab_size, D)),
        "mamba": init_mamba_params(norm, next(keys), cfg),
        "attn": {
            "mixer_norm": jnp.ones((La, D), cfg.dtype),
            "wq": norm((La, D, H, hd)), "wk": norm((La, D, KV, hd)),
            "wv": norm((La, D, KV, hd)), "wo": norm((La, H, hd, D)),
        },
        "layers": {
            "mixer_norm": jnp.ones((Ls, D), cfg.dtype),
            **init_router(next(keys), cfg),
            "we_up": norm((Ls, E, D, F)), "we_down": norm((Ls, E, F, D)),
            "ws_up": norm((Ls, D, Fs)), "ws_down": norm((Ls, Fs, D)),
        },
        "final_norm": jnp.ones((D,), cfg.dtype),
        "lm_head": norm((D, cfg.vocab_size)),
    }, cfg)


def init_cache(cfg: NemotronHConfig, batch: int, cache_len: int, *,
               quantized: bool = False) -> dict:
    """What a program carries: llama's KV cache over the attention layers
    alone, every Mamba layer's convolution tail and recurrent state, the
    sparse layers' expert counters and picks."""
    attention = types.SimpleNamespace(
        n_layers=cfg.n_attention, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, dtype=cfg.dtype)
    return {
        **init_kv_cache(attention, batch, cache_len, quantized=quantized),
        **init_mamba_state(cfg, batch),
        **init_expert_state(cfg.n_sparse, cfg.n_held, batch,
                            cfg.num_experts_per_tok, decode_touched=True),
    }


# -- the mixers and forward ---------------------------------------------------


def _attention_mixer(h, lp, slot, mask, cache, write_index,
                     cfg: NemotronHConfig, stacked_attention_fn):
    """The ``jax.named_scope`` names are metadata a device trace is read by
    (README "Device time by layer")."""
    aq = cfg.w8a8_prefill and h.shape[1] > 1
    with jax.named_scope("qkv"):
        q = _proj("bsd,dhk->bshk", h, lp["wq"], aq)
        k = _proj("bsd,dhk->bshk", h, lp["wk"], aq)
        v = _proj("bsd,dhk->bshk", h, lp["wv"], aq)
    cache = _write_kv(cache, k, v, slot, write_index)
    attn = _cache_attention(q, cache, slot, mask, cfg.q_per_kv, None,
                            stacked_attention_fn)
    with jax.named_scope("attn_out"):
        return _proj("bshk,hkd->bsd", attn, lp["wo"], aq), cache


def _expert_mixer(h, lp, experts, slot, valid, cache, cfg: NemotronHConfig,
                  experts_fn):
    """The routed experts (``models/experts.py``, under this family's
    routing rule) + the shared expert over h [B, S, D], and the counters."""
    B, S, D = h.shape
    aq = cfg.w8a8_prefill and S > 1
    flat = h.reshape(B * S, D)

    def picks():
        return route(
            jnp.einsum("td,de->te", flat.astype(jnp.float32),
                       lp["router"].astype(jnp.float32)),
            lp["router_bias"].astype(jnp.float32), cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)

    routed, cache = expert_layer(flat, picks, valid, experts, slot, cache,
                                 cfg, experts_fn, rows=B)

    def shared(h):
        with jax.named_scope("shared_expert"):
            up = _proj("bsd,di->bsi", h, lp["ws_up"], aq)
            return _proj("bsi,id->bsd", _mlp_act(up, cfg.act),
                         lp["ws_down"], aq)

    # a few rows at a time: 3,712 wide over a chunk of 24 rows in float32
    # is most of a gigabyte
    return (routed.reshape(B, S, D).astype(h.dtype)
            + by_rows(shared, h, _EXPERT_PIECE_TOKENS)), cache


def forward(params: dict, cfg: NemotronHConfig, tokens, positions, cache,
            write_index, mask, *, last_only: bool = False,
            stacked_attention_fn=None, scan_kernels: bool = False,
            interpret: bool = False, experts_fn=None):
    """Run the decoder over ``tokens`` [B, S] written at cache slots
    ``write_index ..``; returns (logits [B, S, vocab] float32, state).

    ``positions`` is taken and not read: nothing here encodes a position.
    ``stacked_attention_fn(q, cache, layer_idx)`` is the phase's kernel over
    the stacked cache of the attention layers (llama's); None is the dense
    XLA attention under ``mask`` [B, S, C]. ``scan_kernels`` runs the
    recurrence through ``ops/ssd_scan.py``'s kernels (``interpret``: on the
    CPU), else through their XLA forms. ``experts_fn(x, local, weights,
    experts, slot)`` is the routed experts' product (``grouped_experts``);
    None is ``dense_experts``."""
    del positions
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    # a token under a row's left pad: its query row of the mask is all False
    valid = jnp.any(mask, axis=-1)
    # the experts stay out of the layers' slices: the grouped product reads
    # the stack in place, by the sparse layer's index
    experts = {n: params["layers"][n] for n in UNGATED_EXPERT_LEAVES}
    groups = dict(params, layers={
        n: w for n, w in params["layers"].items()
        if n not in UNGATED_EXPERT_LEAVES})
    seen = dict.fromkeys(_GROUP, 0)
    for kind in cfg.layer_pattern:
        slot = seen[kind]
        seen[kind] += 1
        lp = jax.tree.map(lambda w: w[slot], groups[_GROUP[kind]])
        h = _rmsnorm(x, lp["mixer_norm"], cfg.norm_eps)
        if kind == "M":
            h = jnp.where(valid[..., None], h, jnp.zeros_like(h))
            out, cache = mamba_mixer(h, lp, slot, valid, cache, cfg,
                                     scan_kernels, interpret)
        elif kind == "*":
            out, cache = _attention_mixer(h, lp, slot, mask, cache,
                                          write_index, cfg,
                                          stacked_attention_fn)
        else:
            out, cache = _expert_mixer(h, lp, experts, slot, valid, cache,
                                       cfg, experts_fn)
        x = x + out.astype(x.dtype)
    with jax.named_scope("lm_head"):
        if last_only:
            x = x[:, -1:, :]
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head_logits(x, params, cfg)
    return logits, cache


def forward_dense(params: dict, cfg: NemotronHConfig, tokens) -> jax.Array:
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


def row_record(cache: dict) -> dict:
    """What a parity check may see of the position just scored: the first
    and the last Mamba layer's recurrent state (``last_state``) and the
    routers' picks, [sparse layers, B, k]."""
    return {"ssm": last_state(cache), "picks": cache["picks"]}


def _forward_kwargs(cfg: NemotronHConfig, kernels: bool, interpret: bool):
    if not kernels:
        # flash=False: dense attention, the scan's XLA forms, dense_experts
        return {}
    return {"scan_kernels": True, "interpret": interpret,
            "experts_fn": functools.partial(
                grouped_experts, cfg=cfg, interpret=interpret)}


def _family():
    from .family import Family

    carries_state = (
        "this family's state holds every Mamba layer's recurrent state and "
        "convolution tail and the sparse layers' expert counters and picks "
        "beside the keys and values of its few attention layers")
    return Family(
        name="nemotron-h", forward=forward, init_cache=init_cache,
        init_params=init_params, kernels_supported=_kernels_supported,
        attention_supported=_attention_supported,
        prefill_attention=_prefill_attention,
        decode_attention=_decode_attention, counts_prefill_blocks=True,
        attention_layers=lambda cfg: cfg.n_attention,
        prefill_counts=prefill_counts, forward_kwargs=_forward_kwargs,
        counters=counters, row_record=row_record,
        missing={
            "slot loop": (
                "the slot programs (backend/inflight.py, engine._make_slot_*"
                ", _make_adopt_fn) fill one row at a time, scatter every "
                "leaf of a joined batch's cache on its second axis, as keys "
                "and values, and return no counters; adopting, evicting and "
                "filling a row would have to move a recurrent state they do "
                "not carry: " + carries_state),
            "prefix cache": (
                "cache/radix.py and cache/store.py slice keys and values "
                "by block at any token and the resume program returns the "
                "final cache in the counters' place; a recurrent state can "
                "be resumed only from a snapshot taken at a boundary, and "
                "none is kept: " + carries_state),
            "mesh": (
                "parallel/sharding.py has no specs for the mixer's "
                "parameters (in_proj's parts, the convolution, out_proj), "
                "the router, its bias and the stacked two-matrix experts, "
                "for the recurrent state and the convolution tail, no "
                "expert axis and no exchange of the experts' partial sums"),
            "speculative decoding": (
                "a rejected draft has to roll the recurrent state back to "
                "the last accepted token, and the verify step keeps no "
                "state per position and hands a KV cache alone from step to "
                "step: " + carries_state),
            "long-context backend": (
                "the ring prefill runs models.llama.cache_free_block and "
                "passes keys and values between shards; a recurrence would "
                "have to hand its state from shard to shard in order, and "
                "the block has no expert layer"),
        },
    )


FAMILY = _family()
