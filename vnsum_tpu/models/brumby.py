"""Brumby family in functional JAX: the Qwen3 dense skeleton with every
attention layer replaced by POWER RETENTION (degree 2), for the one-shot
generation program.

A ninth family behind ``models/family.py``, and the first with no attention
layer at all: what a program carries between steps is a float32 matrix
state a layer, row and KV head, and no keys and values. The skeleton is
``models/llama.py``'s — RMSNorm, per-head QK-norm before rotary
(rotate-half), SwiGLU, the int8 products of ``models/quant.py`` — and the
mixer's recurrence is ``ops/power_retention.py``'s. What this module owns
is the config, the parameters, the state and ``forward``. ``FAMILY`` at the
end is what the engine's seam picks up for a ``BrumbyConfig``.

The equations (``benchmarks/reference_brumby.py`` is the same in plain
float32, in the ATTENTION form), for one layer with input ``x``, ``h =
RMSNorm(x)``, ``s = head_dim^-0.5``:

    q = h W_q [H x d]     k = h W_k [KV x d]     v = h W_v [KV x d]   (no bias)
    q = RMSNorm_head(q; w_qn)   k = RMSNorm_head(k; w_kn)      over a head's d
    q, k = rotary(q, k; theta, rotate-half)
    gamma_t[h] = logsigmoid(h_t W_g[:, h] + b_g[h])   float32, a KV head, <= 0
    query head a reads KV head h = a // (H / KV):
    w_tj  = (s q_t^a . k_j^h)^2 exp(gamma_{j+1}[h] + ... + gamma_t[h])    j <= t
    o_t^a = sum_j w_tj v_j^h / (sum_j w_tj + eps)
    x <- x + concat_a(o_t^a) W_o ;   x <- x + SwiGLU(RMSNorm(x))

computed here in the STATE form — ``S_t = exp(gamma_t) S_{t-1} + phi(k_t)
v_t^T``, the normaliser alike, ``o_t = phi(s q_t)^T S_t / (phi(s q_t)^T z_t +
eps)`` with ``phi(a) . phi(b) = (a . b)^2`` — chunk by chunk in prefill and
token by token in decode (``ops/power_retention.py`` has the layout of
``phi``, of the state and of the unpacked normaliser, and the chunked form).

- **Left pads.** The engine pads rows on the left and a recurrence runs
  through pads. At a pad position (``mask`` shows it: its query row is all
  False) ``h`` is zeroed before the projections, which have no bias, so
  ``k = v = 0`` there (a zero vector stays zero through QK-norm and
  rotary): state and normaliser are exactly zero when the row's first real
  token arrives, whatever the pad's length and however many prefill chunks
  it spans, and a pad's output is ``0 / (0 + eps)``.

State a program carries (``init_cache``), and nothing else:

- ``ret``: the state, ``[L, B, KV, T, d, d]`` float32 — ``phi``'s tile, the
  value channel on the sublanes, ``phi``'s lane on the lanes (T = d / 2 + 1
  tiles of d lanes: 8,320 lanes for the 8,256 distinct products at d = 128);
- ``norm``: the normaliser unpacked, ``[L, B, KV, d, d]`` float32.

Neither grows with the row. At the published widths a row and layer hold
34.6 MB: as much as the bfloat16 keys and values of 8,448 slots.

The identical layers are ONE ``lax.scan``; a layer's weights are read where
they are used.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from .llama import (
    _apply_rope,
    _embed_lookup,
    _lm_head_logits,
    _mlp_act,
    _proj,
    _rmsnorm,
)


@dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151_936
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    intermediate: int = 17_408
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 32_768
    tie_embeddings: bool = False
    act: str = "silu"
    # the power of the score: 2 is what ops/power_retention.py expands
    retention_degree: int = 2
    # tokens between two reads of the state in prefill
    retention_chunk_size: int = 256
    retention_eps: float = 1e-6
    # W8A8 on multi-token forwards, as LlamaConfig's; the engine sets it
    w8a8_prefill: bool = False
    dtype: Any = field(default=jnp.bfloat16)
    # the state's and the normaliser's type: float32 as assumed; anything
    # narrower is a precision cut a parity check has to see
    state_dtype: Any = field(default=jnp.float32)

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        if self.retention_degree != 2:
            raise ValueError(
                "degree 2 is what this family builds (phi's products of "
                f"pairs); retention_degree={self.retention_degree}")
        if self.head_dim % 2:
            raise ValueError("rotary and phi's tiles need an even head_dim")

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def score_scale(self) -> float:
        return self.head_dim ** -0.5


def brumby_14b(**kw) -> BrumbyConfig:
    """manifestai/Brumby-14B-Base ``config.json``, uncut."""
    return BrumbyConfig(**kw)


def tiny_brumby(**kw) -> BrumbyConfig:
    """Small config for hermetic CPU tests: three layers, 4 query heads on
    2 KV heads of 16 (9 tiles of phi), retention chunks of 8."""
    base = dict(
        vocab_size=384, dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=16, intermediate=128, retention_chunk_size=8,
        max_seq_len=256, dtype=jnp.float32,
    )
    base.update(kw)
    return BrumbyConfig(**base)


# -- parameters and state -----------------------------------------------------


def float_leaves(key: jax.Array, cfg: BrumbyConfig) -> dict:
    """{group: {leaf: array}} of the leaves ``models/quant.py``'s direct
    int8 init must not draw its own way: the gate's projection and bias,
    float32 as the log-decay they give, and the QK-norm's weights."""
    kg, kq, kk = jax.random.split(key, 3)
    shape = (cfg.n_layers, cfg.head_dim)
    return {"layers": {**_gate_leaves(kg, cfg),
                       "q_norm": _around_one(kq, shape, cfg.dtype),
                       "k_norm": _around_one(kk, shape, cfg.dtype)}}


def _around_one(key, shape, dtype):
    """U[0.5, 1.5]: a QK-norm weight under which norm and rotary do not
    commute."""
    return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5).astype(dtype)


def _gate_leaves(key: jax.Array, cfg: BrumbyConfig) -> dict:
    """``W_g`` N(0, 0.02) and ``b_g`` U[2, 9] a KV head, STRATIFIED: a
    layer's heads take one draw from each of KV equal parts of [2, 9], in a
    random order, so that every layer's decays spread from a head that
    forgets in ~8 tokens (sigmoid(2) = 0.88) to one that keeps ~8,000
    (sigmoid(9) = 0.9999) whatever the seed."""
    kw, kb, kp = jax.random.split(key, 3)
    L, D, KV = cfg.n_layers, cfg.dim, cfg.n_kv_heads
    part = (jnp.arange(KV) + jax.random.uniform(kb, (L, KV))) / KV
    order = jax.vmap(lambda k: jax.random.permutation(k, KV))(
        jax.random.split(kp, L))
    return {
        "w_gate_ret": jax.random.normal(kw, (L, D, KV), jnp.float32) * 0.02,
        "b_gate_ret": 2.0 + 7.0 * jnp.take_along_axis(part, order, axis=1),
    }


def init_params(key: jax.Array, cfg: BrumbyConfig) -> dict:
    """Random init; layer weights are stacked on a leading L dim. The
    QK-norm's weights are drawn from U[0.5, 1.5] so that norm and rotary do
    not commute."""
    L, D, H, KV, hd, F = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, cfg.intermediate)
    keys = iter(jax.random.split(key, 16))

    def norm(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale
                ).astype(cfg.dtype)

    return {
        "embed": norm((cfg.vocab_size, D)),
        "layers": {
            "mixer_norm": jnp.ones((L, D), cfg.dtype),
            "wq": norm((L, D, H, hd)), "wk": norm((L, D, KV, hd)),
            "wv": norm((L, D, KV, hd)), "wo": norm((L, H, hd, D)),
            "q_norm": _around_one(next(keys), (L, hd), cfg.dtype),
            "k_norm": _around_one(next(keys), (L, hd), cfg.dtype),
            **_gate_leaves(next(keys), cfg),
            "mlp_norm": jnp.ones((L, D), cfg.dtype),
            "w_gate": norm((L, D, F)), "w_up": norm((L, D, F)),
            "w_down": norm((L, F, D)),
        },
        "final_norm": jnp.ones((D,), cfg.dtype),
        "lm_head": norm((D, cfg.vocab_size)),
    }


def init_cache(cfg: BrumbyConfig, batch: int, cache_len: int, *,
               quantized: bool = False) -> dict:
    """What a program carries: every layer's state and unpacked normaliser,
    and no keys and values — ``cache_len`` sizes nothing."""
    from ..ops.power_retention import n_tiles

    if quantized:
        raise ValueError("a retention state has no int8 form")
    del cache_len
    L, KV, d = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    return {
        "ret": jnp.zeros((L, batch, KV, n_tiles(d), d, d), cfg.state_dtype),
        "norm": jnp.zeros((L, batch, KV, d, d), cfg.state_dtype),
    }


# -- the mixer and forward ----------------------------------------------------


def _retention_mixer(h, lp, slot, rope, valid, cache, cfg: BrumbyConfig,
                     scan_kernels: bool, interpret: bool, cache_rows=None):
    """Power retention over h [B, S, D] (normed, zero under the pad) at
    layer ``slot`` of the state. ``cache_rows`` [B]: h is a row piece and
    row b's state and normaliser live at the state's batch row
    ``cache_rows[b]``, read and written there in place. The
    ``jax.named_scope`` names are metadata a device trace is read by (README
    "Device time by layer")."""
    # imported on use, as llama's kernels: the other families' paths never
    # load it
    from ..ops import power_retention as pr

    S = h.shape[1]
    aq = cfg.w8a8_prefill and S > 1
    f32 = jnp.float32
    how = dict(scale=cfg.score_scale, eps=cfg.retention_eps)
    kernels = scan_kernels and cache["ret"].dtype == f32
    with jax.named_scope("ret_in"):
        q = _proj("bsd,dhk->bshk", h, lp["wq"], aq)
        k = _proj("bsd,dhk->bshk", h, lp["wk"], aq)
        v = _proj("bsd,dhk->bshk", h, lp["wv"], aq)
        q = _rmsnorm(q, lp["q_norm"], cfg.norm_eps)
        k = _rmsnorm(k, lp["k_norm"], cfg.norm_eps)
        q = _apply_rope(q, *rope)
        k = _apply_rope(k, *rope)
        # the log-decay a KV head, float32 from the normed stream
        gamma = jax.nn.log_sigmoid(
            jnp.einsum("bsd,dh->bsh", h.astype(f32), lp["w_gate_ret"],
                       precision=jax.lax.Precision.HIGHEST)
            + lp["b_gate_ret"])
    with jax.named_scope("ret_update" if S == 1 else "ret_scan"):
        state, normaliser = cache["ret"], cache["norm"]
        if kernels:
            if S == 1:
                o, state, normaliser = pr.retention_decode_update(
                    q[:, 0], k[:, 0], v[:, 0], gamma[:, 0], state,
                    normaliser, slot, interpret=interpret, **how)
                o = o[:, None]
            else:
                # left padding: a row's pads are its first positions
                pads = S - jnp.sum(valid, axis=-1, dtype=jnp.int32)
                o, state, normaliser = pr.retention_prefill_scan(
                    q, k, v, gamma, state, normaliser, slot, pads,
                    cache_rows, chunk=cfg.retention_chunk_size,
                    interpret=interpret, **how)
        else:
            mine = [jax.lax.dynamic_index_in_dim(a, slot, 0, False).astype(
                f32) for a in (state, normaliser)]
            if S == 1:
                o, *mine = pr.retention_step_xla(
                    q[:, 0], k[:, 0], v[:, 0], gamma[:, 0], *mine, **how)
                o = o[:, None]
            else:
                o, *mine = pr.retention_chunked_xla(
                    q, k, v, gamma, *mine, cfg.retention_chunk_size,
                    cache_rows, **how)
            state, normaliser = (
                jax.lax.dynamic_update_index_in_dim(
                    a, new.astype(a.dtype), slot, 0)
                for a, new in zip((state, normaliser), mine))
    with jax.named_scope("ret_out"):
        out = _proj("bshk,hkd->bsd", o.astype(h.dtype), lp["wo"], aq)
    return out, {"ret": state, "norm": normaliser}


def forward(params: dict, cfg: BrumbyConfig, tokens, positions, cache,
            write_index, mask, *, last_only: bool = False,
            stacked_attention_fn=None, scan_kernels: bool = False,
            interpret: bool = False, cache_rows=None):
    """Run the decoder over ``tokens`` [B, S]; returns (logits [B, S, vocab]
    float32, state).

    ``write_index`` is taken and not read: nothing is written at a slot.
    ``stacked_attention_fn`` is taken and must be None: no layer attends
    over a cache. ``mask`` [B, S, C] is read for one thing, which tokens lie
    under a row's left pad. ``scan_kernels`` runs the recurrence through
    ``ops/power_retention.py``'s kernels (``interpret``: on the CPU), else
    through their XLA forms. The scan stands where the state says: a
    prefill chunk continues the one before it.

    ``cache_rows`` [B] int32: the tokens are a row piece of a batch whose
    state holds more rows (the engine's prefill, ``Family.
    prefill_piece_tokens``) and row b of them lives at the state's batch
    row ``cache_rows[b]`` — state and normaliser written and read there in
    place, the state's other rows left as they are. A (row, chunk) piece
    that is all left pad need not run: under the pad the mixer's input is
    zeroed and no projection has a bias, so state and normaliser stay the
    zeros they came as."""
    del write_index
    if stacked_attention_fn is not None:
        raise ValueError("no layer of this family attends over a cache")
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    with jax.named_scope("ret_in"):   # the rotary table every layer reads
        half = cfg.head_dim // 2
        inv = 1.0 / (cfg.rope_theta ** (
            jnp.arange(0, half, dtype=jnp.float32) / half))
        angles = positions[..., None].astype(jnp.float32) * inv
        rope = (jnp.cos(angles), jnp.sin(angles))
    # a token under a row's left pad: its query row of the mask is all False
    valid = jnp.any(mask, axis=-1)

    def layer(carry, l):
        x, cache = carry
        # layer l's weights, read where they are used: the slice fuses into
        # the products that consume it
        lp = jax.tree.map(
            lambda w: jax.lax.dynamic_index_in_dim(w, l, 0, keepdims=False),
            params["layers"])
        h = _rmsnorm(x, lp["mixer_norm"], cfg.norm_eps)
        h = jnp.where(valid[..., None], h, jnp.zeros_like(h))
        out, cache = _retention_mixer(h, lp, l, rope, valid, cache, cfg,
                                      scan_kernels, interpret, cache_rows)
        x = x + out
        aq = cfg.w8a8_prefill and x.shape[1] > 1
        with jax.named_scope("mlp"):
            h = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
            gate = _proj("bsd,di->bsi", h, lp["w_gate"], aq)
            up = _proj("bsd,di->bsi", h, lp["w_up"], aq)
            x = x + _proj("bsi,id->bsd", _mlp_act(gate, cfg.act) * up,
                          lp["w_down"], aq)
        return (x, cache), None

    (x, cache), _ = jax.lax.scan(layer, (x, cache), jnp.arange(cfg.n_layers))
    with jax.named_scope("lm_head"):
        if last_only:
            x = x[:, -1:, :]
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head_logits(x, params, cfg)
    return logits, cache


def forward_dense(params: dict, cfg: BrumbyConfig, tokens) -> jax.Array:
    """Cache-free causal forward of whole sequences [B, S] with no kernel:
    logits [B, S, vocab] float32 (the recurrence through a state from
    zero, in its chunked XLA form)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mask = jnp.ones((B, S, 1), bool)
    logits, _ = forward(params, cfg, tokens, positions,
                        init_cache(cfg, B, S), 0, mask)
    return logits


# -- the engine's seam (models/family.py) -------------------------------------


def prefill_counts(cfg: BrumbyConfig, pad_lens, spans, cache_len=None) -> dict:
    """What one dispatch's prefill saw, from the pads it was packed with:
    ``retention_tokens_real`` (real prompt tokens) and
    ``retention_tokens_computed`` (tokens of the chunks
    ``retention_prefill_scan`` did not skip: a chunk wholly under a row's
    pad is skipped, whether or not its row piece ran), both x layers.
    ``spans`` are the prefill's query spans [lo, hi) over the bucket."""
    import numpy as np

    from ..ops.power_retention import retention_tokens_computed

    pads = np.asarray(pad_lens, np.int64)
    real = computed = 0
    for lo, hi in spans:
        inside = np.clip(pads - lo, 0, hi - lo)   # pads among these tokens
        real += int(((hi - lo) - inside).sum())
        computed += retention_tokens_computed(
            inside, hi - lo, cfg.retention_chunk_size)
    return {"retention_tokens_real": real * cfg.n_layers,
            "retention_tokens_computed": computed * cfg.n_layers}


def row_record(cache: dict) -> dict:
    """What a parity check may see of the position just scored: the first
    and the last layer's state [2, B, KV, T, d, d] and normaliser [2, B, KV,
    d, d] — the first carries one product's rounding and the scan's own
    arithmetic, the last everything before it."""
    return {"state": jnp.stack([cache["ret"][0], cache["ret"][-1]]),
            "normaliser": jnp.stack([cache["norm"][0], cache["norm"][-1]])}


def _kernels_supported(cfg: BrumbyConfig, interpret: bool) -> bool:
    # a head is one lane tile of the layer's [B, S, H * d] arrays and
    # phi's tiles are lane rotations of it; interpreted, any
    return interpret or cfg.head_dim == 128


def _forward_kwargs(cfg: BrumbyConfig, kernels: bool, interpret: bool):
    if not kernels:
        return {}   # flash=False: the recurrence's XLA forms
    return {"scan_kernels": True, "interpret": interpret}


def _family():
    from .family import Family

    carries_state = (
        "this family's state is every layer's float32 retention state "
        "[KV heads, 65 tiles, 128, 128] and normaliser [KV heads, 128, "
        "128] a row (34.6 MB a row and layer), and no keys and values")
    return Family(
        name="brumby", forward=forward, init_cache=init_cache,
        init_params=init_params, kernels_supported=_kernels_supported,
        # no attention kernel to choose: the engine builds none for a family
        # without attention layers, whatever this says
        attention_supported=lambda cfg, S, C: (True, True),
        prefill_attention=None, decode_attention=None,
        int8_cache=False, attention_layers=lambda cfg: 0,
        prefill_counts=prefill_counts,
        # one row of a 2,048-token chunk a piece, as the dense skeleton's
        # (models/llama.py): 4,096 operations a weight byte, and the scan's
        # grid has 8 KV heads a row to spread
        prefill_piece_tokens=2048,
        forward_kwargs=_forward_kwargs, row_record=row_record,
        missing={
            "slot loop": (
                "the slot programs (backend/inflight.py, engine._make_slot_*"
                ", _make_adopt_fn) fill one row at a time and scatter every "
                "leaf of a joined batch's cache on its second axis as "
                "[L, B, KV, C, hd] keys and values; adopting, evicting and "
                "filling a row would have to move a matrix state they do "
                "not carry, and there are no keys and values to scatter: "
                + carries_state),
            "prefix cache": (
                "cache/radix.py and cache/store.py slice [N, L, KV, BLK, hd] "
                "keys and values by block at any token; a retention state "
                "resumes only from a snapshot of the state and the "
                "normaliser taken at the block's boundary (34.6 MB a layer "
                "where the block's keys and values are 0.26), and none is "
                "kept: " + carries_state),
            "mesh": (
                "parallel/sharding.py has no specs for the gate's "
                "projection and bias or for a state and a normaliser "
                "sharded by KV head, and shards a cache of keys and values "
                "this family does not have"),
            "speculative decoding": (
                "a rejected draft has to roll every layer's state and "
                "normaliser back to the last accepted token, and the verify "
                "step (backend/engine.py _make_spec_fn) keeps no state per "
                "position, writes per-row cache slots and runs the GQA "
                "verify kernel of ops/decode_attention.py over keys and "
                "values: " + carries_state),
            "long-context backend": (
                "the ring prefill (backend/long_context.py) runs "
                "models.llama.cache_free_block and passes keys and values "
                "per KV head between shards; a retention layer would have "
                "to hand its state and normaliser from shard to shard in "
                "order, decayed by the shard's whole gate sum"),
        },
    )


FAMILY = _family()
