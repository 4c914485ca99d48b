"""Ling-3.0-flash family (``bailing_hybrid``, the language model) in
functional JAX: Kimi-Delta-Attention layers — a float32 MATRIX state a head,
updated by a gated delta rule — beside a few latent-attention (MLA) layers
with NO compressed query, each followed by a feed-forward — dense on the
leading layers, 512 sparse experts chosen by group-limited sigmoid score +
bias with a shared expert after them — for the one-shot generation program.

An eighth family behind ``models/family.py``, and the first that carries
THREE kinds of state beside its expert counters: a bf16 latent cache for
the MLA layers alone, a float32 ``[heads, d_v, d_k]`` matrix a row for every
KDA layer, and a three-token convolution tail for the q, k and v of those
layers. Its latent attention is ``models/deepseek.py``'s (``dense_attention``
/ ``prefill_attention`` / ``decode_attention`` over ``ops/mla_attention.py``,
taken at 32 heads with the layer's normed input in the compressed query's
place, so ``wq_b`` is the whole query projection), its expert layer
``models/experts.py``'s under that module's ``sigmoid_group_route``, its
convolution ``models/mamba_mixer.py``'s ``causal_conv``, its recurrence
``ops/kda_scan.py``'s. What it owns is the config, the parameters, the
state, the two mixers and ``forward``. ``FAMILY`` at the end is what the
engine's seam picks up for a ``LingConfig``.

The equations (``benchmarks/reference_ling.py`` is the same in plain
float32, whole sequences, the delta rule token by token), ``u = RMSNorm(h)``:

- **Stack.** ``h = E[token]``. Layer ``l``: ``h = h + Mixer_l(RMSNorm(h))``,
  then ``h = h + FF_l(RMSNorm(h))``. ``Mixer_l`` is MLA where ``(l + 1) %
  layer_group_size == 0``, else KDA; ``FF_l`` dense for ``l <
  first_k_dense_replace``, sparse after. Final ``RMSNorm``, an untied head.
- **KDA** (H heads of ``head_dim``): ``q~, k~, v~ = u W_q, u W_k, u W_v``;
  ``q, k, v = silu(conv4(.))`` depth-wise and causal; ``q = q / (|q| + eps)
  * d^-0.5``, ``k = k / (|k| + eps)`` a head; ``g = kda_lower_bound *
  sigmoid(exp(A_log[h]) * (u W_a + dt_bias))`` a head and key channel, in
  ``(kda_lower_bound, 0)``; ``beta = sigmoid(u W_beta)`` a head; the
  recurrence of ``ops/kda_scan.py``; ``y = (RMSNorm_head(o) * sigmoid(u
  W_g)) W_o`` with ONE gate a head.
- **MLA.** ``[q_nope | q_rope] = u W_q`` (``wq_b``: no compressed query);
  ``[c_kv | k_rope] = u W_dkv``, ``c_kv = RMSNorm(c_kv)``; rotate-half RoPE
  on the 64-wide parts; ``[k_nope | v] = c_kv W_ukv`` a head; causal
  ``softmax(q k^T / sqrt(192)) v``; the same head-wise gate on the
  attention's output; ``W_o``. The cache holds ``(c_kv, k_rope)`` alone.
- **sparse FF.** ``models/experts.py::sigmoid_group_route`` (the bias steers
  the choice, never the weight), the experts held here, plus one shared
  SwiGLU every token passes.
- **Left pads.** At a pad position ``u`` is zeroed before the KDA
  projections (none has a bias, so ``q~ = k~ = v~ = 0`` and the tails stay
  zero) and ``beta`` after its sigmoid: the state is exactly zero when the
  row's first real token arrives, whatever the gate reads under the pad.
  A pad token is routed nowhere and counted nowhere.

State a program carries (``init_cache``): ``latent`` ``[MLA layers, B, C,
rank + rope]``; ``kda`` ``[KDA layers, B, H, d_v, d_k]`` in ``state_dtype``
(float32: ``S`` transposed, ``ops/kda_scan.py`` says why); ``conv`` ``[KDA
layers, B, K - 1, 3 H d]`` (q | k | v) in the activations' type; the sparse
layers' expert counters and picks.

The stack is traced a few bodies whatever the depth (``models/lfm2.py``'s
``_plan``): layers 0-11 are ``[KDA + dense] x 2``, ``[KDA + sparse] x 3``,
``MLA + sparse``, ``[KDA + sparse] x 5``, ``MLA + sparse``.
"""
from __future__ import annotations

import functools
import types
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from . import deepseek
from .experts import (
    EXPERT_LEAVES,
    _quantize_rows,
    counters,
    expert_layer,
    grouped_experts,
    init_expert_state,
    sigmoid_group_route,
)
from .granite_hybrid import _runs   # the runs of one kind in a period
from .lfm2 import _plan             # the blocks a stack is scanned in
from .llama import (
    _apply_rope,
    _cache_write,
    _embed_lookup,
    _lm_head_logits,
    _mlp_act,
    _proj,
    _rmsnorm,
)
from .mamba_mixer import causal_conv

# the published rule's group score: the sum of a group's two best
GROUP_TOP = 2
# under the l2 norms of q and k: a zero row (a pad's) stays zero
L2_EPS = 1e-6


@dataclass(frozen=True)
class LingConfig:
    vocab_size: int = 157_184
    dim: int = 2560
    n_layers: int = 42
    layer_group_size: int = 6         # the last layer of each group is MLA
    n_heads: int = 32                 # both mixers'
    # as published: every head has keys and values of its own once expanded
    # (KDA's, and MLA's out of the one latent row a token the cache holds)
    n_kv_heads: int = 32
    head_dim: int = 128               # KDA's d_k = d_v
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    # tokens of the prefill scan's chunk (ops/kda_scan.py)
    kda_chunk_size: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6_000_000.0
    norm_eps: float = 1e-6
    first_k_dense_replace: int = 2
    intermediate: int = 6144          # the leading dense layers' width
    moe_intermediate: int = 768
    shared_intermediate: int = 768
    n_routed_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    # what ``models/experts.py`` asks of a config: the experts this chip
    # holds of each sparse layer (0: all of them) from ``expert_offset``
    expert_offset: int = 0
    experts_held: int = 0
    max_seq_len: int = 131_072
    tie_embeddings: bool = False
    act: str = "silu"
    # W8A8 on multi-token forwards, as LlamaConfig's; the engine sets it
    w8a8_prefill: bool = False
    dtype: Any = field(default=jnp.bfloat16)
    # the KDA state's type; anything narrower is a precision cut a parity
    # check has to see (the kernels of ops/kda_scan.py take float32 alone)
    state_dtype: Any = field(default=jnp.float32)
    # a latent row rounded to an int8 grid a token where it is written (kept
    # in the cache's type): the nearest precision below the bf16 latent,
    # which a parity check has to see; the engine never sets it
    latent_int8: bool = False

    def __post_init__(self):
        if self.n_kv_heads != self.n_heads:
            raise ValueError("this family builds as many KV heads as heads")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")
        if not 0 < self.topk_group <= self.n_group:
            raise ValueError("topk_group is between 1 and n_group")
        if self.num_experts_per_tok > (
                self.topk_group * self.n_routed_experts // self.n_group):
            raise ValueError("more picks than the kept groups hold experts")
        if (self.n_routed_experts % self.n_held
                or self.expert_offset % self.n_held
                or self.expert_offset + self.n_held > self.n_routed_experts):
            raise ValueError(
                f"{self.n_held} experts held from expert_offset "
                f"{self.expert_offset} are no whole share of "
                f"{self.n_routed_experts}")
        if not 0 <= self.first_k_dense_replace <= self.n_layers:
            raise ValueError("first_k_dense_replace past the depth")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotated width is even")

    @property
    def n_held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def layer_kinds(self) -> tuple:
        """``"mla"`` | ``"kda"`` a layer."""
        return tuple("mla" if (l + 1) % self.layer_group_size == 0 else "kda"
                     for l in range(self.n_layers))

    @property
    def n_mla(self) -> int:
        return self.layer_kinds.count("mla")

    @property
    def n_kda(self) -> int:
        return self.layer_kinds.count("kda")

    @property
    def n_sparse(self) -> int:
        return self.n_layers - self.first_k_dense_replace

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kda_width(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def mla(self):
        """What ``models/deepseek.py``'s attention functions read of a
        config, at this family's numbers (``head_dim`` there is the query's
        nope + rope)."""
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        return types.SimpleNamespace(
            n_heads=self.n_heads, head_dim=qk,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, kv_lora_rank=self.kv_lora_rank,
            softmax_scale=qk ** -0.5, dtype=self.dtype)


def ling_3_0_flash(**kw) -> LingConfig:
    """inclusionAI/Ling-3.0-flash(-VL) ``config.json``, the language model,
    uncut."""
    return LingConfig(**kw)


def tiny_ling(**kw) -> LingConfig:
    """Small config for hermetic CPU tests: two periods of three layers
    (KDA, KDA, MLA), the first layer dense, 16 experts top-3 in 4 groups of
    which 2 are kept (top-2 of 2 groups would be the top-2 of all: no group
    rule could show), 4 heads of 16, a scan chunk of 32 tokens (two
    sub-blocks). The vocabulary holds the byte tokenizer's 256 bytes and its
    special ids."""
    base = dict(
        vocab_size=384, dim=64, n_layers=6, layer_group_size=3, n_heads=4,
        n_kv_heads=4,
        head_dim=16, kda_chunk_size=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=10_000.0,
        first_k_dense_replace=1, intermediate=128, moe_intermediate=32,
        shared_intermediate=32, n_routed_experts=16, num_experts_per_tok=3,
        n_group=4, topk_group=2, max_seq_len=256, dtype=jnp.float32,
    )
    base.update(kw)
    return LingConfig(**base)


# -- parameters and state -----------------------------------------------------

# how far the seeded ``expert_bias`` spreads (``models/lfm2.py`` says why)
_BIAS_SPREAD = 0.05


def float_leaves(key: jax.Array, cfg: LingConfig) -> dict:
    """{group: {leaf: array}} of the leaves ``models/quant.py``'s direct
    int8 init must not draw its own way, float32 whatever the weights'
    type: the taps ``U[-1/2, 1/2]`` (a depth-wise ``Conv1d``'s default at
    four taps); ``A_log = log U[0.5, 2]`` a head and ``dt_bias = U[-8, -1]``
    a head and key channel, so that a seeded layer's decays spread from a
    channel that forgets in a few tokens (``g`` ~ -2) to one that keeps
    thousands (``g`` ~ -1e-5), as a trained gate's would; the router
    ``N(0, 0.02)`` and ``expert_bias``."""
    kw, ka, kd, kr, kb = jax.random.split(key, 5)
    f32 = jnp.float32
    Lk, Ls, E = cfg.n_kda, cfg.n_sparse, cfg.n_routed_experts
    K = cfg.short_conv_kernel_size
    bound = K ** -0.5
    return {
        "kda": {
            "conv_w": jax.random.uniform(
                kw, (Lk, 3 * cfg.kda_width, K), f32, -bound, bound),
            "A_log": jnp.log(jax.random.uniform(
                ka, (Lk, cfg.n_heads), f32, 0.5, 2.0)),
            "dt_bias": jax.random.uniform(
                kd, (Lk, cfg.n_heads, cfg.head_dim), f32, -8.0, -1.0),
        },
        "layers": {
            "router": jax.random.normal(kr, (Ls, cfg.dim, E), f32) * 0.02,
            "expert_bias": jax.random.normal(kb, (Ls, E), f32) * _BIAS_SPREAD,
        },
    }


def init_params(key: jax.Array, cfg: LingConfig) -> dict:
    """Random init, stacked by kind: the KDA mixers under ``kda``, the MLA
    mixers under ``mla``, the leading dense feed-forwards under ``dense``,
    the routers, experts and shared experts under ``layers``; each group
    with the norm before it."""
    D, H, hd = cfg.dim, cfg.n_heads, cfg.head_dim
    Lk, Lm, Ld, Ls = (cfg.n_kda, cfg.n_mla, cfg.first_k_dense_replace,
                      cfg.n_sparse)
    F, Fe, Fs, E = (cfg.intermediate, cfg.moe_intermediate,
                    cfg.shared_intermediate, cfg.n_held)
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    keys = iter(jax.random.split(key, 32))

    def norm(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale
                ).astype(cfg.dtype)

    floats = float_leaves(next(keys), cfg)
    return {
        "embed": norm((cfg.vocab_size, D)),
        "kda": {
            "mixer_norm": jnp.ones((Lk, D), cfg.dtype),
            "wq": norm((Lk, D, H, hd)), "wk": norm((Lk, D, H, hd)),
            "wv": norm((Lk, D, H, hd)),
            # the decay gate: ONE matrix (no_kda_lora), a head and channel
            "wa": norm((Lk, D, H, hd)),
            "w_beta": norm((Lk, D, H)), "wg_head": norm((Lk, D, H)),
            "o_norm": jnp.ones((Lk, hd), cfg.dtype),
            "wo": norm((Lk, H, hd, D)),
            **floats["kda"],
        },
        "mla": {
            "mixer_norm": jnp.ones((Lm, D), cfg.dtype),
            # the whole query projection (q_lora_rank null): deepseek's
            # ``_queries`` reads it under this name
            "wq_b": norm((Lm, D, H, qk)),
            "wkv_a": norm((Lm, D, cfg.latent_width)),
            "kv_norm": jnp.ones((Lm, cfg.kv_lora_rank), cfg.dtype),
            "wk_b": norm((Lm, cfg.kv_lora_rank, H, cfg.qk_nope_head_dim)),
            "wv_b": norm((Lm, cfg.kv_lora_rank, H, cfg.v_head_dim)),
            "wg_head": norm((Lm, D, H)),
            "wo": norm((Lm, H, cfg.v_head_dim, D)),
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
            "ws_gate": norm((Ls, D, Fs)), "ws_up": norm((Ls, D, Fs)),
            "ws_down": norm((Ls, Fs, D)),
        },
        "final_norm": jnp.ones((D,), cfg.dtype),
        "lm_head": norm((D, cfg.vocab_size)),
    }


def init_cache(cfg: LingConfig, batch: int, cache_len: int, *,
               quantized: bool = False) -> dict:
    """What a program carries: the latent cache over the MLA layers alone,
    every KDA layer's matrix state (``S`` transposed: value channel, key
    channel) and convolution tail (q | k | v on the lanes), the sparse
    layers' expert counters and picks."""
    if quantized:
        raise ValueError("the latent cache has no int8 form")
    return {
        "latent": jnp.zeros(
            (cfg.n_mla, batch, cache_len, cfg.latent_width), cfg.dtype),
        "kda": jnp.zeros((cfg.n_kda, batch, cfg.n_heads, cfg.head_dim,
                          cfg.head_dim), cfg.state_dtype),
        "conv": jnp.zeros((cfg.n_kda, batch, cfg.short_conv_kernel_size - 1,
                           3 * cfg.kda_width), cfg.dtype),
        **init_expert_state(cfg.n_sparse, cfg.n_held, batch,
                            cfg.num_experts_per_tok, decode_touched=True),
    }


# -- the mixers, the feed-forwards and forward --------------------------------

# the KDA projections whose columns are a head's channels on the lanes
LANE_LEAVES = ("wq", "wk", "wv", "wa")
# tokens a tile of a [B, S, H * hd] activation holds: (8 x 128 lanes)
TILE_TOKENS = 8


def _lane_views(kda: dict) -> dict:
    """The stacked ``kda`` group as ``_kda_mixer`` takes it: ``LANE_LEAVES``
    viewed [L, D, H * hd] (an int8 leaf's scales [L, H * hd]) and ``wo``
    [L, H * hd, D]. Stored [L, D, H, hd], a stack is tiled (heads x lanes),
    and a product that reads a layer of it ``bsd,dhk->bshk`` first copies
    that layer out of the stack and turns it to (rows of D x lanes) — 10.5
    MB a projection, layer and decode step at the published widths — and
    hands a row piece's result over head-major, to be turned again. Viewed
    in ``forward`` before the layers' scans, the turn is the whole stack's
    once a call (XLA lifts it out of the call's loops: five a one-shot
    dispatch) and a layer's slice fuses into its product. ``wo``'s view
    moves nothing; it keeps the head norm's result [B, S, H * hd]. The
    stored tree keeps its shapes: ``benchmarks/reference_ling.py`` reads
    them ``sd,dhk->shk``."""
    def lanes(x):      # [L, D, H, hd] and its scales [L, H, hd]
        return x.reshape(x.shape[:-2] + (-1,))

    def rows(x):       # [L, H, hd, D]; its scales [L, D] as they are
        return x.reshape(x.shape[0], -1, x.shape[-1]) if x.ndim == 4 else x

    views = {n: jax.tree.map(lanes, kda[n]) for n in LANE_LEAVES}
    return {**kda, **views, "wo": jax.tree.map(rows, kda["wo"])}


def _tile_order(x):
    # pinned: in the whole program XLA's simplifier else cancels the two
    # turns around the element-wise work between them, and the 4-D reshape
    # with its copy is back
    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def _head_tiles(x):
    """x [B, S, H, ...] in the order the [B, S, H * hd] array it is a
    reshape of is tiled on the chip — (8 tokens x 128 lanes), a head of 128
    a lane tile —: [B, S / 8, H, 8, ...], where S is whole tiles; else x as
    it is. A float32 reduction over a head's channels on the [B, S, H, hd]
    reshape draws that shape's own tiling (8 heads x 128 lanes) and with it
    a copy of the whole array, 134 MB a layer and row piece at the published
    widths; on this view it reads the array where it lies. Value for value
    the same: ``_head_rows`` turns back."""
    B, S, H = x.shape[:3]
    if S % TILE_TOKENS:
        return x
    return _tile_order(x.reshape((B, S // TILE_TOKENS, TILE_TOKENS, H)
                                 + x.shape[3:]).swapaxes(2, 3))


def _head_rows(x, S: int):
    """``_head_tiles``' way back, to [B, S, H, ...]."""
    if S % TILE_TOKENS:
        return x
    x = _tile_order(x).swapaxes(2, 3)
    return x.reshape((x.shape[0], S) + x.shape[3:])


def _kda_mixer(u, lp, slot, valid, cache, cfg: LingConfig, scan_kernels: bool,
               interpret: bool, cache_rows=None):
    """Kimi Delta Attention over u [B, S, D] (normed, zero under the pad) at
    KDA slot ``slot`` of the state, ``lp`` a layer of ``_lane_views``' group.
    ``cache_rows`` [B]: u is a row piece and
    row b's tail and matrix state live at the state's batch row
    ``cache_rows[b]``, read and written there in place. The
    ``jax.named_scope`` names are metadata a device trace is read by (README
    "Device time by layer")."""
    # imported on use, as llama's kernels: the other families' paths never
    # load it
    from ..ops import kda_scan

    B, S, _ = u.shape
    H, hd, W = cfg.n_heads, cfg.head_dim, cfg.kda_width
    aq = cfg.w8a8_prefill and S > 1
    f32 = jnp.float32
    kernels = scan_kernels and cache["kda"].dtype == f32
    decay = dict(A_log=lp["A_log"], dt_bias=lp["dt_bias"],
                 lower_bound=cfg.kda_lower_bound)
    with jax.named_scope("kda_in"):
        *parts, a = (_proj("bsd,dw->bsw", u, lp[n], aq) for n in LANE_LEAVES)
        b = _proj("bsd,dh->bsh", u, lp["w_beta"], aq)
        gate = _proj("bsd,dh->bsh", u, lp["wg_head"], aq)
    with jax.named_scope("kda_conv"):
        tail = jax.lax.dynamic_index_in_dim(cache["conv"], slot, 0, False)
        if cache_rows is not None:
            tail = tail[cache_rows]
        # q, k and v a run of lanes each: no 3 W-wide copy of the chunk
        out = [causal_conv(x, tail[..., i * W:(i + 1) * W],
                           lp["conv_w"][i * W:(i + 1) * W])
               for i, x in enumerate(parts)]
        conv = _cache_write(
            cache["conv"],
            jnp.concatenate([t for _, t in out], -1).astype(
                cache["conv"].dtype), slot, 0, cache_rows)
        q, k, v = (x.reshape(B, S, H, hd) for x, _ in out)      # float32

        def unit(x, scale=1.0):
            x = _head_tiles(x)
            x = x / (jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)) + L2_EPS)
            return _head_rows((x * scale).astype(u.dtype), S)

        q = unit(q, hd ** -0.5)
        k = unit(k)
        v = v.astype(u.dtype)
        # the prefill kernel takes the gate's projection as it is and makes
        # the log-decay, its running sum, beta k and beta v itself, a head's
        # tile at a time in VMEM: no float32 [B, S, W] array lies between
        # the convolution and the kernel
        if kernels and S > 1:
            g = None
        else:
            # the barrier keeps the reshape out of the product: folded into
            # it, a decode step's product wants ``wa`` with D on the lanes
            # and copies the layer out of the stack to turn it
            g = kda_scan.kda_gate(
                jax.lax.optimization_barrier(a).reshape(B, S, H, hd), **decay)
        # a pad's beta would be sigmoid(0): nothing is erased or written there
        beta = jnp.where(valid[..., None], jax.nn.sigmoid(b.astype(f32)), 0.0)
    with jax.named_scope("kda_scan"):
        state = cache["kda"]
        if kernels:
            if S == 1:
                o, state = kda_scan.kda_decode_update(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
                    slot, interpret=interpret)
                o = o[:, None]
            else:
                # left padding: a row's pads are its first positions
                pads = S - jnp.sum(valid, axis=-1, dtype=jnp.int32)
                o, state = kda_scan.kda_prefill_scan(
                    q, k, v, a.reshape(B, S, H, hd), beta, state, slot, pads,
                    cache_rows, **decay,
                    chunk=cfg.kda_chunk_size, interpret=interpret)
        else:
            mine = jax.lax.dynamic_index_in_dim(state, slot, 0, False).astype(
                f32)
            if S == 1:
                o, mine = kda_scan.kda_step_xla(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], mine)
                o = o[:, None]
            else:
                o, mine = kda_scan.kda_chunked_xla(
                    q, k, v, g, beta, mine, cfg.kda_chunk_size, cache_rows)
            state = jax.lax.dynamic_update_slice(
                state, mine.astype(state.dtype)[None], (slot, 0, 0, 0, 0))
    with jax.named_scope("kda_out"):
        # a norm a head (group_norm_size 1), then ONE gate a head
        o = _head_tiles(o.astype(f32))
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
        gate = _head_tiles(jax.nn.sigmoid(gate.astype(f32)))[..., None]
        y = _head_rows(
            (o * lp["o_norm"].astype(f32) * gate).astype(u.dtype), S)
        out = _proj("bsw,wd->bsd", y.reshape(B, S, W), lp["wo"], aq)
    return out, dict(cache, conv=conv, kda=state)


def _int8_grid(rows):
    """``rows`` [..., w] rounded to an int8 grid a row (``latent_int8``)."""
    q, s = _quantize_rows(rows)
    return (q.astype(jnp.float32) * s).astype(rows.dtype)


def _mla_mixer(u, lp, slot, rope, attention, cache, write_index,
               cfg: LingConfig, cache_rows=None):
    """Latent attention over u [B, S, D] (normed) at MLA slot ``slot`` of
    the latent cache, with the layer's input in the compressed query's
    place and the head-wise gate on the attention's output."""
    aq = cfg.w8a8_prefill and u.shape[1] > 1
    rank = cfg.kv_lora_rank
    with jax.named_scope("kv_latent"):
        kv = _proj("bsd,dw->bsw", u, lp["wkv_a"], aq)
        c_kv = _rmsnorm(kv[..., :rank], lp["kv_norm"], cfg.norm_eps)
        k_rope = _apply_rope(kv[..., None, rank:], *rope)[:, :, 0]
        rows = jnp.concatenate([c_kv, k_rope], -1).astype(
            cache["latent"].dtype)
        if cfg.latent_int8:
            rows = _int8_grid(rows)
    with jax.named_scope("kv_write"):
        if cache_rows is None:
            latent = jax.lax.dynamic_update_slice(
                cache["latent"], rows[None], (slot, 0, write_index, 0))
        else:
            latent = cache["latent"]
            for b in range(rows.shape[0]):   # a piece's few rows, in place
                latent = jax.lax.dynamic_update_slice(
                    latent, rows[None, b:b + 1],
                    (slot, cache_rows[b], write_index, 0))
        # rows stay rows (models/deepseek.py ``_block`` says why)
        cache = dict(cache, latent=with_layout_constraint(
            latent, Layout(major_to_minor=(0, 1, 2, 3))))
    with jax.named_scope("attn_gate"):
        gate = jax.nn.sigmoid(
            _proj("bsd,dh->bsh", u, lp["wg_head"], aq).astype(jnp.float32))
    if cache_rows is None:
        view, at = cache, slot
    else:
        # a piece's rows of this layer, gathered: the kernels address a run
        # of batch rows, and a piece's rows are the batch's by pad length
        with jax.named_scope("attn"):
            view = {"latent": jax.lax.dynamic_index_in_dim(
                cache["latent"], slot, 0, False)[cache_rows][None]}
        at = 0
    return attention.attend(u, rope, view, at, lp, aq, gate=gate), cache


def _dense_ffn(x, lp, cfg: LingConfig):
    """The leading layers' SwiGLU over the normed x [B, S, D] (the caller
    adds the residual, as for ``_sparse_ffn``)."""
    aq = cfg.w8a8_prefill and x.shape[1] > 1
    u = _rmsnorm(x, lp["ffn_norm"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        gate = _proj("bsd,di->bsi", u, lp["w_gate"], aq)
        up = _proj("bsd,di->bsi", u, lp["w_up"], aq)
        return _proj("bsi,id->bsd", _mlp_act(gate, cfg.act) * up,
                     lp["w_down"], aq)


def _sparse_ffn(x, lp, experts, slot, valid, cache, cfg: LingConfig,
                experts_fn, cache_rows=None):
    """The routed experts held here (``models/experts.py``, under
    ``sigmoid_group_route``) + the shared expert over x [B, S, D], and the
    counters."""
    B, S, D = x.shape
    aq = cfg.w8a8_prefill and S > 1
    u = _rmsnorm(x, lp["ffn_norm"], cfg.norm_eps)
    flat = u.reshape(B * S, D)

    def picks():
        return sigmoid_group_route(
            jnp.einsum("td,de->te", flat.astype(jnp.float32),
                       lp["router"].astype(jnp.float32)),
            lp["expert_bias"].astype(jnp.float32), cfg.num_experts_per_tok,
            cfg.n_group, cfg.topk_group, cfg.routed_scaling_factor, GROUP_TOP)

    routed, cache = expert_layer(flat, picks, valid, experts, slot, cache,
                                 cfg, experts_fn, rows=B,
                                 cache_rows=cache_rows)
    with jax.named_scope("shared_experts"):
        gate = _proj("bsd,di->bsi", u, lp["ws_gate"], aq)
        up = _proj("bsd,di->bsi", u, lp["ws_up"], aq)
        shared = _proj("bsi,id->bsd", _mlp_act(gate, cfg.act) * up,
                       lp["ws_down"], aq)
    return routed.reshape(B, S, D).astype(x.dtype) + shared, cache


def forward(params: dict, cfg: LingConfig, tokens, positions, cache,
            write_index, mask, *, last_only: bool = False,
            stacked_attention_fn=None, experts_fn=None,
            scan_kernels: bool = False, interpret: bool = False,
            cache_rows=None):
    """Run the decoder over ``tokens`` [B, S] written at cache slots
    ``write_index ..``; returns (logits [B, S, vocab] float32, state).

    ``stacked_attention_fn`` is the phase's latent attention over the
    stacked cache of the MLA layers (``models/deepseek.py``'s
    ``LatentAttention``); None is the dense XLA attention under ``mask``
    [B, S, C]. ``experts_fn(x, local, weights, experts, slot)`` is the
    routed experts' product (``grouped_experts``); None is
    ``dense_experts``. ``scan_kernels`` runs the delta rule through
    ``ops/kda_scan.py``'s kernels (``interpret``: on the CPU), else through
    its XLA forms.

    ``cache_rows`` [B] int32: the tokens are a row piece of a batch whose
    state holds more rows (the engine's prefill,
    ``Family.prefill_piece_tokens``) and row b of them lives at the state's
    batch row ``cache_rows[b]`` — latent rows, matrix state, tails and picks
    written and read there in place, the state's other rows left as they
    are. A (row, chunk) piece that is all left pad need not run: under the
    pad the KDA mixer's input and ``beta`` are zeroed and nothing in it has
    a bias, so state and tails stay the zeros they came as, and every
    kernel masks a pad's latent rows by ``pad_lens``."""
    attention = stacked_attention_fn or deepseek.dense_attention(
        cfg.mla, mask)
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    with jax.named_scope("kv_latent"):  # the rope table the MLA layers read
        half = cfg.qk_rope_head_dim // 2
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
    Ld = cfg.first_k_dense_replace
    # outside the scans: a layer's slice then fuses into the product that
    # reads it
    mixers = {"kda": _lane_views(params["kda"]), "mla": params["mla"]}

    def one(tree, i):
        """Layer i of a stacked group, read where it is used (the slice
        fuses into the products that consume it)."""
        return jax.tree.map(
            lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False),
            tree)

    def layer(carry, kind, mixer_slot, l):
        """One layer of ``kind`` (mixer, dense?) at slot ``mixer_slot`` of
        its mixer's group; ``l`` its index in the stack."""
        x, cache = carry
        mixer, dense = kind
        lp = one(mixers[mixer], mixer_slot)
        u = _rmsnorm(x, lp["mixer_norm"], cfg.norm_eps)
        if mixer == "kda":
            u = jnp.where(valid[..., None], u, jnp.zeros_like(u))
            out, cache = _kda_mixer(u, lp, mixer_slot, valid, cache, cfg,
                                    scan_kernels, interpret, cache_rows)
        else:
            out, cache = _mla_mixer(u, lp, mixer_slot, rope, attention, cache,
                                    write_index, cfg, cache_rows)
        x = x + out.astype(x.dtype)
        if dense:
            return x + _dense_ffn(x, one(params["dense"], l), cfg), cache
        out, cache = _sparse_ffn(x, one(sparse, l - Ld), experts, l - Ld,
                                 valid, cache, cfg, experts_fn, cache_rows)
        return x + out, cache

    kinds = tuple((mixer, l < Ld) for l, mixer in enumerate(cfg.layer_kinds))
    # mixer slots before each layer: KDA layers before it, or MLA
    before = [sum(k[0] == kinds[l][0] for k in kinds[:l])
              for l in range(cfg.n_layers)]
    carry = (x, cache)
    for first, period, repeats in _plan(kinds):
        P = len(period)

        def period_step(carry, p, first=first, period=period, P=P):
            for kind, j0, count in _runs(period):
                # a mixer's slots in one period of this block
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


def forward_dense(params: dict, cfg: LingConfig, tokens) -> jax.Array:
    """Cache-free causal forward of whole sequences [B, S] with no kernel:
    logits [B, S, vocab] float32. (The latent rows still pass through a
    cache of exactly S slots, the recurrence through a state from zero.)"""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    logits, _ = forward(params, cfg, tokens, positions,
                        init_cache(cfg, B, S), 0, mask)
    return logits


# -- the engine's seam (models/family.py) -------------------------------------

# the fewest tokens a row piece of the prefill holds: four rows of a
# 2,048-token chunk, so that each of 128 held experts sees ~128 rows of a
# piece (2 of a token's 8 picks held) and a piece's temporaries — the scan's
# float32 gate and running sum, [rows, chunk, 4096] each — stay a fixed size
# whatever the batch
PREFILL_PIECE_TOKENS = 8192


def prefill_counts(cfg: LingConfig, pad_lens, spans, cache_len=None) -> dict:
    """What one dispatch's prefill saw, from the pads it was packed with:
    ``kda_tokens_real`` (real prompt tokens) and ``kda_tokens_computed``
    (tokens of the scan chunks ``kda_prefill_scan`` did not skip: a chunk
    wholly under a row's pad is skipped, whether or not its row piece ran),
    both x KDA layers; and the latent kernel's ``latent_keys_expanded`` /
    ``latent_keys_real`` (``models/deepseek.py::prefill_counts``'s rule) x
    MLA layers. ``spans`` are the prefill's query spans [lo, hi) over the
    bucket."""
    import numpy as np

    from ..ops.kda_scan import kda_tokens_computed
    from ..ops.mla_attention import prefill_tile_classes

    pads = np.asarray(pad_lens, np.int64)
    real = computed = expanded = 0
    for lo, hi in spans:
        inside = np.clip(pads - lo, 0, hi - lo)   # pads among these tokens
        real += int(((hi - lo) - inside).sum())
        computed += kda_tokens_computed(inside, hi - lo, cfg.kda_chunk_size)
        expanded += prefill_tile_classes(
            pad_lens, hi - lo, hi, lo)["keys_expanded"]
    keys = np.clip(spans[-1][1] - pads, 0, None)
    return {"kda_tokens_real": real * cfg.n_kda,
            "kda_tokens_computed": computed * cfg.n_kda,
            "latent_keys_expanded": expanded * cfg.n_mla,
            "latent_keys_real": int(keys.sum()) * cfg.n_mla}


def row_record(cache: dict) -> dict:
    """What a parity check may see of the position just scored: the first
    and the last KDA layer's matrix state [2, B, H, d_v, d_k] — the first
    carries one product's rounding and the scan's own arithmetic, the last
    everything before it — and the routers' picks [sparse layers, B, k].
    (The latent rows of every position are in the final cache.)"""
    return {"state": jnp.stack([cache["kda"][0], cache["kda"][-1]]),
            "picks": cache["picks"]}


def _kernels_supported(cfg: LingConfig, interpret: bool) -> bool:
    # a KDA head is one lane tile of the layer's [B, S, H * d] arrays; the
    # MLA kernels take the published widths; interpreted, any
    return interpret or cfg.head_dim == 128


def _forward_kwargs(cfg: LingConfig, kernels: bool, interpret: bool):
    if not kernels:
        return {}   # flash=False: dense attention, dense_experts, XLA scan
    return {"experts_fn": functools.partial(
        grouped_experts, cfg=cfg, interpret=interpret),
        "scan_kernels": True, "interpret": interpret}


def _family():
    from .family import Family

    carries_state = (
        "this family's state holds every KDA layer's float32 matrix state "
        "[heads, d_v, d_k] and three-token convolution tail and the sparse "
        "layers' expert counters and picks beside the latent rows of its "
        "few MLA layers")
    return Family(
        name="ling", forward=forward, init_cache=init_cache,
        init_params=init_params, kernels_supported=_kernels_supported,
        attention_supported=lambda cfg, S, C: (True, True),
        prefill_attention=lambda cfg, mesh, interpret, pad_lens, window,
        q_offset=0, cache_rows=None: deepseek.prefill_attention(
            cfg.mla, pad_lens, q_offset, interpret=interpret),
        decode_attention=lambda cfg, mesh, interpret, pad_lens, S, t,
        window: deepseek.decode_attention(
            cfg.mla, pad_lens, S, t, interpret=interpret),
        int8_cache=False, attention_layers=lambda cfg: cfg.n_mla,
        prefill_counts=prefill_counts,
        prefill_piece_tokens=PREFILL_PIECE_TOKENS,
        forward_kwargs=_forward_kwargs, counters=counters,
        row_record=row_record,
        missing={
            "slot loop": (
                "the slot programs (backend/inflight.py, engine._make_slot_*"
                ", _make_adopt_fn) fill one row at a time, scatter every "
                "leaf of a joined batch's cache on its second axis as "
                "[L, B, KV, C, hd] keys and values, and return no counters; "
                "they know no latent cache, and adopting and evicting a row "
                "would have to move a matrix state and a tail they do not "
                "carry: " + carries_state),
            "prefix cache": (
                "cache/radix.py and cache/store.py slice [N, L, KV, BLK, hd] "
                "keys and values by block at any token and the resume "
                "program returns the final cache in the counters' place; a "
                "latent row has no KV heads, and a delta-rule state resumes "
                "only from a snapshot of the matrix (and the tail) taken at "
                "the block's boundary, and none is kept: " + carries_state),
            "mesh": (
                "parallel/sharding.py has no specs for the KDA mixer's "
                "parameters (the five projections, the taps, A_log, "
                "dt_bias), the router, its bias and the stacked experts, for "
                "the matrix state and the tails, no expert axis and no "
                "exchange of the experts' partial sums, and shards a cache "
                "by KV heads the latent cache does not have"),
            "speculative decoding": (
                "a rejected draft has to roll every KDA layer's matrix state "
                "and tail back to the last accepted token, and the verify "
                "step keeps no state per position, writes per-row cache "
                "slots, runs the GQA verify kernel and hands a KV cache "
                "alone from step to step: " + carries_state),
            "long-context backend": (
                "the ring prefill runs models.llama.cache_free_block and "
                "passes keys and values per KV head between shards; a delta "
                "rule would have to hand its matrix state and tail from "
                "shard to shard in order, the latent rows have no KV heads, "
                "and the block has no expert layer"),
        },
    )


FAMILY = _family()
