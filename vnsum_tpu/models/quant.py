"""Weight-only int8 quantization for decode throughput.

Single-token decode on a 3B model is HBM-bandwidth-bound: every step streams
the full weight set. Storing matmul weights as int8 with per-output-channel
float scales halves that traffic. The matmul runs on the raw int8 values
(converted to the activation dtype on the way into the MXU — a fusion XLA
always does) and the scale is applied to the matmul OUTPUT, which is exactly
equivalent because each scale multiplies only channels that never mix in the
contraction:

- ``wq/wk/wv [L, D, H, hd]``  (contract d)      -> scale ``[L, H, hd]``
- ``wo [L, H, hd, D]``        (contract h, k)   -> scale ``[L, D]``
- ``w_gate/w_up [L, D, I]``   (contract d)      -> scale ``[L, I]``
- ``w_down [L, I, D]``        (contract i)      -> scale ``[L, D]``
- ``embed [V, D]``            row-wise          -> scale ``[V]`` (works for
  both the gather and the tied LM head, whose output channel IS the row)
- ``lm_head [D, V]``          (contract d)      -> scale ``[V]``

Norm weights stay in full precision (tiny, and numerically sensitive).

The reference has no quantization support at all — its nearest analog is
running 4-bit Ollama builds like ``gemma3:4b-it-qat``
(run_full_evaluation_pipeline.py:960-962) as a black box.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# weight name -> axes that are CONTRACTED in its matmul (reduced over for the
# scale max) ; remaining axes are output channels and keep per-channel scales
_CONTRACT_AXES = {
    "wq": (0,), "wk": (0,), "wv": (0,),   # [D, H, hd] contract D
    "wo": (0, 1),                          # [H, hd, D] contract H, hd
    "w_gate": (0,), "w_up": (0,),          # [D, I] contract D
    "w_down": (0,),                        # [I, D] contract I
}
# the same for every family's stacked leaves: the dense stack's above (which
# parallel/sharding.py also reads, name for name) and those of latent
# attention and sparse experts (models/deepseek.py)
_ALL_CONTRACT_AXES = {
    **_CONTRACT_AXES,
    "wq_a": (0,), "wq_b": (0,),            # [D, r], [r, H, hd]
    "wkv_a": (0,),                         # [D, rank + rope]
    "wk_b": (0,), "wv_b": (0,),            # [rank, H, k] contract rank
    "ws_gate": (0,), "ws_up": (0,), "ws_down": (0,),   # shared experts
    # stacked experts [E, D, F] / [E, F, D]: the expert axis stays, so the
    # scale is per expert and output channel, [E, F] / [E, D]
    "we_gate": (1,), "we_up": (1,), "we_down": (1,),
    # a Mamba-2 mixer's products (models/mamba_mixer.py: in_proj held as
    # its parts z | xBC | dt, and out_proj); its convolution, dt_bias,
    # A_log, D and gated norm stay float32
    "in_z": (0,), "in_xbc": (0,), "in_dt": (0,),   # [D, inner | conv | H]
    "out_proj": (0,),                              # [inner, D]
    # a gated short-convolution operator's products (models/lfm2.py: in_proj
    # held as its parts B | C | x; its out_proj is "out_proj" above); the
    # taps stay float32
    "in_b": (0,), "in_c": (0,), "in_x": (0,),      # [D, D]
    # a Kimi-Delta-Attention mixer's products beside wq, wk, wv and wo
    # (models/ling.py: the decay gate's one matrix, beta's and the head-wise
    # output gate's, which its MLA layers have too); the taps, A_log and
    # dt_bias stay float32
    "wa": (0,), "w_beta": (0,), "wg_head": (0,),   # [D, H, hd], [D, H] x 2
    # a sparse-attention indexer's two projections (models/keye.py); the
    # heads' weights, its LayerNorm and the QK-norms stay full precision
    "wq_idx": (0,), "wk_idx": (0,),                # [D, Hi, di], [D, di]
}
# the groups of stacked layers a params tree may hold: every family has
# "layers"; one with leading dense layers keeps them under "dense"; one
# whose attention differs in shape by layer kind keeps each kind's under
# "full" and "sliding" (models/laguna.py); one that mixes Mamba and attention
# layers keeps each kind's mixer under "mamba" and "attn" and every layer's
# feed-forward under "layers" (models/granite_hybrid.py); one whose layers
# are each ONE mixer keeps the sparse-expert layers under "layers"
# (models/nemotron_h.py: two matrices an expert, "we_up" and "we_down"; its
# router and the router's correction bias stay float32); one that mixes
# convolution operators and attention keeps each kind's operator under
# "conv" and "attn", its leading dense feed-forwards under "dense" and the
# routers and experts under "layers" (models/lfm2.py); one that mixes
# delta-rule and latent-attention layers keeps each kind's mixer under "kda"
# and "mla" (models/ling.py)
_LAYER_GROUPS = ("layers", "dense", "full", "sliding", "mamba", "attn",
                 "conv", "kda", "mla")


def _quantize(w: jax.Array, contract_axes: tuple[int, ...]) -> dict:
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=contract_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "s": jnp.squeeze(scale, axis=contract_axes)}


def quantize_params(params: dict) -> dict:
    """Params pytree -> same tree with matmul weights as {"q": int8, "s": f32}.

    Layer weights have a leading stacked L dim, so their contract axes shift
    by one; the scale keeps the L dim for the layer scan.
    """
    def stack(group: dict) -> dict:
        layers = {}
        for name, w in group.items():
            if name in _ALL_CONTRACT_AXES:
                axes = tuple(a + 1 for a in _ALL_CONTRACT_AXES[name])
                layers[name] = _quantize(w, axes)
            else:  # norms, a router
                layers[name] = w
        return layers

    out = {
        "embed": _quantize(params["embed"], (1,)),  # row max -> scale [V]
        "final_norm": params["final_norm"],
        **{g: stack(params[g]) for g in _LAYER_GROUPS if g in params},
    }
    if "lm_head" in params:
        out["lm_head"] = _quantize(params["lm_head"], (0,))  # scale [V]
    if "exit_gate" in params:   # a looped stack's gate: float32 as it is
        out["exit_gate"] = params["exit_gate"]
    return out


def init_params_quantized(key: jax.Array, cfg) -> dict:
    """Random-init a params tree DIRECTLY in quantize_params' int8 layout.

    The usual path (bf16 init, then on-device quantize) keeps both trees
    resident — 3x the int8 bytes — which can never fit phi4:14b (~14.2 GB
    int8) on one 16 GB chip. This builds the int8 tree without a bf16 one
    ever existing: random int8 weights with a constant ~1/(sqrt(fan_in)*127)
    scale, so dequantized magnitudes sit in the usual init range. Shapes
    come from jax.eval_shape over init_params — the two layouts cannot
    drift. Perf-sweep tool (real memory/compute shape, untrained values);
    jit via models.jitted_init like init_params.
    """
    import importlib

    # the family's own init_params, from the module its config lives in
    module = importlib.import_module(type(cfg).__module__)
    init_params = module.init_params
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), key)
    n_leaves = len(jax.tree.leaves(shapes, is_leaf=lambda x: x is None))
    keys = iter(jax.random.split(key, max(n_leaves, 8)))

    def qinit(k, spec, contract_axes):
        q = jax.random.randint(k, spec.shape, -127, 128, dtype=jnp.int8)
        fan = 1
        for a in contract_axes:
            fan *= spec.shape[a]
        s_shape = tuple(
            d for i, d in enumerate(spec.shape) if i not in contract_axes
        )
        s = jnp.full(s_shape, (fan ** -0.5) / 127.0, jnp.float32)
        return {"q": q, "s": s}

    def stack(group: dict) -> dict:
        layers = {}
        for name, spec in group.items():
            if name in _ALL_CONTRACT_AXES:
                axes = tuple(a + 1 for a in _ALL_CONTRACT_AXES[name])
                layers[name] = qinit(next(keys), spec, axes)
            elif spec.ndim > 2:  # a router: small, kept in full precision
                layers[name] = (jax.random.normal(
                    next(keys), spec.shape, jnp.float32) * 0.02
                ).astype(spec.dtype)
            else:  # norm vectors
                layers[name] = jnp.ones(spec.shape, spec.dtype)
        return layers

    # the layer stacks draw their keys first, then the embedding, then the
    # head: the order a seed's weights have always been drawn in
    groups = {g: stack(shapes[g]) for g in _LAYER_GROUPS if g in shapes}
    if hasattr(module, "float_leaves"):
        # full-precision leaves the family draws its own way (a scan's
        # decays), from a key of their own: no other draw moves
        for g, leaves in module.float_leaves(
                jax.random.fold_in(key, 1), cfg).items():
            groups[g].update(leaves)
    if hasattr(module, "zero_padding"):
        # stored values that are no part of the model (experts stored wider
        # than they are): zero, whatever was drawn there
        groups = module.zero_padding(groups, cfg)
    out = {
        "embed": qinit(next(keys), shapes["embed"], (1,)),
        "final_norm": jnp.ones(
            shapes["final_norm"].shape, shapes["final_norm"].dtype
        ),
        **groups,
    }
    if "lm_head" in shapes:
        out["lm_head"] = qinit(next(keys), shapes["lm_head"], (0,))
    if "exit_gate" in shapes:
        # a looped stack's gate, float32, from a key of its own: no other
        # draw moves
        gate = shapes["exit_gate"]
        out["exit_gate"] = {
            "w": jax.random.normal(
                jax.random.fold_in(key, 2), gate["w"].shape, jnp.float32
            ) * 0.02,
            "b": jnp.zeros(gate["b"].shape, jnp.float32)}
    return out


def dequantize_params(qparams: dict) -> dict:
    """Inverse transform (tests / round-trip checks)."""

    def stack(group: dict) -> dict:
        return {
            name: dequantize_leaf(
                w, tuple(a + 1 for a in _ALL_CONTRACT_AXES[name]))
            if name in _ALL_CONTRACT_AXES else w
            for name, w in group.items()}

    out = {
        "embed": dequantize_leaf(qparams["embed"], (1,)),
        "final_norm": qparams["final_norm"],
        **{g: stack(qparams[g]) for g in _LAYER_GROUPS if g in qparams},
    }
    if "lm_head" in qparams:
        out["lm_head"] = dequantize_leaf(qparams["lm_head"], (0,))
    if "exit_gate" in qparams:
        out["exit_gate"] = qparams["exit_gate"]
    return out


def dequantize_leaf(leaf, contract_axes: tuple[int, ...]) -> jax.Array:
    """A float32 weight from a plain or an int8 ``{"q", "s"}`` leaf whose
    scale lacks ``contract_axes``."""
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    s = leaf["s"]
    for a in sorted(contract_axes):
        s = jnp.expand_dims(s, a)
    return leaf["q"].astype(jnp.float32) * s


def is_quantized(params: dict) -> bool:
    return isinstance(params.get("embed"), dict)
