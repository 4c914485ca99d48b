"""PartitionSpec trees for model state (megatron-style tensor parallelism).

Weights are sharded on the head / hidden dimensions over the `model` axis;
batches over `data`. GSPMD inserts the all-gathers / reduce-scatters over ICI
— nothing here issues a collective by hand (scaling-book recipe; contrast
SURVEY.md §2.2: the reference has no parallelism to port).
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .mesh import AXES

_D, _M, _F = AXES.data, AXES.model, AXES.fsdp


def param_specs(
    tie_embeddings: bool = True,
    quantized: bool = False,
    fsdp: bool = False,
    qk_norm: bool = False,
    sandwich_norms: bool = False,
    looped: bool = False,
) -> dict[str, Any]:
    """PartitionSpec pytree matching models.llama param structure.

    Layer leaves carry a leading stacked-layer dim (scanned); with
    ``fsdp=True`` that dim is sharded over the `fsdp` mesh axis (ZeRO-3
    style: each layer-scan step all-gathers just that layer's weights, so
    per-device parameter + optimizer memory drops by the axis size).

    With ``quantized=True`` the tree matches models.quant.quantize_params
    output: each matmul weight becomes ``{"q": <weight spec>, "s": <scale
    spec>}`` where the scale spec is the weight spec with the contracted
    axes removed (a per-output-channel scale lives on the output axes, so it
    inherits exactly their sharding).
    """
    L = _F if fsdp else None  # leading stacked-layer dim of every layer leaf
    specs = {
        "embed": P(_M, None),          # vocab-sharded embedding
        "layers": {
            "attn_norm": P(L, None),
            "wq": P(L, None, _M, None),      # [L, D, nh, hd] — heads sharded
            "wk": P(L, None, _M, None),
            "wv": P(L, None, _M, None),
            "wo": P(L, _M, None, None),      # [L, nh, hd, D]
            "mlp_norm": P(L, None),
            "w_gate": P(L, None, _M),        # [L, D, I] — hidden sharded
            "w_up": P(L, None, _M),
            "w_down": P(L, _M, None),        # [L, I, D]
        },
        "final_norm": P(None),
    }
    if qk_norm:
        # per-head Q/K norms [L, hd]: tiny, replicated over model
        specs["layers"]["q_norm"] = P(L, None)
        specs["layers"]["k_norm"] = P(L, None)
    if sandwich_norms:
        specs["layers"]["post_attn_norm"] = P(L, None)
        specs["layers"]["post_ffw_norm"] = P(L, None)
    if not tie_embeddings:
        specs["lm_head"] = P(None, _M)       # [D, V]
    if looped:
        # a looped stack's exit gate (llama.init_params): a vector and a
        # scalar, float32, replicated
        specs["exit_gate"] = {"w": P(None), "b": P()}
    if quantized:
        from ..models.quant import _CONTRACT_AXES

        def qspec(spec: P, contract_axes: tuple[int, ...]) -> dict:
            scale = P(*(ax for i, ax in enumerate(spec) if i not in contract_axes))
            return {"q": spec, "s": scale}

        for name, axes in _CONTRACT_AXES.items():
            shifted = tuple(a + 1 for a in axes)  # leading stacked-L dim
            specs["layers"][name] = qspec(specs["layers"][name], shifted)
        specs["embed"] = qspec(specs["embed"], (1,))
        if not tie_embeddings:
            specs["lm_head"] = qspec(specs["lm_head"], (0,))
    return specs


def cache_specs(quantized: bool = False) -> dict[str, Any]:
    """KV cache [L, B, kv_heads, C, hd]: batch over data, heads over model.

    With ``quantized=True`` adds the int8-cache per-(token, head) scale
    planes [L, B, kv_heads, C], which shard exactly like their cache dims.
    """
    kv = P(None, _D, _M, None, None)
    specs: dict[str, Any] = {"k": kv, "v": kv}
    if quantized:
        scale = P(None, _D, _M, None)
        specs["ks"] = scale
        specs["vs"] = scale
    return specs


def batch_spec() -> P:
    """[B, S] token batches shard over data."""
    return P(_D, None)


def param_shardings(
    mesh: Mesh,
    tie_embeddings: bool = True,
    quantized: bool = False,
    fsdp: bool = False,
    qk_norm: bool = False,
    sandwich_norms: bool = False,
    looped: bool = False,
) -> dict[str, Any]:
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        param_specs(tie_embeddings, quantized, fsdp, qk_norm, sandwich_norms,
                    looped),
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params: Any, mesh: Mesh, tie_embeddings: bool = True) -> Any:
    """Place a param pytree onto the mesh with TP shardings.

    Raises a config-level error (which sharded dim, which axis) instead of
    letting device_put surface a raw XLA divisibility failure.
    """
    from ..models.quant import is_quantized

    quantized = is_quantized(params)
    qk_norm = "q_norm" in params["layers"]
    sandwich = "post_attn_norm" in params["layers"]
    looped = "exit_gate" in params
    specs = param_specs(
        tie_embeddings, quantized, qk_norm=qk_norm, sandwich_norms=sandwich,
        looped=looped,
    )

    def check(leaf, spec):
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            size = mesh.shape.get(axis, 1)
            if leaf.shape[dim] % size:
                raise ValueError(
                    f"param dim {dim} (size {leaf.shape[dim]}) is not "
                    f"divisible by mesh axis '{axis}' ({size}); shrink that "
                    "mesh axis or pick a TP-compatible model config"
                )

    jax.tree.map(check, params, specs, is_leaf=lambda x: isinstance(x, P))
    shardings = param_shardings(
        mesh, tie_embeddings, quantized, qk_norm=qk_norm,
        sandwich_norms=sandwich, looped=looped,
    )
    return jax.tree.map(jax.device_put, params, shardings)
