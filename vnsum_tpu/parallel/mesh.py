"""Device mesh construction.

The reference has no distributed backend at all (SURVEY.md §2.2 — its only
cross-process channel is HTTP to Ollama). Here parallelism is expressed the
TPU-native way: a named `jax.sharding.Mesh` over ICI, `NamedSharding`
annotations, and GSPMD-inserted collectives under `jit`.

Axis conventions (scaling-book style):
    data   — batch / document-chunk batch (DP)
    model  — attention heads + MLP hidden (TP, megatron-style)
    seq    — sequence/context parallelism for ring attention (SP)
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["AXES", "MeshAxes", "make_mesh", "mesh_from_spec"]


@dataclass(frozen=True)
class MeshAxes:
    data: str = "data"
    model: str = "model"
    seq: str = "seq"
    fsdp: str = "fsdp"  # stacked-layer (stage) sharding; weights all-gather
    #                     per layer-scan step, FSDP/ZeRO-3 style


AXES = MeshAxes()


def make_mesh(
    shape: dict[str, int] | None = None, *, platform: str | None = None
) -> Mesh:
    """Build a Mesh from {axis: size}. Missing sizes default to 1; a single
    -1 entry absorbs the remaining devices (like a reshape wildcard).

    ``platform`` selects a device kind explicitly; None is the default
    platform (under JAX_PLATFORMS=cpu, the forced host-device pool)."""
    devices = jax.devices(platform) if platform else jax.devices()
    n = len(devices)
    shape = dict(shape or {})
    for ax in (AXES.data, AXES.model, AXES.seq):
        shape.setdefault(ax, 1)
    # the fsdp axis is opt-in: only materialize it when requested, so
    # existing (data, model, seq) meshes keep their shape
    if AXES.fsdp in shape and shape[AXES.fsdp] in (1, None):
        shape.pop(AXES.fsdp)
    wild = [ax for ax, s in shape.items() if s == -1]
    if len(wild) > 1:
        raise ValueError("at most one mesh axis may be -1")
    fixed = int(np.prod([s for s in shape.values() if s != -1]))
    if wild:
        if n % fixed:
            raise ValueError(f"{n} devices not divisible by fixed axes {fixed}")
        shape[wild[0]] = n // fixed
    total = int(np.prod(list(shape.values())))
    if total > n:
        raise ValueError(f"mesh shape {shape} needs {total} devices, have {n}")
    names = tuple(shape.keys())
    dims = tuple(shape[k] for k in names)
    return Mesh(np.asarray(devices[:total]).reshape(dims), names)


def mesh_from_spec(spec: str) -> Mesh:
    """Parse "data=2,model=4" into a Mesh."""
    shape: dict[str, int] = {}
    for part in spec.split(","):
        if not part.strip():
            continue
        k, v = part.split("=")
        shape[k.strip()] = int(v)
    return make_mesh(shape)
