"""shard_map wrappers that keep the Pallas attention kernels under a mesh.

Without these, a meshed engine had to fall back to the dense XLA attention
path (whose per-step whole-cache copies are exactly what the kernels remove
— see ops/decode_attention.py). The wrapping is collective-free: batch rows
live on the `data` axis and heads on the `model` axis, so every (row, head)
softmax is complete within one shard — each chip just runs the same kernel
on its local q/cache blocks. GSPMD continues to partition the rest of the
forward around these calls.

The reference has no analog (its only "distribution" is HTTP to Ollama,
SURVEY.md §2.2); this is the scaling-book recipe: pick a mesh, keep the hot
kernel local, let the compiler move everything else.
"""
from __future__ import annotations

from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import AXES
from ..parallel.sharding import cache_specs
from .decode_attention import flash_decode_attention
from .flash_attention import flash_prefill_attention

_Q_SPEC = P(AXES.data, None, AXES.model, None)  # [B, S|1, H, hd]


def _cache_specs(cache: dict) -> dict:
    return cache_specs(quantized="ks" in cache)


def sharded_flash_prefill(
    mesh: Mesh,
    q,
    cache: dict,
    layer_idx,
    pad_lens,
    q_per_kv: int,
    window=None,
    q_offset=None,
    cache_rows=None,
    *,
    interpret: bool = False,
):
    """flash_prefill_attention with q/cache sharded over (data, model).
    ``window`` and ``q_offset`` are replicated scalars (0/None = global
    layer / whole-prompt prefill). ``cache_rows`` (q a row piece of the
    cache's batch) is a replicated vector: the engine makes pieces only
    where the `data` axis holds the whole batch."""
    import jax.numpy as jnp

    # the piece's rows ride last, where there are any
    rows = () if cache_rows is None else (cache_rows,)
    fn = shard_map(
        lambda qs, cs, li, pads, win, off, *rows: flash_prefill_attention(
            qs, cs, li, pads, q_per_kv, win, off, *rows, interpret=interpret
        ),
        mesh=mesh,
        in_specs=(_Q_SPEC, _cache_specs(cache), P(), P(AXES.data), P(), P())
        + (P(),) * len(rows),
        out_specs=_Q_SPEC,
        check_vma=False,
    )
    win = jnp.asarray(0 if window is None else window, jnp.int32)
    off = jnp.asarray(0 if q_offset is None else q_offset, jnp.int32)
    return fn(q, cache, layer_idx, pad_lens, win, off, *rows)


def sharded_flash_decode(
    mesh: Mesh,
    q,
    cache: dict,
    layer_idx,
    pad_lens,
    fill,
    q_per_kv: int,
    window=None,
    *,
    interpret: bool = False,
):
    """flash_decode_attention with q/cache sharded over (data, model).
    ``window`` is a replicated scalar (0/None = global layer)."""
    import jax.numpy as jnp

    fn = shard_map(
        lambda qs, cs, li, pads, fl, win: flash_decode_attention(
            qs, cs, li, pads, fl, q_per_kv, win, interpret=interpret
        ),
        mesh=mesh,
        in_specs=(_Q_SPEC, _cache_specs(cache), P(), P(AXES.data), P(), P()),
        out_specs=_Q_SPEC,
        check_vma=False,
    )
    win = jnp.asarray(0 if window is None else window, jnp.int32)
    return fn(q, cache, layer_idx, pad_lens, fill, win)
