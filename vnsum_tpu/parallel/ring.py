"""Ring attention: sequence/context parallelism over the `seq` mesh axis.

The reference handles long context purely algorithmically (chunk + collapse,
SURVEY.md §5); this gives the framework true sequence parallelism so a single
forward can span sequences longer than one chip's memory. Blockwise design
following the ring-attention pattern (Liu et al.): K/V blocks rotate around
the ring via `ppermute` while each device keeps its Q block and accumulates
flash-style online-softmax partial results — compute overlaps the ICI
transfer and no device ever materializes the full [S, S] score matrix.

Implemented with `shard_map` over the full mesh: batch and heads are data-
local (no collectives), only `seq` communicates.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from .mesh import AXES

_NEG = jnp.float32(-1e30)


def _ring_local(qb, kb, vb, pad_lens, q_per_kv: int, axis_name: str, causal: bool):
    """Per-device body. qb [B, Sq, H, hd], kb/vb [B, Sk, KV, hd] (local);
    pad_lens [B] (or None) masks out the left-padding slots of each row."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Sq, H, hd = qb.shape
    KV = kb.shape[2]
    G = q_per_kv
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    qg = qb.reshape(B, Sq, KV, G, hd)
    q_pos = idx * Sq + jnp.arange(Sq)

    # derive accumulators from q so they carry the same varying-manual-axes
    # type as the loop outputs (fresh zeros would be "unvarying" and trip
    # shard_map's carry check)
    qt = qg.transpose(0, 2, 3, 1, 4).astype(jnp.float32)  # [B, KV, G, Sq, hd]
    o0 = qt * 0.0
    m0 = qt[..., 0] * 0.0 + _NEG
    l0 = qt[..., 0] * 0.0

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        o, m, l, k_cur, v_cur = carry
        src = (idx - i) % n  # ring: who this K/V block belongs to
        scores = (
            jnp.einsum("bskgh,bckh->bkgsc", qg, k_cur,
                       preferred_element_type=jnp.float32)
            * scale
        )
        k_pos = src * Sq + jnp.arange(k_cur.shape[1])
        allowed = None
        if causal:
            allowed = jnp.broadcast_to(
                q_pos[:, None] >= k_pos[None, :], (B, Sq, k_cur.shape[1])
            )
        if pad_lens is not None:
            valid = k_pos[None, None, :] >= pad_lens[:, None, None]
            allowed = valid if allowed is None else allowed & valid
        if allowed is not None:
            # scores [B, KV, G, Sq, Sk]; allowed [B, Sq, Sk]
            scores = jnp.where(allowed[:, None, None], scores, _NEG)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        if allowed is not None:
            # a fully-masked block would otherwise give exp(_NEG-_NEG)=1
            p = jnp.where(allowed[:, None, None], p, 0.0)
        l = l * correction + jnp.sum(p, axis=-1)
        o = o * correction[..., None] + jnp.einsum(
            "bkgsc,bckh->bkgsh", p, v_cur.astype(jnp.float32)
        )
        def rotate(kv):
            k_c, v_c = kv
            return (
                jax.lax.ppermute(k_c, axis_name, perm),
                jax.lax.ppermute(v_c, axis_name, perm),
            )

        # the last block's rotation would be discarded — skip the transfer
        # (predicate is uniform across devices, so cond is collective-safe)
        k_next, v_next = jax.lax.cond(
            i < n - 1, rotate, lambda kv: kv, (k_cur, v_cur)
        )
        return o, m_new, l, k_next, v_next

    o, m, l, _, _ = jax.lax.fori_loop(0, n, step, (o0, m0, l0, kb, vb))
    out = o / jnp.maximum(l[..., None], 1e-30)
    # [B, KV, G, Sq, hd] -> [B, Sq, H, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(qb.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_per_kv: int,
    *,
    mesh: Mesh,
    causal: bool = True,
    pad_lens: jax.Array | None = None,
):
    """Drop-in attention_fn for models.llama.forward_train: global views
    [B, S, H|KV, hd], sequence dim sharded over the `seq` axis. ``pad_lens``
    [B] (engine-style left padding) masks the pad slots of each row so the
    long-context prefill can reuse the ring."""
    spec_q = P(AXES.data, AXES.seq, AXES.model, None)
    spec_kv = P(AXES.data, AXES.seq, AXES.model, None)

    if pad_lens is None:
        fn = shard_map(
            partial(
                _ring_local,
                pad_lens=None,
                q_per_kv=q_per_kv,
                axis_name=AXES.seq,
                causal=causal,
            ),
            mesh=mesh,
            in_specs=(spec_q, spec_kv, spec_kv),
            out_specs=spec_q,
        )
        return fn(q, k, v)
    fn = shard_map(
        partial(
            _ring_local, q_per_kv=q_per_kv, axis_name=AXES.seq, causal=causal
        ),
        mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv, P(AXES.data)),
        out_specs=spec_q,
    )
    return fn(q, k, v, pad_lens)
