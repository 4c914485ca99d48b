"""The plain reference of the decoder both configurations run through.

Qwen3 and Phi-4 share one published description: pre-norm residual blocks,
RMSNorm, rotary positions (rotate-half pairing, base ``rope_theta``),
grouped-query causal attention, a SwiGLU feed-forward and an output head
that is untied from the embedding. Qwen3 adds an RMSNorm over each head's
query and key before the rotation (``qk_norm``). Written from that
description in straightforward ``jax.numpy`` and float32, with no kernel,
cache, batching trick or quantization: the whole sequence at once.

It reads the program's parameter tree (stacked ``[L, ...]`` leaves; int8
leaves as ``{"q", "s"}`` are multiplied out first) because the weights have
to be the same, and nothing else of the program. Departures from the
published checkpoints: fused ``qkv``/``gate_up`` projections are held
unfused (same operations), and weights are random.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dense(leaf, contract_axes: tuple[int, ...]) -> jax.Array:
    """A float32 weight from a plain or an int8 ``{"q", "s"}`` leaf."""
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    s = leaf["s"]
    for a in sorted(contract_axes):
        s = jnp.expand_dims(s, a)
    return leaf["q"].astype(jnp.float32) * s


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rotate(x, theta):
    """x [S, H, hd]: rotate pairs (i, i + hd/2) by position * theta^(-2i/hd)."""
    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _rows(leaf, tokens) -> jax.Array:
    """Rows ``tokens`` of the embedding as float32, gathered before an int8
    leaf is multiplied out (the whole table in float32 is 2.5 GB)."""
    if not isinstance(leaf, dict):
        return leaf[tokens].astype(jnp.float32)
    return leaf["q"][tokens].astype(jnp.float32) * leaf["s"][tokens][:, None]


def logits(params: dict, tokens, *, n_heads: int, n_kv_heads: int,
           rope_theta: float, eps: float, qk_norm: bool,
           last: int | None = None) -> jax.Array:
    """Logits [S, vocab] of one sequence of token ids [S], float32; with
    ``last`` only those of the last ``last`` positions."""
    with jax.default_matmul_precision("highest"):
        lp = params["layers"]
        n_layers = lp["attn_norm"].shape[0]
        x = _rows(params["embed"], tokens)
        s = x.shape[0]
        causal = jnp.tril(jnp.ones((s, s), bool))
        group = n_heads // n_kv_heads

        def layer(i, x):   # one loop body for every layer: compiles once
            w = jax.tree.map(lambda a: a[i], lp)
            h = _rmsnorm(x, w["attn_norm"], eps)
            q = jnp.einsum("sd,dhk->shk", h, _dense(w["wq"], (0,)))
            k = jnp.einsum("sd,dhk->shk", h, _dense(w["wk"], (0,)))
            v = jnp.einsum("sd,dhk->shk", h, _dense(w["wv"], (0,)))
            if qk_norm:
                q = _rmsnorm(q, w["q_norm"], eps)
                k = _rmsnorm(k, w["k_norm"], eps)
            q, k = _rotate(q, rope_theta), _rotate(k, rope_theta)
            k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
            score = jnp.einsum("shk,thk->hst", q, k) / jnp.sqrt(q.shape[-1])
            prob = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), -1)
            ctx = jnp.einsum("hst,thk->shk", prob, v)
            x = x + jnp.einsum("shk,hkd->sd", ctx, _dense(w["wo"], (0, 1)))
            h = _rmsnorm(x, w["mlp_norm"], eps)
            gate = (jax.nn.silu(h @ _dense(w["w_gate"], (0,)))
                    * (h @ _dense(w["w_up"], (0,))))
            return x + gate @ _dense(w["w_down"], (0,))

        x = jax.lax.fori_loop(0, n_layers, layer, x)
        if last is not None:
            x = x[-last:]
        x = _rmsnorm(x, params["final_norm"], eps)
        head = (_dense(params["lm_head"], (0,)) if "lm_head" in params
                else _dense(params["embed"], (1,)).T)
        return x @ head
