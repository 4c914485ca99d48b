"""Token sampling under jit: greedy, temperature, top-k, top-p.

Greedy matches the reference's do_sample=False baseline
(runners/run_summarization.py:44); Ollama's default sampling is approximated
by temperature/top-k/top-p knobs (GenerationConfig).

Also home to the speculative-decoding acceptance rule
(:func:`draft_acceptance_rows`): the verify step (backend/engine.py spec
path) scores k+1 positions in one forward and this module decides, per row,
how many drafted tokens the model keeps — exact argmax matching for greedy
(bit-identical to plain decode by construction), rejection-style acceptance
against the filtered distribution for temperature sampling (the drafter is
a deterministic point-mass proposal, so accept-with-prob-p / resample-from-
residual is the lossless scheme of arXiv:2304.04487 §2.2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def filter_logits(
    logits: jax.Array,      # [..., V] float32
    temperature: float,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jax.Array:
    """Temperature-scale then apply top-k / top-p cutoffs (blocked ids get
    float32 min). ONE copy of the filtering algebra shared by sample_logits
    and the speculative acceptance rule — the two must agree on what
    distribution "the model would sample from" means. Caller guarantees
    temperature > 0."""
    logits = logits / jnp.float32(temperature)

    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, jnp.finfo(jnp.float32).min, logits)

    if top_p < 1.0:
        sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob > top_p; keep at least one token
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(
            sorted_logits, cutoff_idx[..., None], axis=-1
        )
        logits = jnp.where(logits < cutoff, jnp.finfo(jnp.float32).min, logits)

    return logits


def sample_logits(
    logits: jax.Array,      # [B, V] float32
    key: jax.Array,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jax.Array:
    """Returns sampled token ids [B]. temperature==0 -> argmax (greedy)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = filter_logits(logits, temperature, top_k, top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def sample_logits_rows(
    logits: jax.Array,      # [B, V] float32
    keys: jax.Array,        # [B] PRNG keys, one per row
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jax.Array:
    """Per-row-keyed sampling: row i draws only from keys[i], so a row's
    sampled stream is invariant to its position in the batch. This is what
    lets the slot loop seat a request in any row, beside any others, without
    changing its output (engine.py derives keys[i] from (seed, row_uid,
    step) — counter-based, like per-request generators in
    continuous-batching servers)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.vmap(
        lambda l, k: sample_logits(l[None], k, temperature, top_k, top_p)[0]
    )(logits, keys)


def draft_acceptance_rows(
    logits: jax.Array,      # [B, K+1, V] float32 — verify-step logits
    drafts: jax.Array,      # [B, K] int32 — proposed continuation tokens
    n_draft: jax.Array,     # [B] int32 — how many of drafts are real
    keys: jax.Array,        # [B, K+1] PRNG keys (ignored for greedy)
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Decide per row how many drafted tokens survive verification.

    Position i's logits are conditioned on the current token plus drafts
    d_1..d_i, so logits[:, i] IS the model's next-token distribution after
    accepting i drafts. Returns ``(m [B], next_token [B])``: the row keeps
    drafts d_1..d_m and ``next_token`` is the model's own token after them —
    always well-defined, so every verify step retires at least one token.

    Greedy: accept while argmax(logits[:, i-1]) == d_i (exact prefix match
    — the spec stream is provably identical to plain greedy decode).
    Sampled: accept d_i with probability p_i-1(d_i) under the filtered
    distribution; on rejection sample from the residual (p with the
    rejected draft masked out, renormalized — exact for a point-mass
    proposal); when every draft survives, sample position m freely."""
    K = drafts.shape[1]
    real = jnp.arange(K)[None, :] < n_draft[:, None]          # [B, K]

    if temperature <= 0.0:
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # [B, K+1]
        ok = (g[:, :K] == drafts) & real
        m = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
        nxt = jnp.take_along_axis(g, m[:, None], axis=1)[:, 0]
        return m.astype(jnp.int32), nxt

    f = filter_logits(logits, temperature, top_k, top_p)      # [B, K+1, V]
    probs = jax.nn.softmax(f, axis=-1)
    p_draft = jnp.take_along_axis(
        probs[:, :K], drafts[..., None], axis=-1
    )[..., 0]                                                 # [B, K]
    u = jax.vmap(jax.vmap(lambda k: jax.random.uniform(jax.random.fold_in(k, 0))))(
        keys[:, :K]
    )
    ok = (u < p_draft) & real
    m = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1).astype(jnp.int32)

    # candidate "next" tokens at EVERY position, gathered at m afterwards:
    # free sample (used when all real drafts survived) and residual sample
    # (used at the rejection point — the rejected draft is excluded)
    free = jax.vmap(
        jax.vmap(
            lambda l, k: jax.random.categorical(jax.random.fold_in(k, 2), l)
        )
    )(f, keys).astype(jnp.int32)                              # [B, K+1]
    neg = jnp.finfo(jnp.float32).min
    f_resid = jnp.where(
        jax.nn.one_hot(drafts, f.shape[-1], dtype=bool), neg, f[:, :K]
    )
    resid = jax.vmap(
        jax.vmap(
            lambda l, k: jax.random.categorical(jax.random.fold_in(k, 1), l)
        )
    )(f_resid, keys[:, :K]).astype(jnp.int32)                 # [B, K]
    resid = jnp.concatenate([resid, free[:, -1:]], axis=1)    # pad pos K
    rejected = m < n_draft  # m == n_draft means the chain never broke
    nxt = jnp.where(
        rejected,
        jnp.take_along_axis(resid, m[:, None], axis=1)[:, 0],
        jnp.take_along_axis(free, m[:, None], axis=1)[:, 0],
    )
    return m, nxt
