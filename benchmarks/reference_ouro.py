"""The plain reference of Ouro's decoder: a dense stack applied
``total_ut_steps`` times over the SAME weights, sandwich norms in every
layer, the final norm after every pass, and keys and values that belong to
a (pass, layer).

Written from the published ``config.json`` (ByteDance/Ouro-2.6B,
``model_type`` ``ouro``) and the model's public description (each mechanism
the config names by a number alone is under ``assumed`` in
``benchmarks/configs/ouro-2.6b-l12-int8.json``), in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision: the whole
sequence at once, no kernel, no cache, no batching, no quantization; the
passes, the layers and the heads by plain loops (a head's scores over the
7,008 tokens of the chip's parity check are 196 MB in float32: one head at
a time). It reads the program's parameter tree (``layers`` stacked on a
leading dim; int8 ``{"q", "s"}`` leaves are multiplied out first) because
the weights have to be the same, and nothing else of the program.

    h = E[token]
    for pass t = 0 .. T-1:
        for layer l = 0 .. L-1:
            a = Attn_l(rmsnorm(h; g1_l); t);   h = h + rmsnorm(a; g2_l)
            u = rmsnorm(h; g3_l)
            m = W_down_l (silu(W_gate_l u) * W_up_l u);  h = h + rmsnorm(m; g4_l)
        h = rmsnorm(h; g_final)        the SAME g_final; the normed stream
                                       enters pass t + 1
    logits = h W_head                  from the last pass's normed stream

``g1..g4`` are the tree's ``attn_norm``, ``post_attn_norm``, ``mlp_norm``,
``post_ffw_norm``; ``rmsnorm(x; g) = x * rsqrt(mean(x^2) + eps) * g``, plain
weight. Attention: q, k, v of 16 heads of 128 each, no bias, no QK norm;
rotary over all 128 dims of q and k at the TOKEN's position (the same in
every pass), rotate-half pairing as the rest of the repo; causal
``softmax(q k^T / sqrt(128)) v``; ``W_o``. Position ``i`` of pass ``t``
attends over the keys and values that pass ``t`` of layer ``l`` made for
positions ``<= i`` and never another pass's: they are what cache layer
``t * L + l`` would hold, and ``k`` / ``v`` hand them out under that index.

The exit gate (``exit_gate``: ``sigmoid(w . h_t + b)`` on a pass's normed
stream) decides where a token leaves the loop once the cumulative exit
probability reaches ``early_exit_threshold``; at the published threshold 1
that is the last pass for every token, so the gate changes no logit and is
not evaluated here; a threshold under 1 is refused.

``faults`` names departures the parity check has to tell from this model
(``FAULTS``), one line each: ``three_passes`` (T - 1 passes), ``one_pass``,
``norm_at_end_alone`` (no norm between passes: the un-normed stream is
handed on and the final norm applied once before the head — a norm computed
after each pass whose output only the head reads is the same function, so
the two are ONE fault), ``no_output_norms`` (g2 and g4 left out),
``output_norm_after_add`` (``h = rmsnorm(h + a; g2)``, and the same for the
feed-forward), ``norm_plus_one`` (every weight ``1 + g``), ``qk_norm`` (an
unweighted RMS norm over each head of q and k before the rotary),
``no_rotary``, ``rotary_rebased`` (pass ``t`` turns by position ``+ t * S``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("three_passes", "one_pass", "norm_at_end_alone", "no_output_norms",
          "output_norm_after_add", "norm_plus_one", "qk_norm", "no_rotary",
          "rotary_rebased")


def _dense(leaf, contract_axes: tuple[int, ...]) -> jax.Array:
    """A float32 weight from a plain or an int8 ``{"q", "s"}`` leaf."""
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    s = leaf["s"]
    for a in sorted(contract_axes):
        s = jnp.expand_dims(s, a)
    return leaf["q"].astype(jnp.float32) * s


def _rows(leaf, tokens) -> jax.Array:
    if not isinstance(leaf, dict):
        return leaf[tokens].astype(jnp.float32)
    return leaf["q"][tokens].astype(jnp.float32) * leaf["s"][tokens][:, None]


def _rmsnorm(x, w, eps, plus_one: bool = False):
    w = w.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w if plus_one else w)


def _rotate(x, positions, theta: float):
    """x [S, H, hd]: pairs (i, i + hd/2) turn by position * theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def layer(h, w: dict, sizes: dict, positions, faults=()):
    """One layer's one pass: h [S, D] -> (h, this pass's keys and values of
    the layer [S, KV, hd], as a cache would keep them)."""
    eps, P1 = sizes["rms_norm_eps"], "norm_plus_one" in faults
    S = h.shape[0]
    x = _rmsnorm(h, w["attn_norm"], eps, P1)
    q = jnp.einsum("sd,dhk->shk", x, _dense(w["wq"], (0,)))
    k = jnp.einsum("sd,dhk->shk", x, _dense(w["wk"], (0,)))
    v = jnp.einsum("sd,dhk->shk", x, _dense(w["wv"], (0,)))
    if "qk_norm" in faults:
        q = _rmsnorm(q, jnp.ones(q.shape[-1]), eps)
        k = _rmsnorm(k, jnp.ones(k.shape[-1]), eps)
    if "no_rotary" not in faults:
        q = _rotate(q, positions, sizes["rope_theta"])
        k = _rotate(k, positions, sizes["rope_theta"])
    group = q.shape[1] // k.shape[1]
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def one_head(args):
        qh, head = args                               # [S, hd], its index
        kh, vh = k[:, head // group], v[:, head // group]
        score = qh @ kh.T / jnp.sqrt(jnp.float32(qh.shape[-1]))
        return jax.nn.softmax(jnp.where(mask, score, -jnp.inf), -1) @ vh

    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 jnp.arange(q.shape[1])))   # [H, S, hd]
    a = jnp.einsum("hsk,hkd->sd", ctx, _dense(w["wo"], (0, 1)))

    def add(h, out, g):
        if "no_output_norms" in faults:
            return h + out
        if "output_norm_after_add" in faults:
            return _rmsnorm(h + out, g, eps, P1)
        return h + _rmsnorm(out, g, eps, P1)

    h = add(h, a, w["post_attn_norm"])
    u = _rmsnorm(h, w["mlp_norm"], eps, P1)
    m = (jax.nn.silu(u @ _dense(w["w_gate"], (0,)))
         * (u @ _dense(w["w_up"], (0,)))) @ _dense(w["w_down"], (0,))
    return add(h, m, w["post_ffw_norm"]), (k, v)


def forward(params: dict, tokens, sizes: dict, *, last: int | None = None,
            keep=None, faults=()) -> dict:
    """One sequence of token ids [S] through the looped decoder, float32:
    ``logits`` [S, vocab] (with ``last`` only those of the last ``last``
    positions) and ``k``, ``v`` ``{cache layer: [S, KV, hd]}`` — what cache
    layer ``t * L + l`` would hold of every token — for the cache layers in
    ``keep`` (None: every one; at the published widths 48 of them over 7k
    tokens are 5.5 GB). ``sizes`` holds the published ``config.json`` keys
    (``total_ut_steps``, ``early_exit_threshold``, ``rms_norm_eps``,
    ``rope_theta``); the layers are as many as the tree has."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    if sizes["early_exit_threshold"] < 1:
        raise NotImplementedError(
            "early_exit_threshold under 1: tokens leaving the loop at "
            "different passes are not computed by this reference")
    eps, P1 = sizes["rms_norm_eps"], "norm_plus_one" in faults
    L = params["layers"]["attn_norm"].shape[0]
    T = sizes["total_ut_steps"]
    passes = (1 if "one_pass" in faults
              else T - 1 if "three_passes" in faults else T)
    S = tokens.shape[0]
    ks, vs = {}, {}
    with jax.default_matmul_precision("highest"):
        h = _rows(params["embed"], tokens)
        for t in range(passes):
            positions = jnp.arange(S) + (
                t * S if "rotary_rebased" in faults else 0)
            for l in range(L):
                w = jax.tree.map(lambda a: a[l], params["layers"])
                h, (k, v) = layer(h, w, sizes, positions, faults)
                if keep is None or t * L + l in keep:
                    ks[t * L + l], vs[t * L + l] = k, v
            if "norm_at_end_alone" not in faults or t == passes - 1:
                h = _rmsnorm(h, params["final_norm"], eps, P1)
        h = h if last is None else h[-last:]
        return {"logits": h @ _dense(params["lm_head"], (0,)),
                "k": ks, "v": vs}


def logits(params: dict, tokens, sizes: dict, *, last: int | None = None,
           faults=()) -> jax.Array:
    """``forward``'s logits alone."""
    return forward(params, tokens, sizes, last=last, keep=(),
                   faults=faults)["logits"]
