"""The plain reference of SmallThinker's decoder: grouped-query attention
with rotary sliding-window layers and position-free global layers, sparse
ReGLU experts whose router reads the layer's input.

Written from the published ``config.json``
(PowerInfer/SmallThinker-21BA3B-Instruct) and its description in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision:
the whole sequence at once, no kernel, no cache, no batching, no
quantization, the experts by a plain loop over all of them and the heads by
a plain loop too (computed in such blocks the 5,008 tokens of the chip's
parity check fit beside the engine at the published widths: a head's scores
are 100 MB, an expert's weights 24 MB). It reads the program's parameter
tree (stacked ``[L, ...]`` leaves under ``layers``; int8 ``{"q", "s"}``
leaves are multiplied out first) because the weights have to be the same,
and nothing else of the program.

The layer, for layer ``l`` with input ``x`` [T, D]:

    s      = x W_r                      float32; the router reads x itself,
                                        before any norm
    ids    = top_k(s);  w = softmax(s[ids])   softmax over the picked logits
                                        alone (moe_primary_router_apply_softmax;
                                        norm_topk_prob is then the identity)
    h      = rmsnorm(x)
    q,k,v  = h W_q, h W_k, h W_v        no bias, no QK-norm
    q,k    = rope(q, k; theta, all of head_dim, half-split pairs)
                                        if rope_layout[l] == 1, else unchanged
    a      = softmax(q k^T / sqrt(head_dim) + mask) v     GQA; mask: j <= i,
                                        and if sliding_window_layout[l] == 1
                                        also i - j < sliding_window_size
    x'     = x + a W_o
    h'     = rmsnorm(x')
    y      = sum_{e in ids} w_e (relu(h' G_e) * (h' U_e)) D_e
    out    = x' + y

Every layer is an expert layer; embedding and head are untied; final
RMSNorm. Departures from the published model, the same as the program's:
rotate-half RoPE pairing as the rest of the repo; no attention bias, no
QK-norm and no expert bias (the source states none); the description's
"secondary experts" have no key in ``config.json`` and are not built;
weights are random.

``faults`` names departures the parity check has to catch, one line each:
``no_window`` (window layers attend everything causal), ``rope_everywhere``
(rotary on the global layers too), ``router_after_norm`` and
``router_after_attention`` (the router fed ``h`` or ``h'``),
``softmax_over_all`` (weights = the picks' shares of a softmax over all
experts) and ``silu`` (SwiGLU for ReGLU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("no_window", "rope_everywhere", "router_after_norm",
          "router_after_attention", "softmax_over_all", "silu")


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


def _rows(leaf, tokens) -> jax.Array:
    if not isinstance(leaf, dict):
        return leaf[tokens].astype(jnp.float32)
    return leaf["q"][tokens].astype(jnp.float32) * leaf["s"][tokens][:, None]


def _rotate(x, theta: float):
    """x [S, H, d]: rotate pairs (i, i + d/2) by position * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def route(logits, top_k: int, over_all: bool = False, among=None):
    """logits [S, E] -> (expert ids [S, top_k], weights): the largest
    logits (``among`` [S, E] bool: of those experts alone), softmax over
    the picked ones alone."""
    ranked = logits if among is None else jnp.where(among, logits, -jnp.inf)
    picked, ids = jax.lax.top_k(ranked, top_k)
    if over_all:
        return ids, jnp.take_along_axis(jax.nn.softmax(logits, -1), ids, 1)
    return ids, jax.nn.softmax(picked, -1)


def ties_broken_their_way(logits, theirs, tie_band: float):
    """Which rows of ``theirs`` [R, k] (another implementation's picks) are
    a rightful top-k of ``logits`` [R, E] once ties are allowed: a top-k is
    not a continuous function, and where two experts score within the
    rounding of the other side's arithmetic both picks are right. A row is
    rightful when its picks are distinct and every one of them scores
    within ``tie_band`` of the best expert left out: they are THE top-k of
    logits that each moved by less than half the band. ``tie_band`` 0
    admits only the reference's own picks."""
    picked = (theirs[:, :, None] == jnp.arange(logits.shape[1])).any(1)
    worst_pick = jnp.where(picked, logits, jnp.inf).min(-1)
    best_left = jnp.where(picked, -jnp.inf, logits).max(-1)
    return ((picked.sum(-1) == theirs.shape[1])
            & (worst_pick >= best_left - tie_band))


def attention(x, w: dict, sizes: dict, windowed, rotary, faults=()):
    """x [S, D] -> (x + Attn(RMSNorm(x)), the normed input RMSNorm(x), this
    layer's keys and values [S, KV, hd] as a cache would keep them).
    ``windowed`` and ``rotary`` are the layer's entries of the two layouts
    (traced scalars)."""
    S = x.shape[0]
    kv, theta = sizes["num_key_value_heads"], sizes["rope_theta"]
    h = _rmsnorm(x, w["attn_norm"], sizes["rms_norm_eps"])
    q = jnp.einsum("sd,dhk->shk", h, _dense(w["wq"], (0,)))
    k = jnp.einsum("sd,dhk->shk", h, _dense(w["wk"], (0,)))
    v = jnp.einsum("sd,dhk->shk", h, _dense(w["wv"], (0,)))
    turn = jnp.asarray(True) if "rope_everywhere" in faults else rotary > 0
    q = jnp.where(turn, _rotate(q, theta), q)
    k = jnp.where(turn, _rotate(k, theta), k)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = j <= i
    if "no_window" not in faults:
        mask = mask & ((windowed == 0) | (i - j < sizes["sliding_window_size"]))
    group = q.shape[1] // kv

    def one_head(args):
        qh, head = args                               # [S, hd], its index
        kh, vh = k[:, head // group], v[:, head // group]
        score = qh @ kh.T / jnp.sqrt(jnp.float32(qh.shape[-1]))
        return jax.nn.softmax(jnp.where(mask, score, -jnp.inf), -1) @ vh

    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 jnp.arange(q.shape[1])))   # [H, S, hd]
    out = x + jnp.einsum("hsk,hkd->sd", ctx, _dense(w["wo"], (0, 1)))
    return out, h, (k, v)


def expert_ffn(h, ids, weights, w: dict, faults=()):
    """sum over each token's picks of w_e ReGLU_e(h), by a plain loop over
    the experts in ``w``."""
    act = jax.nn.silu if "silu" in faults else jax.nn.relu
    held = jax.tree.leaves(w["we_gate"])[0].shape[0]

    def one_expert(e, y):
        ew = jax.tree.map(lambda a: a[e], {
            k: w[k] for k in ("we_gate", "we_up", "we_down")})
        # this expert's weight for each token: its pick's, else 0
        mine = jnp.sum(jnp.where(ids == e, weights, 0.0), -1)
        out = (act(h @ _dense(ew["we_gate"], (0,)))
               * (h @ _dense(ew["we_up"], (0,)))) @ _dense(ew["we_down"], (0,))
        return y + mine[:, None] * out

    return jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(h))


def forward(params: dict, tokens, sizes: dict, *, last: int | None = None,
            theirs=None, tie_band: float = 0.0, faults=()) -> dict:
    """One sequence of token ids [S] through the decoder, float32:
    ``logits`` [S, vocab] (with ``last`` only those of the last ``last``
    positions), ``k`` and ``v`` [L, S, KV, hd] (what each layer's cache
    would hold of every token) and ``took``. ``theirs`` [L, R, k] are
    another implementation's picks for the last R tokens: each layer takes
    them where they are a rightful top-k of its own logits within
    ``tie_band`` (``ties_broken_their_way``), and ``took`` [L, R] says
    where it did. ``sizes`` holds the published ``config.json`` keys
    (``num_key_value_heads``, ``rope_theta``, ``rms_norm_eps``,
    ``sliding_window_size``, ``moe_num_active_primary_experts``) and, for as
    many layers as the tree has, ``sliding_window_layout`` and
    ``rope_layout``."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    top_k, eps = sizes["moe_num_active_primary_experts"], sizes["rms_norm_eps"]
    n_layers = params["layers"]["attn_norm"].shape[0]
    if theirs is None:
        theirs = jnp.zeros((n_layers, 0, top_k), jnp.int32)
    with jax.default_matmul_precision("highest"):

        def layer(x, xs):
            w, windowed, rotary, picks = xs
            router = w["router"].astype(jnp.float32)
            x_out, h, kv = attention(x, w, sizes, windowed, rotary, faults)
            h2 = _rmsnorm(x_out, w["mlp_norm"], eps)
            seen = {"router_after_norm": h,
                    "router_after_attention": h2}
            source = next((seen[f] for f in faults if f in seen), x)
            logits = source @ router
            ids, weights = route(logits, top_k, "softmax_over_all" in faults)
            R = picks.shape[0]
            took = jnp.zeros((0,), bool)
            if R:
                tail = logits[-R:]
                took = ties_broken_their_way(tail, picks, tie_band)
                among = (picks[:, :, None] == jnp.arange(tail.shape[1])).any(1)
                their_ids, their_weights = route(
                    tail, top_k, "softmax_over_all" in faults, among)
                ids = ids.at[-R:].set(
                    jnp.where(took[:, None], their_ids, ids[-R:]))
                weights = weights.at[-R:].set(
                    jnp.where(took[:, None], their_weights, weights[-R:]))
            return x_out + expert_ffn(h2, ids, weights, w, faults), (kv, took)

        x = _rows(params["embed"], tokens)
        x, ((k, v), took) = jax.lax.scan(layer, x, (
            params["layers"],
            jnp.asarray(sizes["sliding_window_layout"][:n_layers], jnp.int32),
            jnp.asarray(sizes["rope_layout"][:n_layers], jnp.int32), theirs))
        x = _rmsnorm(x if last is None else x[-last:], params["final_norm"],
                     eps)
        return {"logits": x @ _dense(params["lm_head"], (0,)),
                "k": k, "v": v, "took": took}


def logits(params: dict, tokens, sizes: dict, *, last: int | None = None,
           faults=()) -> jax.Array:
    """``forward``'s logits alone."""
    return forward(params, tokens, sizes, last=last, faults=faults)["logits"]
