"""The plain reference of Laguna's decoder: grouped-query attention whose
query heads differ by layer kind, a per-head output gate, YaRN partial
rotary on full layers and plain rotary on sliding ones, a leading dense
layer and sparse SwiGLU experts with a shared one.

Written from the published ``config.json`` (poolside/Laguna-S-2.1) with the
family's conventions where it states a mechanism by name alone (each under
``assumed`` in ``benchmarks/configs/laguna-s-2.1-l5-int8.json``), in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision:
the whole sequence at once, no kernel, no cache, no batching, no
quantization, one layer kind a function, the layers by a plain loop, the
heads by a plain loop and the experts by a plain loop that multiplies ONE
expert's weights out at a time (a layer's 256 in float32 would be 9.7 GB:
the pass has to fit beside 10.74 GB of weights; a head's scores over the
5,008 tokens of the chip's parity check are 100 MB). It reads the program's
parameter tree (``dense``, ``full``, ``sliding`` and ``layers``, each
stacked on a leading dim; int8 ``{"q", "s"}`` leaves are multiplied out
first) because the weights have to be the same, and nothing else of the
program.

The layer, for layer ``l`` with input ``x`` [T, D] and ``H_l`` query heads
(the width of ITS ``W_q``: 48 on full layers, 72 on sliding ones):

    h      = rmsnorm(x)
    q,k,v  = h W_q, h W_k, h W_v        [H_l | 8, 128]; no bias, no QK-norm
    full:    q,k = rope(first 64 dims; YaRN theta 500,000 over those 64,
                        factor 128, original 8,192, ramp beta 32 .. 1;
                        cos, sin x attention_factor), other 64 dims pass
    sliding: q,k = rope(all 128 dims; theta 10,000)
    a      = softmax(q k^T / sqrt(128) + mask) v    GQA; mask: j <= i, and
                                        on sliding layers also i - j < 512
    g      = sigmoid(h W_g)             [H_l]: one scalar a head and token
    x'     = x + (g * a) W_o            each head scaled before W_o
    h'     = rmsnorm(x')
    dense (layer 0):  out = x' + (silu(h' G) * (h' U)) D          12,288
    sparse: s   = softmax(h' W_r)       float32, over all 256
            ids = top_10(s);  w = s[ids] / sum(s[ids]) * 2.5
            out = x' + sum_e w_e (silu(h' G_e) * (h' U_e)) D_e
                     + (silu(h' G_s) * (h' U_s)) D_s              shared

Embedding and head untied; final RMSNorm. Departures from the published
model, the same as the program's: rotate-half RoPE pairing as the rest of
the repo; weights are random.

``faults`` names departures the parity check has to catch, one line each
(``FAULTS``): the gate left out or one scalar a token (the mean over a
token's heads), a sliding layer run on the first 48 heads' worth of its
weights, rotary over all dims on full layers, ``attention_factor`` left
out, the window ignored, theta swapped between the kinds, the scaling 2.5
left out, no renormalisation, the shared expert left out, and the six best
of the ten picks alone (at another k, the best six tenths).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

FAULTS = ("no_gate", "gate_a_token", "sliding_heads_as_full", "full_rotary",
          "no_attention_factor", "no_window", "theta_swapped", "no_scaling",
          "no_renorm", "no_shared", "top_6")


def _dense(leaf, contract_axes: tuple[int, ...]) -> jax.Array:
    """A float32 weight from a plain or an int8 ``{"q", "s"}`` leaf."""
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    s = leaf["s"]
    for a in sorted(contract_axes):
        s = jnp.expand_dims(s, a)
    return leaf["q"].astype(jnp.float32) * s


def _at(tree, *index):
    """``leaf[index]`` of every leaf: one layer of a stacked group, or one
    expert of one layer (cut out of the stack in one step, so that no whole
    layer of experts is ever copied)."""
    return jax.tree.map(lambda a: a[index], tree)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rows(leaf, tokens) -> jax.Array:
    if not isinstance(leaf, dict):
        return leaf[tokens].astype(jnp.float32)
    return leaf["q"][tokens].astype(jnp.float32) * leaf["s"][tokens][:, None]


def yarn_inv_freq(dim: int, rope: dict) -> jax.Array:
    """Inverse frequencies of ``dim`` rotated dims under a published
    ``rope_type: yarn`` group: theta^(-2i/dim), divided by ``factor`` below
    the correction dim of ``beta_fast`` rotations over the original length,
    unchanged above that of ``beta_slow``, a linear ramp between."""
    base, factor = rope["rope_theta"], rope["factor"]
    original = rope["original_max_position_embeddings"]
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def _rotate(x, inv_freq, scale: float = 1.0):
    """x [S, H, d]: the leading ``2 * len(inv_freq)`` dims of each head turn
    in pairs (i, i + len(inv_freq)) by position * inv_freq[i], cos and sin
    times ``scale``; the rest pass."""
    n = inv_freq.shape[0]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :] * scale, jnp.sin(ang)[:, None, :] * scale
    a, b = x[..., :n], x[..., n:2 * n]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., 2 * n:]], -1)


def _attention(x, w: dict, sizes: dict, rotate, window: int, faults=()):
    """What the two layer kinds share: x [S, D] -> (x + gated attention,
    this layer's keys and values [S, KV, hd] as a cache would keep them).
    ``rotate`` turns q and k; ``window`` 0 attends everything causal."""
    S = x.shape[0]
    kv = sizes["num_key_value_heads"]
    h = _rmsnorm(x, w["attn_norm"], sizes["rms_norm_eps"])
    q = jnp.einsum("sd,dhk->shk", h, _dense(w["wq"], (0,)))
    k = rotate(jnp.einsum("sd,dhk->shk", h, _dense(w["wk"], (0,))))
    v = jnp.einsum("sd,dhk->shk", h, _dense(w["wv"], (0,)))
    q = rotate(q)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = j <= i
    if window and "no_window" not in faults:
        mask = mask & (i - j < window)
    group = q.shape[1] // kv

    def one_head(args):
        qh, head = args                               # [S, hd], its index
        kh, vh = k[:, head // group], v[:, head // group]
        score = qh @ kh.T / jnp.sqrt(jnp.float32(qh.shape[-1]))
        return jax.nn.softmax(jnp.where(mask, score, -jnp.inf), -1) @ vh

    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 jnp.arange(q.shape[1])))   # [H, S, hd]
    gate = jax.nn.sigmoid(h @ w["attn_gate"].astype(jnp.float32))   # [S, H]
    if "gate_a_token" in faults:
        gate = jnp.broadcast_to(gate.mean(-1, keepdims=True), gate.shape)
    if "no_gate" not in faults:
        ctx = ctx * gate.T[:, :, None]
    return x + jnp.einsum("hsk,hkd->sd", ctx, _dense(w["wo"], (0, 1))), (k, v)


def full_attention(x, w: dict, sizes: dict, faults=()):
    """A full-attention layer: YaRN over the leading ``partial_rotary_factor``
    of each head, cos and sin times ``attention_factor``, every key causal."""
    rope = dict(sizes["rope_parameters"]["full_attention"])
    if "theta_swapped" in faults:
        rope["rope_theta"] = sizes["rope_parameters"]["sliding_attention"][
            "rope_theta"]
    share = 1.0 if "full_rotary" in faults else rope["partial_rotary_factor"]
    inv = yarn_inv_freq(int(sizes["head_dim"] * share), rope)
    scale = (1.0 if "no_attention_factor" in faults
             else rope["attention_factor"])
    return _attention(x, w, sizes, lambda t: _rotate(t, inv, scale), 0, faults)


def sliding_attention(x, w: dict, sizes: dict, faults=()):
    """A sliding-window layer: plain rotary over the whole head, the last
    ``sliding_window`` keys alone, and its own (larger) number of heads."""
    rope = dict(sizes["rope_parameters"]["sliding_attention"])
    if "theta_swapped" in faults:
        rope["rope_theta"] = sizes["rope_parameters"]["full_attention"][
            "rope_theta"]
    if "sliding_heads_as_full" in faults:
        n = sizes["num_attention_heads"]
        w = dict(w, wq=_dense(w["wq"], (0,))[:, :n],
                 attn_gate=w["attn_gate"][:, :n],
                 wo=_dense(w["wo"], (0, 1))[:n])
    dim = int(sizes["head_dim"] * rope["partial_rotary_factor"])
    inv = 1.0 / rope["rope_theta"] ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return _attention(x, w, sizes, lambda t: _rotate(t, inv),
                      sizes["sliding_window"], faults)


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _dense(gate, (0,))) * (h @ _dense(up, (0,)))) \
        @ _dense(down, (0,))


def route(logits, sizes: dict, faults=(), among=None):
    """logits [S, E] -> (expert ids [S, top_k], weights): softmax over all
    experts, its largest (``among`` [S, E] bool: of those experts alone),
    renormalised to one, times the scaling factor."""
    scores = jax.nn.softmax(logits, -1)
    ranked = scores if among is None else jnp.where(among, scores, -jnp.inf)
    picked, ids = jax.lax.top_k(ranked, sizes["num_experts_per_tok"])
    if "top_6" in faults:   # of ten; at another k the best six tenths
        keep = -(-6 * picked.shape[1] // 10)
        picked = jnp.where(jnp.arange(picked.shape[1]) < keep, picked, 0.0)
    if "no_renorm" not in faults:
        picked = picked / picked.sum(-1, keepdims=True)
    if "no_scaling" not in faults:
        picked = picked * sizes["moe_routed_scaling_factor"]
    return ids, picked


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


def expert_ffn(h, ids, weights, experts: dict, slot: int):
    """sum over each token's picks of w_e SwiGLU_e(h), by a plain loop over
    the experts of sparse layer ``slot``, one multiplied out at a time."""
    held = jax.tree.leaves(experts["we_gate"])[0].shape[1]

    def one_expert(e, y):
        ew = _at(experts, slot, e)
        # this expert's weight for each token: its pick's, else 0
        mine = jnp.sum(jnp.where(ids == e, weights, 0.0), -1)
        return y + mine[:, None] * swiglu(
            h, ew["we_gate"], ew["we_up"], ew["we_down"])

    return jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(h))


def sparse_ffn(x, w: dict, experts: dict, slot: int, sizes: dict, picks,
               tie_band: float, faults=()):
    """x [S, D] -> (x + routed experts + shared expert, where the last rows
    took ``picks`` [R, k])."""
    h = _rmsnorm(x, w["mlp_norm"], sizes["rms_norm_eps"])
    logits = h @ w["router"].astype(jnp.float32)
    ids, weights = route(logits, sizes, faults)
    R = picks.shape[0]
    took = jnp.zeros((0,), bool)
    if R:
        tail = logits[-R:]
        took = ties_broken_their_way(tail, picks, tie_band)
        among = (picks[:, :, None] == jnp.arange(tail.shape[1])).any(1)
        their_ids, their_weights = route(tail, sizes, faults, among)
        ids = ids.at[-R:].set(jnp.where(took[:, None], their_ids, ids[-R:]))
        weights = weights.at[-R:].set(
            jnp.where(took[:, None], their_weights, weights[-R:]))
    y = expert_ffn(h, ids, weights, experts, slot)
    if "no_shared" not in faults:
        y = y + swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    return x + y, took


def forward(params: dict, tokens, sizes: dict, *, last: int | None = None,
            theirs=None, tie_band: float = 0.0, faults=()) -> dict:
    """One sequence of token ids [S] through the decoder, float32:
    ``logits`` [S, vocab] (with ``last`` only those of the last ``last``
    positions), ``k`` and ``v`` [L, S, KV, hd] (what each layer's cache
    would hold of every token) and ``took``. ``theirs`` [sparse layers, R,
    k] are another implementation's picks for the last R tokens: each
    sparse layer takes them where they are a rightful top-k of its own
    logits within ``tie_band`` (``ties_broken_their_way``), and ``took``
    [sparse layers, R] says where it did. ``sizes`` holds the published
    ``config.json`` keys (``num_key_value_heads``, ``num_attention_heads``,
    ``head_dim``, ``rms_norm_eps``, ``sliding_window``,
    ``num_experts_per_tok``, ``moe_routed_scaling_factor``,
    ``rope_parameters``) and, for as many layers as the tree has,
    ``layer_types`` and ``mlp_layer_types``."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    eps = sizes["rms_norm_eps"]
    n_dense = params["dense"]["attn_norm"].shape[0]
    n_sparse = params["layers"]["mlp_norm"].shape[0]
    kinds = sizes["layer_types"][:n_dense + n_sparse]
    if sizes["mlp_layer_types"][:n_dense + n_sparse] != \
            ["dense"] * n_dense + ["sparse"] * n_sparse:
        raise ValueError("the tree's dense layers are not mlp_layer_types'")
    if theirs is None:
        theirs = jnp.zeros((n_sparse, 0, sizes["num_experts_per_tok"]),
                           jnp.int32)
    experts = {n: params["layers"][n] for n in ("we_gate", "we_up", "we_down")}
    ffn = {n: w for n, w in params["layers"].items() if n not in experts}
    attend = {"full_attention": full_attention,
              "sliding_attention": sliding_attention}
    group = {"full_attention": "full", "sliding_attention": "sliding"}
    seen = {"full": 0, "sliding": 0}
    ks, vs, took = [], [], []
    with jax.default_matmul_precision("highest"):
        x = _rows(params["embed"], tokens)
        for l, kind in enumerate(kinds):
            if l < n_dense:
                w = _at(params["dense"], l)
                x, (k, v) = attend[kind](x, w, sizes, faults)
                x = x + swiglu(_rmsnorm(x, w["mlp_norm"], eps),
                               w["w_gate"], w["w_up"], w["w_down"])
            else:
                slot = l - n_dense
                w = _at(params[group[kind]], seen[group[kind]])
                seen[group[kind]] += 1
                x, (k, v) = attend[kind](x, w, sizes, faults)
                x, t = sparse_ffn(x, _at(ffn, slot), experts, slot, sizes,
                                  theirs[slot], tie_band, faults)
                took.append(t)
            ks.append(k)
            vs.append(v)
        x = _rmsnorm(x if last is None else x[-last:], params["final_norm"],
                     eps)
        return {"logits": x @ _dense(params["lm_head"], (0,)),
                "k": jnp.stack(ks), "v": jnp.stack(vs),
                "took": jnp.stack(took) if took else jnp.zeros((0, 0), bool)}


def logits(params: dict, tokens, sizes: dict, *, last: int | None = None,
           faults=()) -> jax.Array:
    """``forward``'s logits alone."""
    return forward(params, tokens, sizes, last=last, faults=faults)["logits"]
