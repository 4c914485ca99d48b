"""The plain reference of LFM2-MoE's decoder: gated short-convolution
layers beside a few rotary, QK-normed grouped-query attention layers, a
dense feed-forward on the leading layers and sparse gated experts chosen by
sigmoid score + bias after them.

Written from the published ``config.json`` (LiquidAI/LFM2-8B-A1B) and the
model's public modelling code in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision: one sequence at a time, the whole sequence
at once, no kernel, no cache, no chunking, no batching, no quantization,
the convolution as an explicit sum over positions ``t-2 .. t``, the layers
by a plain loop, the heads by a plain loop and the experts by a plain loop
that multiplies ONE expert's weights out at a time (a layer's 32 in float32
would be 1.4 GB, the model's 31 GB: the pass has to fit beside 8.4 GB of
int8 weights). It reads the program's parameter tree (``conv``, ``attn``,
``dense`` and ``layers`` stacks, each on a leading dim of its own; int8
``{"q", "s"}`` leaves are multiplied out first) because the weights have to
be the same, and nothing else of the program.

For layer ``l`` with input ``h`` [T, D] (``eps`` = ``norm_eps``):

    h0     = E[token]
    h      = h + Op_l(rmsnorm(h; g_op_l))     by layer_types[l]
    h      = h + FF_l(rmsnorm(h; g_ff_l))     dense for l < num_dense_layers
    logits = rmsnorm(h_L; g_out) E^T          tied

    conv, the gated short convolution (K = conv_L_cache = 3):
    b, c, x = u W_b, u W_c, u W_x    in_proj's three parts, in this order
    y_t    = b_t * x_t
    z_t    = sum_{j=0..K-1} w[:, j] * y_{t-(K-1)+j}
                                     depth-wise, causal, zeros before t=0,
                                     NO bias, NO activation
    Op     = (c * z) W_out
    what a layer keeps between steps: y_{t-1}, y_{t-2}

    full_attention:
    q,k,v  = u W_q, u W_k, u W_v     [32 | 8, 64]; no bias
    q, k   = rmsnorm(q; g_q), rmsnorm(k; g_k)   over each head's dims,
                                     BEFORE the rotary
    q, k   = rotate(q), rotate(k)    all of head_dim, half-split
    Op     = (softmax(q k^T / sqrt(64) + causal mask) v) W_o      GQA

    dense FF:   W_2 (silu(W_1 u) * W_3 u)
    sparse FF:
    s      = sigmoid(u W_r)          float32, over all experts
    ids    = top_k(s + b_e)          b_e = expert_bias: the choice
    w      = s[ids] / (sum(s[ids]) + 1e-6) * routed_scaling_factor
    FF     = sum_e w_e W_2e (silu(W_1e u) * W_3e u)

``assumed`` in the configuration file lists what no key states.

``faults`` names departures the parity check has to catch, one line each
(``FAULTS``): a silu on the convolution and a bias on it (Mamba's), the
convolution over ``x`` with ``b`` gating after it (the tail holding ``x``),
``c`` for ``b`` (in_proj's parts in another order), softmax for sigmoid,
the bias left out, the bias in the weight, no renormalisation, the rotary
before the QK-norm, and experts that drop picks past a capacity. The
``1e-6`` under the weights' sum left out is NOT listed: it moves a weight
by a part in a million of itself, which no tolerance can see.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("conv_silu", "conv_bias", "conv_of_x", "gate_order",
          "softmax_router", "no_bias", "bias_in_weight", "no_renorm",
          "rope_before_norm", "capacity")


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


def _rotate(x, theta: float):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def conv_operator(u, w: dict, sizes: dict, faults=(), keep: int = 1):
    """u [S, D] (normed) -> ((c * z) W_out [S, D], the tail after each of
    the last ``keep`` tokens [keep, K - 1, D]: what a layer would keep)."""
    S, K = u.shape[0], sizes["conv_L_cache"]
    b, c, x = (u @ _dense(w[part], (0,)) for part in ("in_b", "in_c", "in_x"))
    if "gate_order" in faults:
        b, c = c, b
    y = x if "conv_of_x" in faults else b * x
    taps = w["conv_w"].astype(jnp.float32)
    ext = jnp.concatenate([jnp.zeros((K - 1, y.shape[1])), y], 0)
    z = sum(taps[:, j] * ext[j:j + S] for j in range(K))
    if "conv_bias" in faults:
        z = z + taps[:, 0]
    if "conv_silu" in faults:
        z = jax.nn.silu(z)
    if "conv_of_x" in faults:
        z = b * z
    # after token t the layer keeps y_{t-K+2 .. t}: ext[t + 1 : t + K]
    tails = jnp.stack([ext[t + 1:t + K] for t in range(S - keep, S)])
    return (c * z) @ _dense(w["out_proj"], (0,)), tails


def attention_operator(u, w: dict, sizes: dict, faults=()):
    """u [S, D] (normed) -> (a W_o [S, D], this layer's keys and values
    [S, KV, hd] as a cache would keep them)."""
    S = u.shape[0]
    kv, eps = sizes["num_key_value_heads"], sizes["norm_eps"]
    theta = sizes["rope_theta"]
    q = jnp.einsum("sd,dhk->shk", u, _dense(w["wq"], (0,)))
    k = jnp.einsum("sd,dhk->shk", u, _dense(w["wk"], (0,)))
    v = jnp.einsum("sd,dhk->shk", u, _dense(w["wv"], (0,)))
    if "rope_before_norm" in faults:
        q = _rmsnorm(_rotate(q, theta), w["q_norm"], eps)
        k = _rmsnorm(_rotate(k, theta), w["k_norm"], eps)
    else:
        q = _rotate(_rmsnorm(q, w["q_norm"], eps), theta)
        k = _rotate(_rmsnorm(k, w["k_norm"], eps), theta)
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    group = q.shape[1] // kv

    def one_head(args):
        qh, head = args                               # [S, hd], its index
        kh, vh = k[:, head // group], v[:, head // group]
        score = qh @ kh.T / jnp.sqrt(jnp.float32(qh.shape[-1]))
        return jax.nn.softmax(jnp.where(mask, score, -jnp.inf), -1) @ vh

    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 jnp.arange(q.shape[1])))   # [H, S, hd]
    return jnp.einsum("hsk,hkd->sd", ctx, _dense(w["wo"], (0, 1))), (k, v)


def swiglu(u, gate, up, down):
    """down(silu(gate u) * up u)."""
    return (jax.nn.silu(u @ _dense(gate, (0,))) * (u @ _dense(up, (0,)))
            ) @ _dense(down, (0,))


def ranking(logits, bias, faults=()):
    """logits [S, E] -> (scores, what the top-k is taken of): sigmoid
    scores, ranked by score + bias."""
    scores = (jax.nn.softmax(logits, -1) if "softmax_router" in faults
              else jax.nn.sigmoid(logits))
    return scores, (scores if "no_bias" in faults else scores + bias)


def route(logits, bias, sizes: dict, faults=(), among=None):
    """logits [S, E] -> (expert ids [S, top_k], weights): sigmoid scores,
    the largest of score + bias (``among`` [S, E] bool: of those experts
    alone), the picked scores without the bias over their sum + 1e-6,
    times the scaling factor."""
    scores, ranked = ranking(logits, bias, faults)
    if among is not None:
        ranked = jnp.where(among, ranked, -jnp.inf)
    _, ids = jax.lax.top_k(ranked, sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(
        ranked if "bias_in_weight" in faults else scores, ids, -1)
    if "no_renorm" not in faults:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    return ids, picked * sizes["routed_scaling_factor"]


def ties_broken_their_way(ranked, theirs, tie_band: float):
    """Which rows of ``theirs`` [R, k] (another implementation's picks) are
    a rightful top-k of ``ranked`` [R, E] (score + bias) once ties are
    allowed: a top-k is not a continuous function, and where two experts
    rank within the rounding of the other side's arithmetic both picks are
    right. A row is rightful when its picks are distinct and every one of
    them ranks within ``tie_band`` of the best expert left out.
    ``tie_band`` 0 admits only the reference's own picks."""
    picked = (theirs[:, :, None] == jnp.arange(ranked.shape[1])).any(1)
    worst_pick = jnp.where(picked, ranked, jnp.inf).min(-1)
    best_left = jnp.where(picked, -jnp.inf, ranked).max(-1)
    return ((picked.sum(-1) == theirs.shape[1])
            & (worst_pick >= best_left - tie_band))


def expert_ffn(u, ids, weights, experts: dict, slot: int, offset: int,
               faults=()):
    """sum over each token's picks of w_e swiglu_e(u), by a plain loop over
    the experts the tree holds of sparse layer ``slot`` (expert ``offset``
    onwards), one multiplied out at a time."""
    held = jax.tree.leaves(experts["we_up"])[0].shape[1]
    # the fault: an expert takes its fair share of the slots and no more
    capacity = -(-ids.size // (held * 2))

    def one_expert(e, y):
        ew = _at(experts, slot, e)
        # this expert's weight for each token: its pick's, else 0
        mine = jnp.sum(jnp.where(ids == e + offset, weights, 0.0), -1)
        if "capacity" in faults:
            mine = jnp.where(jnp.cumsum(mine > 0) <= capacity, mine, 0.0)
        return y + mine[:, None] * swiglu(
            u, ew["we_gate"], ew["we_up"], ew["we_down"])

    return jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(u))


def sparse_ffn(u, w: dict, experts: dict, slot: int, sizes: dict, picks,
               tie_band: float, faults=()):
    """u [S, D] (normed) -> (the routed experts' sum, the picks [S, k],
    where the last rows took ``picks`` [R, k])."""
    logits = u @ w["router"].astype(jnp.float32)
    bias = w["expert_bias"].astype(jnp.float32)
    ids, weights = route(logits, bias, sizes, faults)
    R = picks.shape[0]
    took = jnp.zeros((0,), bool)
    if R:
        tail = logits[-R:]
        took = ties_broken_their_way(ranking(tail, bias, faults)[1], picks,
                                     tie_band)
        among = (picks[:, :, None] == jnp.arange(tail.shape[1])).any(1)
        their_ids, their_weights = route(tail, bias, sizes, faults, among)
        ids = ids.at[-R:].set(jnp.where(took[:, None], their_ids, ids[-R:]))
        weights = weights.at[-R:].set(
            jnp.where(took[:, None], their_weights, weights[-R:]))
    return (expert_ffn(u, ids, weights, experts, slot,
                       sizes.get("expert_offset", 0), faults), ids, took)


def forward(params: dict, tokens, sizes: dict, *, last: int | None = None,
            theirs=None, tie_band: float = 0.0, faults=()) -> dict:
    """One sequence of token ids [S] through the decoder, float32:
    ``logits`` [S, vocab] (with ``last`` only those of the last ``last``
    positions), ``k`` and ``v`` [attention layers, S, KV, hd], ``conv``
    [conv layers, K - 1, D] every convolution layer's tail after the last
    token, ``tail_rows`` [2, last, K - 1, D] the first and the last
    convolution layer's tail after each of the last ``last`` tokens (1
    without ``last``), ``ids`` [sparse layers, S, k] the routers' picks and
    ``took``. ``theirs`` [sparse layers, R, k] are another implementation's
    picks for the last R tokens: each sparse layer takes them where they
    are a rightful top-k of its own ranking within ``tie_band``
    (``ties_broken_their_way``), and ``took`` [sparse layers, R] says where
    it did. ``sizes`` holds the published ``config.json`` keys, with
    ``layer_types`` for as many layers as the tree has, ``head_dim``, and
    ``expert_offset`` where the tree holds a part of the experts."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    eps, n_dense = sizes["norm_eps"], sizes["num_dense_layers"]
    kinds = sizes["layer_types"]
    if theirs is None:
        theirs = jnp.zeros((len(kinds) - n_dense, 0,
                            sizes["num_experts_per_tok"]), jnp.int32)
    experts = {n: params["layers"][n]
               for n in ("we_gate", "we_up", "we_down")}
    sparse = {n: w for n, w in params["layers"].items() if n not in experts}
    seen = {"conv": 0, "full_attention": 0}
    kept = {"tails": [], "k": [], "v": [], "ids": [], "took": []}
    with jax.default_matmul_precision("highest"):
        h = _rows(params["embed"], tokens)
        for l, kind in enumerate(kinds):
            slot = seen[kind]
            seen[kind] += 1
            if kind == "conv":
                w = _at(params["conv"], slot)
                out, tails = conv_operator(
                    _rmsnorm(h, w["op_norm"], eps), w, sizes, faults,
                    keep=last or 1)
                kept["tails"].append(tails)
            else:
                w = _at(params["attn"], slot)
                out, (k, v) = attention_operator(
                    _rmsnorm(h, w["op_norm"], eps), w, sizes, faults)
                kept["k"].append(k)
                kept["v"].append(v)
            h = h + out
            if l < n_dense:
                w = _at(params["dense"], l)
                h = h + swiglu(_rmsnorm(h, w["ffn_norm"], eps), w["w_gate"],
                               w["w_up"], w["w_down"])
            else:
                w = _at(sparse, l - n_dense)
                out, ids, took = sparse_ffn(
                    _rmsnorm(h, w["ffn_norm"], eps), w, experts, l - n_dense,
                    sizes, theirs[l - n_dense], tie_band, faults)
                h = h + out
                kept["ids"].append(ids)
                kept["took"].append(took)
        h = _rmsnorm(h if last is None else h[-last:], params["final_norm"],
                     eps)
        tails = jnp.stack(kept["tails"])     # [conv layers, keep, K - 1, D]
        return {"logits": h @ _dense(params["embed"], (1,)).T,
                "k": jnp.stack(kept["k"]), "v": jnp.stack(kept["v"]),
                "conv": tails[:, -1],
                "tail_rows": jnp.stack([tails[0], tails[-1]]),
                "ids": jnp.stack(kept["ids"]),
                "took": jnp.stack(kept["took"])}


def logits(params: dict, tokens, sizes: dict, *, last: int | None = None,
           faults=()) -> jax.Array:
    """``forward``'s logits alone."""
    return forward(params, tokens, sizes, last=last, faults=faults)["logits"]
