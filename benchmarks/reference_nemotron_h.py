"""The plain reference of Nemotron-H's decoder: a stack whose every layer is
ONE mixer — Mamba-2 at several groups of B and C, sparse non-gated relu2
experts with a shared one, or position-free grouped-query attention.

Written from the published ``config.json``
(nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16) and the published Mamba-2 and
NemotronH definitions in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision: one sequence at a time, the whole sequence at
once, no kernel, no cache, no chunking, no batching, no quantization, **the
recurrence as a ``lax.scan`` over tokens** with the state ``[heads, P, N]``
as the equations have it, the layers by a plain loop, the heads by a plain
loop and the experts by a plain loop that multiplies ONE expert's weights
out at a time (a layer's 128 in float32 would be 5.1 GB: the pass has to
fit beside 10.1 GB of weights). It reads the program's parameter tree
(``mamba``, ``attn`` and ``layers`` stacks, each on a leading dim of its
own; int8 ``{"q", "s"}`` leaves are multiplied out first) because the
weights have to be the same, and nothing else of the program.

For layer ``l`` of kind ``hybrid_override_pattern[l]`` with input ``x``
[T, D] (``eps`` = ``norm_eps``):

    x0     = E[token]
    x_l+1  = x_l + mixer_l(rmsnorm(x_l))          nothing follows a mixer
    logits = rmsnorm(x_L) W_head                  untied

    M, Mamba-2 mixer (G = n_groups, g(h) = h // (heads / G)):
    z | xBC | dt = h W_in            (inner | inner + 2 G N | heads)
    xBC_t  = silu(b_c + sum_{j=0..K-1} w_c[:, j] * xBC_{t-(K-1)+j})
                                     depth-wise, causal, zeros before t=0
    X, B, C = split(xBC)             X [heads, P], B [G, N], C [G, N]
    dt     = softplus(dt + dt_bias);  A = -exp(A_log)      per head
    H_t[h] = exp(dt_t[h] A[h]) * H_{t-1}[h] + dt_t[h] * X_t[h] (x) B_t[g(h)]
    Y_t[h] = H_t[h] C_t[g(h)] + D[h] * X_t[h]
    y      = rmsnorm_by_group(Y * silu(z)) * w_n    gate first; the mean
                                     square over each of the G runs of
                                     inner / G channels
    mixer  = y W_out

    *, attention:
    q,k,v  = h W_q, h W_k, h W_v     [32 | 2, 128]; no bias, NO rotary
    a      = softmax(q k^T / sqrt(128) + causal mask) v      GQA
    mixer  = a W_o

    E, experts:
    s      = sigmoid(h W_r)          float32, over all routed experts
    ids    = top_k(s + b)            b = e_score_correction_bias: the choice
    w      = s[ids] / sum(s[ids]) * routed_scaling_factor    never the bias
    mixer  = sum_e w_e W_down_e relu(W_up_e h)^2 + W_down_s relu(W_up_s h)^2

``assumed`` in the configuration file lists what no key states.

``faults`` names departures the parity check has to catch, one line each
(``FAULTS``): relu for relu2, a gate multiplied in (``relu(u)^2 * u``),
group 0's B and C for every head, the gated norm over the whole inner
width, norm before gate, softmax for sigmoid, the bias left out, the bias
in the weight, no renormalisation, the scaling left out, the shared expert
left out, the convolution's bias left out, ``D`` left out, a rotary on the
attention layers, and Granite's residual multiplier (0.22) on every mixer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("relu", "gated", "group0_bc", "norm_whole", "norm_before_gate",
          "softmax_router", "no_bias", "bias_in_weight", "no_renorm",
          "no_scaling", "no_shared", "no_conv_bias", "no_D", "rope",
          "residual_multiplier")


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


def attention_mixer(h, w: dict, sizes: dict, faults=()):
    """h [S, D] (normed) -> (a W_o [S, D], this layer's keys and values
    [S, KV, hd] as a cache would keep them)."""
    S = h.shape[0]
    kv = sizes["num_key_value_heads"]
    q = jnp.einsum("sd,dhk->shk", h, _dense(w["wq"], (0,)))
    k = jnp.einsum("sd,dhk->shk", h, _dense(w["wk"], (0,)))
    v = jnp.einsum("sd,dhk->shk", h, _dense(w["wv"], (0,)))
    if "rope" in faults:
        q, k = _rotate(q, sizes["rope_theta"]), _rotate(k, sizes["rope_theta"])
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


def mamba_mixer(h, w: dict, sizes: dict, faults=(), keep: int = 1):
    """h [S, D] (normed) -> (y W_out [S, D], the state after each of the
    last ``keep`` tokens [keep, heads, P, N], the last K - 1 inputs of the
    convolution [K - 1, C])."""
    S = h.shape[0]
    H, P, N = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
               sizes["ssm_state_size"])
    G, K, inner = sizes["n_groups"], sizes["conv_kernel"], H * P
    # in_proj, which the program holds as its three parts
    z, xbc, dt = (h @ _dense(w[part], (0,))
                  for part in ("in_z", "in_xbc", "in_dt"))
    ext = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], 0)
    conv = sum(w["conv_w"][:, j].astype(jnp.float32) * ext[j:j + S]
               for j in range(K))
    if "no_conv_bias" not in faults:
        conv = conv + w["conv_b"].astype(jnp.float32)
    conv = jax.nn.silu(conv)
    X = conv[:, :inner].reshape(S, H, P)
    Bm = conv[:, inner:inner + G * N].reshape(S, G, N)
    Cm = conv[:, inner + G * N:].reshape(S, G, N)
    # head h reads group h // (H / G)
    group_of = jnp.arange(H) // (H // G)
    if "group0_bc" in faults:
        group_of = jnp.zeros_like(group_of)
    Bh, Ch = Bm[:, group_of], Cm[:, group_of]             # [S, H, N]
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(w["A_log"].astype(jnp.float32))
    D = w["D"].astype(jnp.float32) * (0.0 if "no_D" in faults else 1.0)

    def token(state, xs):
        x_t, b_t, c_t, dt_t = xs            # [H, P], [H, N], [H, N], [H]
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + D[:, None] * x_t

    # token by token; the last ``keep`` tokens' states are kept
    cut = lambda a, b: jax.tree.map(  # noqa: E731
        lambda v: v[a:b], (X, Bh, Ch, dt))
    state, Y = jax.lax.scan(token, jnp.zeros((H, P, N)), cut(0, S - keep))

    def kept(state, xs):
        state, y = token(state, xs)
        return state, (state, y)

    _, (states, Y2) = jax.lax.scan(kept, state, cut(S - keep, S))
    Y = jnp.concatenate([Y, Y2]).reshape(S, inner)
    gate, eps, wn = jax.nn.silu(z), sizes["norm_eps"], w["ssm_norm"]
    runs = 1 if "norm_whole" in faults else G

    def norm(y):   # the mean square over each run of inner / runs channels
        y = y.reshape(S, runs, inner // runs)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return y.reshape(S, inner) * wn.astype(jnp.float32)

    y = norm(Y) * gate if "norm_before_gate" in faults else norm(Y * gate)
    return y @ _dense(w["out_proj"], (0,)), states, ext[S:]


def relu2_ffn(h, up, down, faults=()):
    """down(relu(up h)^2): no gate."""
    u = h @ _dense(up, (0,))
    a = jnp.maximum(u, 0.0)
    if "relu" not in faults:
        a = a * a
    if "gated" in faults:
        a = a * u
    return a @ _dense(down, (0,))


def ranking(logits, bias, faults=()):
    """logits [S, E] -> (scores, what the top-k is taken of): sigmoid
    scores, ranked by score + bias."""
    scores = (jax.nn.softmax(logits, -1) if "softmax_router" in faults
              else jax.nn.sigmoid(logits))
    return scores, (scores if "no_bias" in faults else scores + bias)


def route(logits, bias, sizes: dict, faults=(), among=None):
    """logits [S, E] -> (expert ids [S, top_k], weights): sigmoid scores,
    the largest of score + bias (``among`` [S, E] bool: of those experts
    alone), the picked scores without the bias renormalised to one, times
    the scaling factor."""
    scores, ranked = ranking(logits, bias, faults)
    if among is not None:
        ranked = jnp.where(among, ranked, -jnp.inf)
    _, ids = jax.lax.top_k(ranked, sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(
        ranked if "bias_in_weight" in faults else scores, ids, -1)
    if "no_renorm" not in faults:
        picked = picked / picked.sum(-1, keepdims=True)
    if "no_scaling" not in faults:
        picked = picked * sizes["routed_scaling_factor"]
    return ids, picked


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


def expert_ffn(h, ids, weights, experts: dict, slot: int, offset: int,
               faults=()):
    """sum over each token's picks of w_e relu2_e(h), by a plain loop over
    the experts the tree holds of sparse layer ``slot`` (expert ``offset``
    onwards), one multiplied out at a time."""
    held = jax.tree.leaves(experts["we_up"])[0].shape[1]

    def one_expert(e, y):
        ew = _at(experts, slot, e)
        # this expert's weight for each token: its pick's, else 0
        mine = jnp.sum(jnp.where(ids == e + offset, weights, 0.0), -1)
        return y + mine[:, None] * relu2_ffn(
            h, ew["we_up"], ew["we_down"], faults)

    return jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(h))


def expert_mixer(h, w: dict, experts: dict, slot: int, sizes: dict, picks,
                 tie_band: float, faults=()):
    """h [S, D] (normed) -> (routed experts + shared expert, the picks
    [S, k], where the last rows took ``picks`` [R, k])."""
    logits = h @ w["router"].astype(jnp.float32)
    bias = w["router_bias"].astype(jnp.float32)
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
    y = expert_ffn(h, ids, weights, experts, slot,
                   sizes.get("expert_offset", 0), faults)
    if "no_shared" not in faults:
        y = y + relu2_ffn(h, w["ws_up"], w["ws_down"], faults)
    return y, ids, took


def forward(params: dict, tokens, sizes: dict, *, last: int | None = None,
            theirs=None, tie_band: float = 0.0, faults=()) -> dict:
    """One sequence of token ids [S] through the decoder, float32:
    ``logits`` [S, vocab] (with ``last`` only those of the last ``last``
    positions), ``k`` and ``v`` [attention layers, S, KV, hd], ``ssm``
    [mamba layers, heads, P, N] the recurrent states after the last token,
    ``ssm_rows`` [2, last, heads, P, N] the first and the last Mamba layer's
    state after each of the last ``last`` tokens (1 without ``last``),
    ``conv`` [mamba layers, K - 1, C] the convolutions' last inputs,
    ``ids`` [sparse layers, S, k] the routers' picks and ``took``.
    ``theirs`` [sparse layers, R, k] are another implementation's picks for
    the last R tokens: each sparse layer takes them where they are a
    rightful top-k of its own ranking within ``tie_band``
    (``ties_broken_their_way``), and ``took`` [sparse layers, R] says where
    it did. ``sizes`` holds the published ``config.json`` keys, with
    ``hybrid_override_pattern`` for as many layers as the tree has, and
    ``expert_offset`` where the tree holds a part of the experts."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    eps = sizes["norm_eps"]
    pattern = sizes["hybrid_override_pattern"]
    res = 0.22 if "residual_multiplier" in faults else 1.0
    n_sparse = pattern.count("E")
    if theirs is None:
        theirs = jnp.zeros((n_sparse, 0, sizes["num_experts_per_tok"]),
                           jnp.int32)
    experts = {n: params["layers"][n] for n in ("we_up", "we_down")}
    sparse = {n: w for n, w in params["layers"].items() if n not in experts}
    seen = {"M": 0, "E": 0, "*": 0}
    kept = {"ssm": [], "conv": [], "k": [], "v": [], "ids": [], "took": []}
    with jax.default_matmul_precision("highest"):
        x = _rows(params["embed"], tokens)
        for kind in pattern:
            slot = seen[kind]
            seen[kind] += 1
            if kind == "M":
                w = _at(params["mamba"], slot)
                out, states, tail = mamba_mixer(
                    _rmsnorm(x, w["mixer_norm"], eps), w, sizes, faults,
                    keep=last or 1)
                kept["ssm"].append(states)
                kept["conv"].append(tail)
            elif kind == "*":
                w = _at(params["attn"], slot)
                out, (k, v) = attention_mixer(
                    _rmsnorm(x, w["mixer_norm"], eps), w, sizes, faults)
                kept["k"].append(k)
                kept["v"].append(v)
            else:
                w = _at(sparse, slot)
                out, ids, took = expert_mixer(
                    _rmsnorm(x, w["mixer_norm"], eps), w, experts, slot,
                    sizes, theirs[slot], tie_band, faults)
                kept["ids"].append(ids)
                kept["took"].append(took)
            x = x + res * out
        x = _rmsnorm(x if last is None else x[-last:], params["final_norm"],
                     eps)
        ssm = jnp.stack(kept["ssm"])         # [mamba layers, keep, H, P, N]
        return {"logits": x @ _dense(params["lm_head"], (0,)),
                "k": jnp.stack(kept["k"]), "v": jnp.stack(kept["v"]),
                "ssm": ssm[:, -1], "conv": jnp.stack(kept["conv"]),
                "ssm_rows": jnp.stack([ssm[0], ssm[-1]]),
                "ids": jnp.stack(kept["ids"]),
                "took": jnp.stack(kept["took"])}


def logits(params: dict, tokens, sizes: dict, *, last: int | None = None,
           faults=()) -> jax.Array:
    """``forward``'s logits alone."""
    return forward(params, tokens, sizes, last=last, faults=faults)["logits"]


def state_as_the_program_lays_it(ssm) -> jax.Array:
    """[..., heads, P, N] -> [..., N, heads * P]: the layout the program
    keeps the state in (``vnsum_tpu/ops/ssd_scan.py``)."""
    lead = ssm.shape[:-3]
    H, P, N = ssm.shape[-3:]
    return jnp.moveaxis(ssm.reshape(lead + (H * P, N)), -1, -2)
