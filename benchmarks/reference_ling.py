"""The plain reference of Ling-3.0-flash's decoder (the language model of
``bailing_hybrid``): Kimi-Delta-Attention layers beside latent-attention
(MLA) layers with no compressed query, a dense feed-forward on the leading
layers and sparse experts chosen by group-limited sigmoid score + bias, with
a shared expert, after them.

Written from the published ``config.json`` (inclusionAI/Ling-3.0-flash-VL,
the language model's keys) and the papers it names (Kimi Linear,
arXiv:2510.26692, for the delta rule; DeepSeek-V2 / -V3 for the latent
attention and the ``noaux_tc`` router) in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision: one sequence at a time, the whole
sequence at once, no kernel, no cache, no chunking, no batching, no
quantization, the delta rule a ``lax.scan`` over TOKENS on the ``[d_k, d_v]``
state of the equations, the convolution an explicit sum over positions
``t-3 .. t``, the layers by a plain loop, the heads by a plain loop and the
experts by a plain loop that multiplies ONE expert's weights out at a time.
It reads the program's parameter tree (``kda``, ``mla``, ``dense`` and
``layers`` stacks, each on a leading dim of its own; int8 ``{"q", "s"}``
leaves are multiplied out first) because the weights have to be the same,
and nothing else of the program.

For layer ``l`` with input ``h`` [T, D] (``eps`` = ``rms_norm_eps``):

    h0     = E[token]
    h      = h + Mixer_l(rmsnorm(h; g_mix_l))   MLA where (l + 1) % 6 == 0
    h      = h + FF_l(rmsnorm(h; g_ff_l))       dense for l < 2
    logits = rmsnorm(h_L; g_out) W_head         untied

    KDA (H = 32 heads, d_k = d_v = head_dim 128):
    q~,k~,v~ = u W_q, u W_k, u W_v
    q, k, v  = silu(conv4(q~)), silu(conv4(k~)), silu(conv4(v~))
                                      depth-wise, causal, 4 taps, no bias
    q = q / (|q| + 1e-6) * d_k^-0.5 ;  k = k / (|k| + 1e-6)       a head
    g_t    = kda_lower_bound * sigmoid(exp(A_log[h]) * (u W_a + dt_bias))
                                      [H, d_k], in (-5, 0)
    beta_t = sigmoid(u W_beta)        [H]
    S_t    = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t    = S_t^T q_t
    Mixer  = (rmsnorm_head(o_t; g_o) * sigmoid(u w_g[h])) W_o

    MLA (32 heads; nope 128, rope 64, v 128, rank 512):
    [q_nope | q_rope] = u W_q
    [c_kv | k_rope]   = u W_dkv ;  c_kv = rmsnorm(c_kv; g_kv)
    [k_nope | v]      = c_kv W_ukv                                a head
    rotate-half rope over q_rope and the one k_rope, theta 6e6
    Mixer  = concat_h(sigmoid(u w_g[h]) *
                      softmax(q_h k_h^T / sqrt(192) + causal) v_h) W_o

    sparse FF (512 experts top-8, 8 groups of which 4 are kept):
    s      = sigmoid(u W_r) ;  c = s + bias
    group  = sum of the top-2 of c inside each group; the 4 best groups kept
    ids    = the 8 largest c among the kept groups' experts
    w      = s[ids] / sum(s[ids]) * routed_scaling_factor
    FF     = sum_e w_e swiglu_e(u) + swiglu_shared(u)

``assumed`` in the configuration file lists what no key states.

``faults`` names departures the parity check or the CPU tests have to catch,
one line each (``FAULTS``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = (
    "decay_after_write",   # the decay applied after the rank-one term
    "no_beta_erase",       # (I - k k^T): beta left out of the erase
    "no_beta_write",       # k v^T: beta left out of the write
    "scalar_decay",        # a head's mean decay for the channel's own
    "softplus_gate",       # -exp(A_log) softplus(.) for the bounded gate
    "no_k_norm",           # k not l2-normed
    "no_conv_silu",        # no silu after the convolution
    "channel_gate",        # a gate a channel (sigmoid(u W_a)) for a head's
    "rope_wrong_half",     # the rotary over the first 64 lanes of q and k
    "latent_unnormed",     # c_kv not normed
    "qk_head_norm",        # an rmsnorm over each head's expanded q and k
    "group_max",           # a group scored by its largest member
    "no_group_limit",      # the top-8 of all 512
    "bias_in_weight",      # the weights from score + bias
    "no_renorm",           # the weights not renormalised
    "scaling_one",         # routed_scaling_factor 1
    "no_shared",           # the shared expert left out
)


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
    expert of one layer."""
    return jax.tree.map(lambda a: a[index], tree)


def _rmsnorm(x, w, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if w is None else y * w.astype(jnp.float32)


def _rows(leaf, tokens) -> jax.Array:
    if not isinstance(leaf, dict):
        return leaf[tokens].astype(jnp.float32)
    return leaf["q"][tokens].astype(jnp.float32) * leaf["s"][tokens][:, None]


def _rotate(x, theta: float):
    """x [S, ..., w]: rotate pairs (i, i + w/2) by position * theta^(-2i/w)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def delta_rule(q, k, v, g, beta, faults=(), keep: int = 1):
    """The recurrence token by token on the state of the equations: q, k, g
    [S, H, dk], v [S, H, dv], beta [S, H] -> (o [S, H, dv], the state after
    each of the last ``keep`` tokens [keep, H, dk, dv])."""
    def step(S, xs):
        q, k, v, g, b = xs
        erase = 1.0 if "no_beta_erase" in faults else b[:, None, None]
        write = 1.0 if "no_beta_write" in faults else b[:, None, None]
        decay = jnp.exp(g)[:, :, None]
        if "decay_after_write" not in faults:
            S = decay * S
        read = jnp.sum(k[:, :, None] * S, axis=1)                # k^T S
        S = S - erase * k[:, :, None] * read[:, None, :] \
            + write * k[:, :, None] * v[:, None, :]
        if "decay_after_write" in faults:
            S = decay * S
        return S, (jnp.sum(q[:, :, None] * S, axis=1), S)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    n = q.shape[0] - keep
    xs = (q, k, v, g, beta)

    def quiet(S, x):     # a step whose state nobody keeps
        S, (o, _) = step(S, x)
        return S, o

    S, head = jax.lax.scan(quiet, S0, tuple(a[:n] for a in xs))
    _, (tail, states) = jax.lax.scan(step, S, tuple(a[n:] for a in xs))
    return jnp.concatenate([head, tail], 0), states


def kda_mixer(u, w: dict, sizes: dict, faults=(), keep: int = 1):
    """u [S, D] (normed) -> (the mixer's output [S, D], the state after each
    of the last ``keep`` tokens [keep, H, dv, dk] — TRANSPOSED, as the
    program keeps it —, the tail after the last token [K - 1, 3 H d])."""
    S, K = u.shape[0], sizes["short_conv_kernel_size"]
    H, hd = sizes["num_attention_heads"], sizes["head_dim"]
    eps = sizes["rms_norm_eps"]
    pre = jnp.concatenate(
        [jnp.einsum("sd,dhk->shk", u, _dense(w[n], (0,))).reshape(S, H * hd)
         for n in ("wq", "wk", "wv")], -1)                       # q~|k~|v~
    taps = w["conv_w"].astype(jnp.float32)
    ext = jnp.concatenate([jnp.zeros((K - 1, pre.shape[1])), pre], 0)
    z = sum(taps[:, j] * ext[j:j + S] for j in range(K))
    if "no_conv_silu" not in faults:
        z = jax.nn.silu(z)
    q, k, v = (z[:, i * H * hd:(i + 1) * H * hd].reshape(S, H, hd)
               for i in range(3))

    def unit(x):
        return x / (jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)) + 1e-6)

    q = unit(q) * hd ** -0.5
    if "no_k_norm" not in faults:
        k = unit(k)
    a = jnp.einsum("sd,dhk->shk", u, _dense(w["wa"], (0,)))
    rate = jnp.exp(w["A_log"].astype(jnp.float32))[:, None]
    arg = a + w["dt_bias"].astype(jnp.float32)
    if "softplus_gate" in faults:
        g = -rate * jax.nn.softplus(arg)
    else:
        g = sizes["kda_lower_bound"] * jax.nn.sigmoid(rate * arg)
    if "scalar_decay" in faults:
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(u @ _dense(w["w_beta"], (0,)))
    o, states = delta_rule(q, k, v, g, beta, faults, keep)
    o = _rmsnorm(o, w["o_norm"], eps)
    if "channel_gate" in faults:
        o = o * jax.nn.sigmoid(a)
    else:
        o = o * jax.nn.sigmoid(u @ _dense(w["wg_head"], (0,)))[..., None]
    return (jnp.einsum("shk,hkd->sd", o, _dense(w["wo"], (0, 1))),
            states.swapaxes(-1, -2), ext[S:])


def mla_mixer(u, w: dict, sizes: dict, faults=()):
    """u [S, D] (normed) -> (the mixer's output [S, D], the latent rows
    (c_kv | k_rope) [S, rank + rope] as a cache would keep them)."""
    S = u.shape[0]
    rank, dn = sizes["kv_lora_rank"], sizes["qk_nope_head_dim"]
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    q = jnp.einsum("sd,dhk->shk", u, _dense(w["wq_b"], (0,)))   # [S, H, 192]
    kv = u @ _dense(w["wkv_a"], (0,))
    c_kv, k_rope = kv[:, :rank], kv[:, rank:]
    if "latent_unnormed" not in faults:
        c_kv = _rmsnorm(c_kv, w["kv_norm"], eps)
    k_nope = jnp.einsum("sc,chk->shk", c_kv, _dense(w["wk_b"], (0,)))
    v = jnp.einsum("sc,chk->shk", c_kv, _dense(w["wv_b"], (0,)))
    dr = k_rope.shape[-1]
    if "rope_wrong_half" in faults:
        q = jnp.concatenate([_rotate(q[..., :dr], theta), q[..., dr:]], -1)
        k_nope = jnp.concatenate(
            [_rotate(k_nope[..., :dr], theta), k_nope[..., dr:]], -1)
        rotated = k_rope
    else:
        q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], theta)], -1)
        rotated = _rotate(k_rope, theta)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(rotated[:, None, :],
                                  k_nope.shape[:2] + (dr,))], -1)
    if "qk_head_norm" in faults:
        q, k = _rmsnorm(q, None, eps), _rmsnorm(k, None, eps)
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def one_head(args):
        qh, kh, vh = args                                        # [S, *]
        score = qh @ kh.T / jnp.sqrt(jnp.float32(qh.shape[-1]))
        return jax.nn.softmax(jnp.where(mask, score, -jnp.inf), -1) @ vh

    ctx = jax.lax.map(one_head, tuple(
        a.transpose(1, 0, 2) for a in (q, k, v)))               # [H, S, dv]
    gate = jax.nn.sigmoid(u @ _dense(w["wg_head"], (0,)))       # [S, H]
    ctx = ctx * gate.T[:, :, None]
    return (jnp.einsum("hsk,hkd->sd", ctx, _dense(w["wo"], (0, 1))),
            jnp.concatenate([c_kv, rotated], -1))


def swiglu(u, gate, up, down):
    """down(silu(gate u) * up u)."""
    return (jax.nn.silu(u @ _dense(gate, (0,))) * (u @ _dense(up, (0,)))
            ) @ _dense(down, (0,))


def group_scores(ranked, sizes: dict, faults=()):
    """ranked [S, E] -> each group's score [S, G]: the sum of its two
    largest members."""
    S, E = ranked.shape
    per = ranked.reshape(S, sizes["n_group"], E // sizes["n_group"])
    if "group_max" in faults:
        return per.max(-1)
    return jax.lax.top_k(per, 2)[0].sum(-1)


def route(logits, bias, sizes: dict, faults=(), among=None):
    """logits [S, E] -> (expert ids [S, top_k], weights): sigmoid scores;
    ranked by score + bias; the best ``topk_group`` groups by the sum of
    their two largest; the largest ranked among their experts (``among``
    [S, E] bool: of those experts alone, no group rule); the picked scores
    without the bias, renormalised, times the scaling factor."""
    scores = jax.nn.sigmoid(logits)
    ranked = scores + bias
    S, E = ranked.shape
    G = sizes["n_group"]
    if among is not None:
        allowed = among
    elif "no_group_limit" in faults:
        allowed = jnp.ones_like(ranked, bool)
    else:
        _, keep = jax.lax.top_k(group_scores(ranked, sizes, faults),
                                sizes["topk_group"])
        kept = (keep[:, :, None] == jnp.arange(G)).any(1)
        allowed = jnp.repeat(kept, E // G, 1)
    _, ids = jax.lax.top_k(jnp.where(allowed, ranked, -jnp.inf),
                           sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(
        ranked if "bias_in_weight" in faults else scores, ids, -1)
    if "no_renorm" not in faults:
        picked = picked / picked.sum(-1, keepdims=True)
    scaling = (1.0 if "scaling_one" in faults
               else sizes["routed_scaling_factor"])
    return ids, picked * scaling


def ties_broken_their_way(ranked, theirs, sizes: dict, tie_band: float,
                          faults=()):
    """Which rows of ``theirs`` [R, k] (another implementation's picks) are
    a rightful routing of ``ranked`` [R, E] (score + bias) once ties are
    allowed: a top-k is not a continuous function, and where two candidates
    rank within the rounding of the other side's arithmetic both picks are
    right. A row is rightful when its picks are distinct, lie in at most
    ``topk_group`` groups, those groups (filled up with the best of the
    others) are the best groups up to ``2 x tie_band`` on a group's score (a
    sum of two members, each moved by less than the band), and the picks
    rank within ``tie_band`` of the best expert of those groups left out.
    ``tie_band`` 0 admits only the reference's own picks."""
    r, e = ranked.shape
    g, keep = sizes["n_group"], sizes["topk_group"]
    picked = (theirs[:, :, None] == jnp.arange(e)).any(1)             # [R, E]
    used = picked.reshape(r, g, e // g).any(-1)                       # [R, G]
    score = group_scores(ranked, sizes, faults)
    _, kept = jax.lax.top_k(jnp.where(used, 1e3, 0.0) + score, keep)
    kept = (kept[:, :, None] == jnp.arange(g)).any(1)                 # [R, G]
    eligible = jnp.repeat(kept, e // g, 1)
    worst_in = jnp.where(kept, score, jnp.inf).min(-1)
    best_out = jnp.where(kept, -jnp.inf, score).max(-1)
    worst_pick = jnp.where(picked, ranked, jnp.inf).min(-1)
    best_left = jnp.where(eligible & ~picked, ranked, -jnp.inf).max(-1)
    return ((picked.sum(-1) == theirs.shape[1]) & ~(used & ~kept).any(-1)
            & (worst_in >= best_out - 2 * tie_band)
            & (worst_pick >= best_left - tie_band))


def expert_ffn(u, ids, weights, experts: dict, slot: int, offset: int):
    """sum over each token's picks of w_e swiglu_e(u), by a plain loop over
    the experts the tree holds of sparse layer ``slot`` (expert ``offset``
    onwards: a pick outside them adds nothing), one multiplied out at a
    time."""
    held = jax.tree.leaves(experts["we_up"])[0].shape[1]

    def one_expert(e, y):
        ew = _at(experts, slot, e)
        # this expert's weight for each token: its pick's, else 0
        mine = jnp.sum(jnp.where(ids == e + offset, weights, 0.0), -1)
        return y + mine[:, None] * swiglu(
            u, ew["we_gate"], ew["we_up"], ew["we_down"])

    return jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(u))


def sparse_ffn(u, w: dict, experts: dict, slot: int, sizes: dict, picks,
               tie_band: float, faults=()):
    """u [S, D] (normed) -> (routed + shared, the picks [S, k], where the
    last rows took ``picks`` [R, k])."""
    logits = u @ w["router"].astype(jnp.float32)
    bias = w["expert_bias"].astype(jnp.float32)
    ids, weights = route(logits, bias, sizes, faults)
    R = picks.shape[0]
    took = jnp.zeros((0,), bool)
    if R:
        tail = logits[-R:]
        took = ties_broken_their_way(
            jax.nn.sigmoid(tail) + bias, picks, sizes, tie_band, faults)
        among = (picks[:, :, None] == jnp.arange(tail.shape[1])).any(1)
        their_ids, their_weights = route(tail, bias, sizes, faults, among)
        ids = ids.at[-R:].set(jnp.where(took[:, None], their_ids, ids[-R:]))
        weights = weights.at[-R:].set(
            jnp.where(took[:, None], their_weights, weights[-R:]))
    out = expert_ffn(u, ids, weights, experts, slot,
                     sizes.get("expert_offset", 0))
    if "no_shared" not in faults:
        out = out + swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"])
    return out, ids, took


def forward(params: dict, tokens, sizes: dict, *, last: int | None = None,
            theirs=None, tie_band: float = 0.0, faults=()) -> dict:
    """One sequence of token ids [S] through the decoder, float32:
    ``logits`` [S, vocab] (with ``last`` only those of the last ``last``
    positions), ``latent`` [MLA layers, S, rank + rope], ``kda`` [KDA
    layers, H, dv, dk] every KDA layer's state after the last token
    (transposed, as the program keeps it), ``state_rows`` [2, last, H, dv,
    dk] the first and the last KDA layer's state after each of the last
    ``last`` tokens (1 without ``last``), ``conv`` [KDA layers, K - 1, 3 H
    d] every tail after the last token, ``ids`` [sparse layers, S, k] the
    routers' picks and ``took``. ``theirs`` [sparse layers, R, k] are
    another implementation's picks for the last R tokens: each sparse layer
    takes them where they are a rightful routing of its own ranking within
    ``tie_band`` (``ties_broken_their_way``), and ``took`` [sparse layers,
    R] says where it did. ``sizes`` holds the published ``config.json`` keys
    at the tree's depth, and ``expert_offset`` where the tree holds a part
    of the experts."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    eps, n_dense = sizes["rms_norm_eps"], sizes["first_k_dense_replace"]
    n_layers, period = sizes["num_hidden_layers"], sizes["layer_group_size"]
    k_top = sizes["num_experts_per_tok"]
    if theirs is None:
        theirs = jnp.zeros((n_layers - n_dense, 0, k_top), jnp.int32)
    experts = {n: params["layers"][n]
               for n in ("we_gate", "we_up", "we_down")}
    sparse = {n: w for n, w in params["layers"].items() if n not in experts}
    seen = {"kda": 0, "mla": 0}
    kept = {"states": [], "conv": [], "latent": [], "ids": [], "took": []}
    with jax.default_matmul_precision("highest"):
        h = _rows(params["embed"], tokens)
        for l in range(n_layers):
            kind = "mla" if (l + 1) % period == 0 else "kda"
            w = _at(params[kind], seen[kind])
            seen[kind] += 1
            u = _rmsnorm(h, w["mixer_norm"], eps)
            if kind == "kda":
                out, states, tail = kda_mixer(u, w, sizes, faults,
                                              keep=last or 1)
                kept["states"].append(states)
                kept["conv"].append(tail)
            else:
                out, latent = mla_mixer(u, w, sizes, faults)
                kept["latent"].append(latent)
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
        states = kept["states"]
        return {"logits": h @ _dense(params["lm_head"], (0,)),
                "latent": jnp.stack(kept["latent"]),
                "kda": jnp.stack([s[-1] for s in states]),
                "state_rows": jnp.stack([states[0], states[-1]]),
                "conv": jnp.stack(kept["conv"]),
                "ids": jnp.stack(kept["ids"]),
                "took": jnp.stack(kept["took"])}


def logits(params: dict, tokens, sizes: dict, *, last: int | None = None,
           faults=()) -> jax.Array:
    """``forward``'s logits alone."""
    return forward(params, tokens, sizes, last=last, faults=faults)["logits"]
