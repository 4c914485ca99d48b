"""The plain reference of Brumby-14B-Base's decoder: the Qwen3 dense
skeleton with every attention layer a POWER-RETENTION layer of degree 2.

Written from the published ``config.json`` (manifestai/Brumby-14B-Base) and
the paper its mixer comes from (arXiv:2507.04239, "Scaling Context Requires
Rethinking Attention"; the open ``retention`` kernels' ``power_retention(Q,
K, V, log_G, deg, scale)``) in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision: one sequence at a time, the whole sequence at
once, in the ATTENTION form — a ``[T, T]`` matrix of weights a head, in
blocks of rows where T is the chip's —, no kernel, no state, no chunks, no
cache, no batching, no quantization, the layers and the row blocks by plain
loops. It reads the program's parameter tree (one ``layers`` stack on a
leading dim; int8 ``{"q", "s"}`` leaves are multiplied out first) because
the weights have to be the same, and nothing else of the program.

For layer ``l`` with input ``x`` [T, D] (``eps_n`` = ``rms_norm_eps``, ``s`` =
``head_dim^-0.5``, ``p`` = ``retention_degree`` 2, ``eps`` = ``retention_eps``):

    x0     = E[token]
    h      = rmsnorm(x; g_mix_l)
    q, k, v = h W_q [H x d], h W_k [KV x d], h W_v [KV x d]          no bias
    q, k   = rmsnorm_head(q; w_qn), rmsnorm_head(k; w_kn)      over a head's d
    q, k   = rotate-half rope(q), rope(k)                             theta
    gamma_t[h] = logsigmoid(h_t W_g[:, h] + b_g[h])             a KV head, <= 0
    query head a reads KV head h = a // (H / KV):
    w_tj   = (s q_t^a . k_j^h)^p * exp(gamma_{j+1}[h] + ... + gamma_t[h])  j <= t
    o_t^a  = sum_j w_tj v_j^h / (sum_j w_tj + eps)
    x      = x + concat_a(o^a) W_o
    x      = x + (silu(u W_gate) * (u W_up)) W_down ,  u = rmsnorm(x; g_ff_l)
    logits = rmsnorm(x_L; g_out) W_head                                untied

``state_sums`` gives what a recurrence over the same layer would hold after
a token, from its definition and not from a recurrence: ``S_t = sum_{j<=t}
exp(gamma_{j+1} + ... + gamma_t) phi(k_j) v_j^T`` and ``z_t`` alike with 1
for ``v_j``, ``phi(x) = [x_i x_j * (1 if i == j else sqrt 2)]_{i <= j}`` the
``d (d + 1) / 2`` distinct products in row-major order of ``(i, j)``, so that
``phi(a) . phi(b) = (a . b)^2``.

``assumed`` in the configuration file lists what no key states.

``faults`` names departures the parity check or the CPU tests have to catch,
one line each (``FAULTS``). ``retention_eps`` 0 is no fault: a few tokens
into a sequence the divisor is a sum of many squares and eps is lost in it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = (
    "degree_one",              # (s q . k) for its square
    "no_normaliser",           # the weighted sum of v not divided
    "normaliser_not_decayed",  # the divisor's weights without their decay
    "decay_after_write",       # g (S + phi(k) v^T): a token's own gate on it
    "phi_offdiag_one",         # phi's off-diagonal products at weight 1
    "no_scale",                # s = 1
    "scale_on_both",           # s on q and on k: s^2 inside the power
    "one_gate_all_heads",      # the heads' mean gate for a head's own
    "gate_per_query_head",     # query head a decays by gate a % KV, not a // G
    "no_rope",                 # no rotary on q and k
    "no_qk_norm",              # q and k not normed a head
)

# rows of the [T, T] weight matrix computed at a time
BLOCK_ROWS = 1024


def _dense(leaf, contract_axes: tuple[int, ...]) -> jax.Array:
    """A float32 weight from a plain or an int8 ``{"q", "s"}`` leaf."""
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    s = leaf["s"]
    for a in sorted(contract_axes):
        s = jnp.expand_dims(s, a)
    return leaf["q"].astype(jnp.float32) * s


def _at(tree, index):
    """``leaf[index]`` of every leaf: one layer of the stack."""
    return jax.tree.map(lambda a: a[index], tree)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rows(leaf, tokens) -> jax.Array:
    if not isinstance(leaf, dict):
        return leaf[tokens].astype(jnp.float32)
    return leaf["q"][tokens].astype(jnp.float32) * leaf["s"][tokens][:, None]


def _rotate(x, theta: float):
    """x [T, heads, d]: rotate pairs (i, i + d/2) by position * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def phi(x):
    """x [..., d] -> [..., d (d + 1) / 2]: the distinct products ``x_i x_j``,
    ``i <= j`` in row-major order, the off-diagonal ones times sqrt 2."""
    d = x.shape[-1]
    i, j = jnp.triu_indices(d)
    return x[..., i] * x[..., j] * jnp.where(i == j, 1.0, jnp.sqrt(2.0))


def retention_head(q, k, v, cum, own, scale: float, eps: float, faults=()):
    """One query head in the attention form: q, k [T, d], v [T, dv], cum
    [T] the running sum of its KV head's gamma (``own`` [T] gamma itself)
    -> o [T, dv], a block of rows at a time."""
    T = q.shape[0]
    out = []
    for lo in range(0, T, BLOCK_ROWS):
        rows = slice(lo, min(lo + BLOCK_ROWS, T))
        t = jnp.arange(T)[rows, None]
        j = jnp.arange(T)[None, :]
        s = scale * scale if "scale_on_both" in faults else scale
        dot = (q[rows] @ k.T) * s
        if "degree_one" in faults:
            power = dot
        elif "phi_offdiag_one" in faults:
            # sum_i a_i^2 b_i^2 + sum_{i<j} a_i a_j b_i b_j
            power = 0.5 * (dot * dot + (
                (q[rows] * q[rows]) @ (k * k).T) * s * s)
        else:
            power = dot * dot
        # exp(gamma_{j+1} + ... + gamma_t); with the fault gamma_j too
        log_decay = cum[rows, None] - cum[None, :]
        if "decay_after_write" in faults:
            log_decay = log_decay + own[None, :]
        seen = j <= t
        decay = jnp.exp(jnp.where(seen, log_decay, -jnp.inf))
        w = power * decay
        if "no_normaliser" in faults:
            out.append(w @ v)
            continue
        under = power * seen if "normaliser_not_decayed" in faults else w
        out.append((w @ v) / (under.sum(-1, keepdims=True) + eps))
    return jnp.concatenate(out, 0)


def _projections(h, w: dict, sizes: dict, faults=()):
    """h [T, D] (normed) -> q [T, H, d], k, v [T, KV, d] after QK-norm and
    rotary, gamma [T, KV]."""
    eps = sizes["rms_norm_eps"]
    q = jnp.einsum("sd,dhk->shk", h, _dense(w["wq"], (0,)))
    k = jnp.einsum("sd,dhk->shk", h, _dense(w["wk"], (0,)))
    v = jnp.einsum("sd,dhk->shk", h, _dense(w["wv"], (0,)))
    if "no_qk_norm" not in faults:
        q = _rmsnorm(q, w["q_norm"], eps)
        k = _rmsnorm(k, w["k_norm"], eps)
    if "no_rope" not in faults:
        q = _rotate(q, sizes["rope_theta"])
        k = _rotate(k, sizes["rope_theta"])
    gamma = jax.nn.log_sigmoid(
        h @ w["w_gate_ret"].astype(jnp.float32)
        + w["b_gate_ret"].astype(jnp.float32))
    if "one_gate_all_heads" in faults:
        gamma = jnp.broadcast_to(gamma.mean(-1, keepdims=True), gamma.shape)
    return q, k, v, gamma


def state_sums(k, v, gamma, keep: int, faults=()):
    """The state and the normaliser after each of the last ``keep`` tokens,
    from their definition: k, v [T, KV, d], gamma [T, KV] -> (S [keep, KV,
    d (d + 1) / 2, dv], z [keep, KV, d (d + 1) / 2]), each a sum over the
    tokens up to that one of ``exp(gamma_{j+1} + ... + gamma_t) phi(k_j)
    v_j^T`` (``phi(k_j)`` for z). Of ``faults`` the state knows the two that
    are the state's: a token's own gate on it, and a normaliser that never
    decays."""
    T = k.shape[0]
    cum = jnp.cumsum(gamma, axis=0)                            # [T, KV]
    at = jnp.arange(T - keep, T)
    seen = jnp.arange(T)[None, :] <= at[:, None]               # [keep, T]

    def one_head(args):
        kh, vh, ch, gh = args                                  # [T, *], [T]
        log_decay = ch[at][:, None] - ch[None, :]
        if "decay_after_write" in faults:
            log_decay = log_decay + gh[None, :]
        decay = jnp.exp(jnp.where(seen, log_decay, -jnp.inf))  # [keep, T]
        flat = seen if "normaliser_not_decayed" in faults else decay
        pk = phi(kh)                                           # [T, n]
        # the decayed values first: [keep, T, dv], never [T, n, dv]
        return (jnp.einsum("jn,pjc->pnc", pk, decay[:, :, None] * vh[None]),
                jnp.einsum("pj,jn->pn", flat.astype(jnp.float32), pk))

    S, z = jax.lax.map(one_head, (k.swapaxes(0, 1), v.swapaxes(0, 1), cum.T,
                                  gamma.T))
    return S.swapaxes(0, 1), z.swapaxes(0, 1)


def retention_mixer(h, w: dict, sizes: dict, faults=(), keep: int = 0):
    """h [T, D] (normed) -> (the mixer's output [T, D], ``state_sums`` of
    the last ``keep`` tokens or None)."""
    H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    G = H // KV
    scale = 1.0 if "no_scale" in faults else sizes["head_dim"] ** -0.5
    if sizes["retention_degree"] != 2:
        raise ValueError("the reference writes the square out")
    q, k, v, gamma = _projections(h, w, sizes, faults)
    cum = jnp.cumsum(gamma, axis=0)

    def one_head(a):
        of = a % KV if "gate_per_query_head" in faults else a // G
        return retention_head(
            q[:, a], k[:, a // G], v[:, a // G], cum[:, of], gamma[:, of],
            scale, sizes["retention_eps"], faults)

    o = jax.lax.map(one_head, jnp.arange(H))                   # [H, T, dv]
    out = jnp.einsum("hsk,hkd->sd", o, _dense(w["wo"], (0, 1)))
    return out, (state_sums(k, v, gamma, keep, faults) if keep else None)


def swiglu(u, gate, up, down):
    """down(silu(gate u) * up u)."""
    return (jax.nn.silu(u @ _dense(gate, (0,))) * (u @ _dense(up, (0,)))
            ) @ _dense(down, (0,))


def forward(params: dict, tokens, sizes: dict, *, last: int | None = None,
            faults=(), states: bool = True) -> dict:
    """One sequence of token ids [T] through the decoder, float32:
    ``logits`` [T, vocab] (with ``last`` only those of the last ``last``
    positions) and, with ``states``, ``state_rows`` [2, last, KV, n, dv] and
    ``normaliser_rows`` [2, last, KV, n]: the first and the last layer's
    state and normaliser after each of the last ``last`` tokens (1 without
    ``last``), ``n = d (d + 1) / 2``. ``sizes`` holds the published
    ``config.json`` keys at the tree's depth and the two assumed ones,
    ``retention_degree`` and ``retention_eps``."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    eps, L = sizes["rms_norm_eps"], sizes["num_hidden_layers"]
    kept = {}
    with jax.default_matmul_precision("highest"):
        x = _rows(params["embed"], tokens)
        for l in range(L):
            w = _at(params["layers"], l)
            keep = (last or 1) if states and l in (0, L - 1) else 0
            out, sums = retention_mixer(
                _rmsnorm(x, w["mixer_norm"], eps), w, sizes, faults, keep)
            if sums is not None:
                kept[l] = sums
            x = x + out
            x = x + swiglu(_rmsnorm(x, w["mlp_norm"], eps), w["w_gate"],
                           w["w_up"], w["w_down"])
        x = _rmsnorm(x if last is None else x[-last:], params["final_norm"],
                     eps)
        out = {"logits": x @ _dense(params["lm_head"], (0,))}
        if states:
            out["state_rows"] = jnp.stack([kept[0][0], kept[L - 1][0]])
            out["normaliser_rows"] = jnp.stack([kept[0][1], kept[L - 1][1]])
        return out


def logits(params: dict, tokens, sizes: dict, *, last: int | None = None,
           faults=()) -> jax.Array:
    """``forward``'s logits alone."""
    return forward(params, tokens, sizes, last=last, faults=faults,
                   states=False)["logits"]
