"""The plain reference of Granite-4.0-H's decoder: Mamba-2 layers beside a
few position-free grouped-query attention layers, each followed by a dense
SwiGLU, under Granite's four scalars.

Written from the published ``config.json``
(ibm-granite/granite-4.0-h-micro) and the published Mamba-2 and
GraniteMoeHybrid definitions in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision: one sequence at a time, no kernel, no cache,
no chunking, no batching, no quantization, **the recurrence as a
``lax.scan`` over tokens** with the state ``[heads, P, N]`` as the equations
have it, the layers one after another (a run of one kind as one scan over its
layers) with their weights multiplied out a layer at a time. It reads the program's parameter tree
(``mamba``, ``attn`` and ``layers`` stacks; int8 ``{"q", "s"}`` leaves are
multiplied out first) because the weights have to be the same, and nothing
else of the program.

For layer ``l`` with input ``x`` [T, D] (``eps`` = ``rms_norm_eps``):

    x0     = embedding_multiplier * E[token]
    h      = rmsnorm(x)
    x'     = x + residual_multiplier * mixer_l(h)
    g, u   = rmsnorm(x') W_g, rmsnorm(x') W_u
    out    = x' + residual_multiplier * (silu(g) * u) W_d
    logits = rmsnorm(x_L) E^T / logits_scaling        (tied embedding)

    attention mixer (layer_types[l] == "attention"):
    q,k,v  = h W_q, h W_k, h W_v        no bias, no rotary, no QK-norm
    a      = softmax(q k^T * attention_multiplier + causal mask) v    GQA
    mixer  = a W_o

    Mamba-2 mixer (layer_types[l] == "mamba"):
    z | xBC | dt = h W_in               (inner | inner + 2 N | heads)
    xBC_t  = silu(b_c + sum_{j=0..K-1} w_c[:, j] * xBC_{t-(K-1)+j})
                                        depth-wise, causal, zeros before t=0
    X, B, C = split(xBC)                X [heads, P], B [N], C [N]
    dt     = softplus(dt + dt_bias);  A = -exp(A_log)       per head
    H_t    = exp(dt_t A) * H_{t-1} + dt_t * X_t (x) B_t     H [heads, P, N]
    Y_t    = H_t C_t + D * X_t
    y      = rmsnorm(Y * silu(z)) * w_n gate first, one norm group
    mixer  = y W_out

``assumed`` in the configuration file lists what no key states: the gate
before the norm and one norm group, no clamp on ``dt``, float32 state.

``faults`` names departures the parity check has to catch, one line each:
``rope`` (rotary on the attention layers), ``sqrt_scale`` (scores over
sqrt(head_dim) and not times ``attention_multiplier``), ``norm_before_gate``
(the gated norm's other order), ``no_residual_multiplier``, ``no_conv_bias``,
``no_D`` (no skip term) and ``dt_no_bias``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("rope", "sqrt_scale", "norm_before_gate", "no_residual_multiplier",
          "no_conv_bias", "no_D", "dt_no_bias")


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


def _rotate(x, theta: float = 10000.0):
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
        q, k = _rotate(q), _rotate(k)
    scale = (q.shape[-1] ** -0.5 if "sqrt_scale" in faults
             else sizes["attention_multiplier"])
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    group = q.shape[1] // kv

    def one_head(args):
        qh, head = args                               # [S, hd], its index
        kh, vh = k[:, head // group], v[:, head // group]
        score = qh @ kh.T * scale
        return jax.nn.softmax(jnp.where(mask, score, -jnp.inf), -1) @ vh

    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 jnp.arange(q.shape[1])))   # [H, S, hd]
    return jnp.einsum("hsk,hkd->sd", ctx, _dense(w["wo"], (0, 1))), (k, v)


def mamba_mixer(h, w: dict, sizes: dict, faults=(), keep: int = 1):
    """h [S, D] (normed) -> (y W_out [S, D], the state after each of the
    last ``keep`` tokens [keep, heads, P, N], the last K - 1 inputs of the
    convolution [K - 1, C])."""
    S = h.shape[0]
    H, P, N = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
               sizes["mamba_d_state"])
    K, inner = sizes["mamba_d_conv"], H * P
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
    Bm, Cm = conv[:, inner:inner + N], conv[:, inner + N:]
    dt = jax.nn.softplus(
        dt if "dt_no_bias" in faults else dt + w["dt_bias"].astype(
            jnp.float32))
    A = -jnp.exp(w["A_log"].astype(jnp.float32))
    D = w["D"].astype(jnp.float32) * (0.0 if "no_D" in faults else 1.0)

    def token(state, xs):
        x_t, b_t, c_t, dt_t = xs                  # [H, P], [N], [N], [H]
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, state @ c_t + D[:, None] * x_t

    # token by token; the last ``keep`` tokens' states are kept
    cut = lambda a, b: jax.tree.map(  # noqa: E731
        lambda v: v[a:b], (X, Bm, Cm, dt))
    state, Y = jax.lax.scan(token, jnp.zeros((H, P, N)), cut(0, S - keep))

    def kept(state, xs):
        state, y = token(state, xs)
        return state, (state, y)

    _, (states, Y2) = jax.lax.scan(kept, state, cut(S - keep, S))
    Y = jnp.concatenate([Y, Y2])
    Y, gate = Y.reshape(S, inner), jax.nn.silu(z)
    eps, wn = sizes["rms_norm_eps"], w["ssm_norm"]
    y = (_rmsnorm(Y, wn, eps) * gate if "norm_before_gate" in faults
         else _rmsnorm(Y * gate, wn, eps))
    return y @ _dense(w["out_proj"], (0,)), states, ext[S:]


def forward(params: dict, tokens, sizes: dict, *, last: int | None = None,
            faults=()) -> dict:
    """One sequence of token ids [S] through the decoder, float32:
    ``logits`` [S, vocab] (with ``last`` only those of the last ``last``
    positions), ``k`` and ``v`` [attention layers, S, KV, hd], ``ssm``
    [mamba layers, heads, P, N] the recurrent states after the last token,
    ``ssm_rows`` [2, last, heads, P, N] the first and the last Mamba layer's
    state after each of the last ``last`` tokens (1 without ``last``) and
    ``conv`` [mamba layers, K - 1, C] the convolutions' last inputs.
    ``sizes`` holds the published ``config.json`` keys and ``layer_types``
    for as many layers as the tree has."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    eps = sizes["rms_norm_eps"]
    res = 1.0 if "no_residual_multiplier" in faults \
        else sizes["residual_multiplier"]
    def ffn(x, w):
        h = _rmsnorm(x, w["mlp_norm"], eps)
        return x + res * (
            (jax.nn.silu(h @ _dense(w["w_gate"], (0,)))
             * (h @ _dense(w["w_up"], (0,)))) @ _dense(w["w_down"], (0,)))

    def mamba_layer(x, ws):
        w, w_ffn = ws
        out, states, tail = mamba_mixer(
            _rmsnorm(x, w["mixer_norm"], eps), w, sizes, faults,
            keep=last or 1)
        return ffn(x + res * out, w_ffn), (states, tail)

    def attention_layer(x, ws):
        w, w_ffn = ws
        out, kv = attention_mixer(
            _rmsnorm(x, w["mixer_norm"], eps), w, sizes, faults)
        return ffn(x + res * out, w_ffn), kv

    kinds = list(sizes["layer_types"])
    cut = lambda tree, a, b: jax.tree.map(lambda w: w[a:b], tree)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = sizes["embedding_multiplier"] * _rows(params["embed"], tokens)
        seen = {"mamba": 0, "attention": 0}
        kept = {"mamba": [], "attention": []}
        layer = 0
        while layer < len(kinds):
            # the layers in order, a run of one kind as one scan over its
            # layers (the stack's weights a layer at a time)
            kind = kinds[layer]
            n = 1
            while layer + n < len(kinds) and kinds[layer + n] == kind:
                n += 1
            group, step = (("mamba", mamba_layer) if kind == "mamba"
                           else ("attn", attention_layer))
            x, out = jax.lax.scan(step, x, (
                cut(params[group], seen[kind], seen[kind] + n),
                cut(params["layers"], layer, layer + n)))
            kept[kind].append(out)
            seen[kind] += n
            layer += n
        join = lambda parts: jax.tree.map(  # noqa: E731
            lambda *a: jnp.concatenate(a), *parts)
        (ssm, conv), (k, v) = join(kept["mamba"]), join(kept["attention"])
        x = _rmsnorm(x if last is None else x[-last:], params["final_norm"],
                     eps)
        return {"logits": x @ _dense(params["embed"], (1,)).T
                / sizes["logits_scaling"],
                "k": k, "v": v, "ssm": ssm[:, -1], "conv": conv,
                "ssm_rows": jnp.stack([ssm[0], ssm[-1]])}


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
