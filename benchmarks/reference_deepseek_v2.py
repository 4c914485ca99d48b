"""The plain reference of DeepSeek-V2's decoder: latent attention, sparse
experts with shared experts, YaRN.

Written from the published description (deepseek-ai/DeepSeek-V2
``config.json`` and its modeling code's equations) in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision: the whole
sequence at once, keys and values expanded from the latent for every head,
no kernel, no cache, no batching, no quantization, the experts by a plain
loop over those held. It reads the program's parameter tree (stacked
``[L, ...]`` leaves under ``dense`` and ``layers``; int8 ``{"q", "s"}``
leaves are multiplied out first) because the weights have to be the same,
and nothing else of the program.

One layer, pre-norm residual (``h = x + MLA(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``):

- MLA: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads of ``[q_nope |
  q_rope]``; ``[c_kv | k_rope] = x W_kva``, ``c_kv = RMSNorm(c_kv)``; RoPE
  on ``q_rope`` and on the one ``k_rope`` all heads share; per head ``k_nope
  = c_kv W_kb``, ``v = c_kv W_vb``; ``score = (q_nope k_nope + q_rope
  k_rope) * scale``, causal softmax, ``out = P v``, ``o = concat(out) W_o``.
  RoPE is YaRN: inverse frequencies blended between interpolated (over
  ``factor``) and extrapolated by a linear ramp between the correction
  dimensions of ``beta_fast`` and ``beta_slow``; ``scale = (nope + rope)^-0.5
  * m^2`` with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; cos and sin
  are multiplied by ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
  mscale_all_dim)``, which is 1 for the published values.
- FFN: the first ``first_k_dense_replace`` layers are a SwiGLU. The others:
  ``s = softmax(x W_r)`` over all routed experts; group-limited greedy
  (``n_group`` groups, a group's score is its largest ``s``, the best
  ``topk_group`` groups stay, then the best ``num_experts_per_tok`` experts
  inside them); weights are those ``s`` times ``routed_scaling_factor``, not
  renormalised (``norm_topk_prob`` false); ``y = sum_e w_e Expert_e(x) +
  Shared(x)``, every expert a SwiGLU, the shared experts one SwiGLU of
  ``n_shared_experts`` times the width that every token passes.
- Expert parallelism: the tree holds experts ``expert_offset ..
  expert_offset + held`` of each layer (``held`` is the leading size of its
  expert weights). The router keeps all its outputs; a pick outside the
  held range adds nothing, here as in the program, and the partial sum plus
  the shared experts goes on to the next layer.

Departures from the published code, the same as the program's: rotate-half
RoPE pairing (the published code permutes interleaved pairs first, a fixed
column permutation of ``W_qb``/``W_kva`` that a checkpoint converter would
apply; with seeded weights it is the identity); ``kv_b_proj`` is read as its
two halves ``wk_b``/``wv_b``; the auxiliary routing losses are training-only
and absent; weights are random.
"""
from __future__ import annotations

import math

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


def _rows(leaf, tokens) -> jax.Array:
    if not isinstance(leaf, dict):
        return leaf[tokens].astype(jnp.float32)
    return leaf["q"][tokens].astype(jnp.float32) * leaf["s"][tokens][:, None]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: dict) -> jax.Array:
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extrapolated = 1.0 / theta ** exponent
    interpolated = extrapolated / scaling["factor"]

    def correction_dim(rotations):
        return (dim * math.log(scaling["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    return interpolated * ramp + extrapolated * (1 - ramp)


def _rotate(x, inv_freq, mscale):
    """x [S, H, d]: rotate pairs (i, i + d/2) by position * inv_freq[i]."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(ang) * mscale)[:, None, :]
    sin = (jnp.sin(ang) * mscale)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def route(scores, n_group: int, topk_group: int, top_k: int):
    """scores [S, E] -> (expert ids [S, top_k], their scores)."""
    s, e = scores.shape
    group_best = scores.reshape(s, n_group, e // n_group).max(-1)
    _, kept = jax.lax.top_k(group_best, topk_group)
    in_kept = (kept[:, :, None] == jnp.arange(n_group)).any(1)      # [S, G]
    masked = jnp.where(jnp.repeat(in_kept, e // n_group, 1), scores, 0.0)
    weights, ids = jax.lax.top_k(masked, top_k)
    return ids, weights


def context(sizes: dict, length: int) -> dict:
    """What every layer of one sequence of ``length`` tokens reads: the
    widths, YaRN's frequencies and factors, the softmax scale, the mask."""
    scaling = sizes["rope_scaling"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    m = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    return {
        "eps": sizes["rms_norm_eps"], "rank": sizes["kv_lora_rank"],
        "dn": dn, "inv_freq": yarn_inv_freq(dr, sizes["rope_theta"], scaling),
        "rope_m": yarn_mscale(scaling["factor"], scaling["mscale"]) / m,
        "scale": (dn + dr) ** -0.5 * m * m,
        "causal": jnp.tril(jnp.ones((length, length), bool)),
    }


def attention(x, w: dict, c: dict):
    """x [S, D] -> (x + MLA(RMSNorm(x)), the latent rows [S, rank + rope]:
    the normalised ``c_kv`` beside the rotated ``k_rope``, which is all a
    cache would keep of a token)."""
    rank, dn = c["rank"], c["dn"]
    h = _rmsnorm(x, w["attn_norm"], c["eps"])
    c_q = _rmsnorm(h @ _dense(w["wq_a"], (0,)), w["q_norm"], c["eps"])
    q = jnp.einsum("sr,rhk->shk", c_q, _dense(w["wq_b"], (0,)))
    kv = h @ _dense(w["wkv_a"], (0,))
    c_kv = _rmsnorm(kv[:, :rank], w["kv_norm"], c["eps"])
    q_rope = _rotate(q[..., dn:], c["inv_freq"], c["rope_m"])
    k_rope = _rotate(kv[:, None, rank:], c["inv_freq"], c["rope_m"])[:, 0]
    k_nope = jnp.einsum("tc,chk->thk", c_kv, _dense(w["wk_b"], (0,)))
    v = jnp.einsum("tc,chk->thk", c_kv, _dense(w["wv_b"], (0,)))
    score = (jnp.einsum("shk,thk->hst", q[..., :dn], k_nope)
             + jnp.einsum("shk,tk->hst", q_rope, k_rope)) * c["scale"]
    prob = jax.nn.softmax(jnp.where(c["causal"], score, -jnp.inf), -1)
    ctx = jnp.einsum("hst,thk->shk", prob, v)
    out = x + jnp.einsum("shk,hkd->sd", ctx, _dense(w["wo"], (0, 1)))
    return out, jnp.concatenate([c_kv, k_rope], -1)


def dense_layer(x, w: dict, c: dict):
    x, latent = attention(x, w, c)
    h = _rmsnorm(x, w["mlp_norm"], c["eps"])
    return x + _swiglu(h, _dense(w["w_gate"], (0,)), _dense(w["w_up"], (0,)),
                       _dense(w["w_down"], (0,))), latent


def ties_broken_their_way(scores, theirs, sizes: dict, tie_band: float):
    """Which rows of ``theirs`` [R, k] (another implementation's picks) are
    a rightful routing of ``scores`` [R, E] once ties are allowed: a top-k
    is not a continuous function, and where two candidates score within the
    rounding of the other side's arithmetic both picks are right. A row is
    rightful when its picks are distinct, lie in at most ``topk_group``
    groups, those groups (filled up with the best of the others) are the
    best groups up to a factor ``exp(tie_band)`` on a group's score, and
    the picks are the best experts inside them up to the same factor: that
    is, they are THE group-limited top-k of scores that each moved by less
    than the band. ``tie_band`` 0 admits only the reference's own picks."""
    r, e = scores.shape
    g, keep = sizes["n_group"], sizes["topk_group"]
    band = math.exp(-tie_band)
    picked = (theirs[:, :, None] == jnp.arange(e)).any(1)             # [R, E]
    used = picked.reshape(r, g, e // g).any(-1)                       # [R, G]
    group_best = scores.reshape(r, g, e // g).max(-1)
    _, kept = jax.lax.top_k(jnp.where(used, 2.0, 0.0) + group_best, keep)
    kept = (kept[:, :, None] == jnp.arange(g)).any(1)                 # [R, G]
    eligible = jnp.repeat(kept, e // g, 1)
    worst_in = jnp.where(kept, group_best, jnp.inf).min(-1)
    best_out = jnp.where(kept, 0.0, group_best).max(-1)
    worst_pick = jnp.where(picked, scores, jnp.inf).min(-1)
    best_left = jnp.where(eligible & ~picked, scores, 0.0).max(-1)
    return ((picked.sum(-1) == theirs.shape[1]) & ~(used & ~kept).any(-1)
            & (worst_in >= band * best_out) & (worst_pick >= band * best_left))


def expert_ffn(h, w: dict, sizes: dict, expert_offset: int, theirs=None,
               tie_band: float = 0.0):
    """The experts held in ``w`` (by a plain loop) and the shared experts
    over normalised hidden states h [S, D]. ``theirs`` [R, k]: another
    implementation's picks for the last R tokens, taken in place of this
    one's where ``ties_broken_their_way`` says they are rightful. Returns
    (the layer's output, which of the R rows took theirs)."""
    scores = jax.nn.softmax(h @ w["router"].astype(jnp.float32), -1)
    ids, weights = route(scores, sizes["n_group"], sizes["topk_group"],
                         sizes["num_experts_per_tok"])
    took = jnp.zeros((0,), bool)
    if theirs is not None:
        last = scores[-theirs.shape[0]:]
        took = ties_broken_their_way(last, theirs, sizes, tie_band)
        ids = ids.at[-theirs.shape[0]:].set(
            jnp.where(took[:, None], theirs, ids[-theirs.shape[0]:]))
        weights = weights.at[-theirs.shape[0]:].set(jnp.where(
            took[:, None], jnp.take_along_axis(last, theirs, 1),
            weights[-theirs.shape[0]:]))
    weights = weights * sizes["routed_scaling_factor"]
    held = jax.tree.leaves(w["we_gate"])[0].shape[0]

    def one_expert(e, y):
        ew = jax.tree.map(lambda a: a[e], {
            k: w[k] for k in ("we_gate", "we_up", "we_down")})
        # this expert's weight for each token: its pick's, else 0
        mine = jnp.sum(jnp.where(ids == expert_offset + e, weights, 0.0), -1)
        return y + mine[:, None] * _swiglu(
            h, _dense(ew["we_gate"], (0,)), _dense(ew["we_up"], (0,)),
            _dense(ew["we_down"], (0,)))

    out = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(h))
    if sizes["n_shared_experts"]:
        out = out + _swiglu(h, _dense(w["ws_gate"], (0,)),
                            _dense(w["ws_up"], (0,)),
                            _dense(w["ws_down"], (0,)))
    return out, took


def forward(params: dict, tokens, sizes: dict, *, expert_offset: int = 0,
            last: int | None = None, theirs=None,
            tie_band: float = 0.0) -> dict:
    """One sequence of token ids [S] through the decoder, float32:
    ``logits`` [S, vocab] (with ``last`` only those of the last ``last``
    positions) and ``latent`` [L, S, rank + rope] (what each layer's cache
    would hold of every token). ``theirs`` [expert layers, R, k] are
    another implementation's picks for the last R tokens: each expert layer
    takes them where they are a rightful routing of its own scores within
    ``tie_band`` (``ties_broken_their_way``), and ``took`` [expert layers,
    R] says where it did. ``sizes`` holds the published ``config.json``
    keys (``num_attention_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``rope_theta``, ``rope_scaling``,
    ``rms_norm_eps``, ``n_group``, ``topk_group``, ``num_experts_per_tok``,
    ``routed_scaling_factor``, ``n_shared_experts``)."""
    with jax.default_matmul_precision("highest"):
        c = context(sizes, tokens.shape[0])

        def dense_step(x, w):
            return dense_layer(x, w, c)

        def expert_step(x, layer):
            w, picks = layer
            x, latent = attention(x, w, c)
            h = _rmsnorm(x, w["mlp_norm"], c["eps"])
            out, took = expert_ffn(h, w, sizes, expert_offset, picks, tie_band)
            return x + out, (latent, took)

        x = _rows(params["embed"], tokens)
        x, dense_latent = jax.lax.scan(dense_step, x, params["dense"])
        x, (latent, took) = jax.lax.scan(
            expert_step, x, (params["layers"], theirs))
        x = _rmsnorm(x if last is None else x[-last:], params["final_norm"],
                     c["eps"])
        return {"logits": x @ _dense(params["lm_head"], (0,)),
                "latent": jnp.concatenate([dense_latent, latent]),
                "took": took}


def logits(params: dict, tokens, sizes: dict, *, expert_offset: int = 0,
           last: int | None = None) -> jax.Array:
    """``forward``'s logits alone."""
    return forward(params, tokens, sizes, expert_offset=expert_offset,
                   last=last)["logits"]


def expert_layer_routed(h, w: dict, sizes: dict, expert_offset: int):
    """The routed part alone of one expert layer over normalised hidden
    states ``h`` [S, D] for the experts ``w`` holds (unstacked leaves), for
    the share test: the parts of the shares add up to the whole."""
    with jax.default_matmul_precision("highest"):
        return expert_ffn(h, w, {**sizes, "n_shared_experts": 0},
                          expert_offset)[0]
