"""The plain reference of Keye-VL-2.0's language model: the Qwen3-MoE
skeleton (QK-normed rotary GQA by three position components, 128 SwiGLU
experts top-8) whose every layer attends the ``topk`` keys a learned indexer
scores highest (DeepSeek Sparse Attention).

Written from the published ``config.json`` (Kwai-Keye/Keye-VL-2.0-30B-A3B)
and DeepSeek-V3.2-Exp's description of the lightning indexer, with the
family's conventions where the source names a mechanism alone (each under
``assumed`` in ``benchmarks/configs/keye-vl-2.0-l12-int8.json``), in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision:
the whole sequence at once, no kernel, no cache, no chunks, no batching, no
quantization, the layers by a plain loop, the experts by a plain loop, the
selection by a FULL SORT of a query's scores. The one concession to size:
the [T, T] index scores and a head's [T, T] attention scores are made
``ROW_BLOCK`` query rows at a time (11,008 tokens squared is 485 MB a
matrix), which changes no sum. It reads the program's parameter tree
(``layers`` stacked on a leading dim; int8 ``{"q", "s"}`` leaves are
multiplied out first) because the weights have to be the same, and nothing
else of the program.

The layer, ``h = rmsnorm(x)`` [T, D], positions p [T, 3] (a text token at t:
(t, t, t)):

    q, k, v = h W_q [32,128], h W_k [4,128], h W_v [4,128]
    q, k    = rope(rmsnorm_head(q)), rope(rmsnorm_head(k))
              rope: rotate-half, 64 frequencies theta^(-i/64); frequency i
              turns by p[c(i)] theta^(-i/64), c(i) = 0 | 1 | 2 for i in the
              three runs of mrope_section [16, 24, 24]
    qI      = rope_I(h W_qI) [16, 64];  kI = rope_I(layernorm(h W_kI)) [64]
              rope_I: 32 frequencies, all by p[0]
    w       = (h W_wI) / sqrt(16) / sqrt(64)             [16], float32
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])       s <= t
    T_t     = the topk visible s of largest I[t, s] (a stable sort of -I:
              equal scores to the lower s); all of them when t + 1 <= topk
    a_t^h   = softmax_{s in T_t}(q_t^h . k_s^{h // 8} / sqrt 128) v_s
    x'      = x + concat_h(a^h) W_o
    z       = rmsnorm(x');  p = softmax(z W_r) over all 128, float32
    E       = top_8(p);  out = x' + sum_{e in E} p_e / sum_E p  SwiGLU_e(z)

``faults`` names departures the parity checks have to catch (``FAULTS``).
Two of the issue's list cannot show in the logits by construction and say
so: ``no_index_scale`` (a positive factor on every score moves no top-k: it
shows in the recorded scores alone) and softmax over the picked eight,
which IS the rule above (``exp(l_e) / sum_E exp(l)``): ``EQUIVALENT``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

FAULTS = ("dense_attention", "top_half", "select_a_kv_head",
          "softmax_over_visible", "no_relu", "no_head_weights",
          "no_index_scale", "no_index_norm", "no_index_rotary",
          "selection_of_previous_layer", "indexer_of_another_layer",
          "pad_selectable", "no_route_renorm", "top_6", "components_swapped",
          "no_qk_norm")
# a layer's indexer: what ``indexer_of_another_layer`` takes from the layer
# before (every layer but the first: a fault of the DEEP layers' selection
# alone, which the first layer's records cannot show)
INDEXER_LEAVES = ("wq_idx", "wk_idx", "idx_norm_g", "idx_norm_b", "w_idx")
# shows in the selection's recorded scores and nowhere else
SCORES_ONLY = ("no_index_scale",)
# the same function written another way: accepted, and must NOT show
EQUIVALENT = ("softmax_over_picked",)
# query rows whose [rows, T] scores are held at once
ROW_BLOCK = 512
# slots of nothing a ``pad_selectable`` sequence is led by: zero keys, zero
# values, index score 0, as a left pad's cache slots hold
PHANTOM_SLOTS = 16


def _dense(leaf, contract_axes: tuple[int, ...]) -> jax.Array:
    """A float32 weight from a plain or an int8 ``{"q", "s"}`` leaf."""
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    s = leaf["s"]
    for a in sorted(contract_axes):
        s = jnp.expand_dims(s, a)
    return leaf["q"].astype(jnp.float32) * s


def _at(tree, *index):
    return jax.tree.map(lambda a: a[index], tree)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _layernorm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _rows(leaf, tokens) -> jax.Array:
    if not isinstance(leaf, dict):
        return leaf[tokens].astype(jnp.float32)
    return leaf["q"][tokens].astype(jnp.float32) * leaf["s"][tokens][:, None]


def _rotate(x, angles):
    """x [T, H, d] by angles [T, d / 2]: pairs (i, i + d / 2)."""
    n = angles.shape[-1]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :n], x[..., n:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def head_angles(positions, sizes: dict, faults=()):
    """positions [T, 3] -> [T, head_dim / 2]: frequency i by the component
    its ``mrope_section`` run names."""
    half = sizes["head_dim"] // 2
    inv = 1.0 / sizes["rope_theta"] ** (
        jnp.arange(half, dtype=jnp.float32) / half)
    sections = sizes["rope_scaling"]["mrope_section"]
    component = [c for c, n in enumerate(sections) for _ in range(n)]
    if "components_swapped" in faults:
        component = [(c + 1) % 3 for c in component]
    return positions.astype(jnp.float32)[:, jnp.asarray(component)] * inv


def index_angles(positions, sizes: dict):
    half = sizes["sa_config"]["indexer_head_dim"] // 2
    inv = 1.0 / sizes["rope_theta"] ** (
        jnp.arange(half, dtype=jnp.float32) / half)
    return positions.astype(jnp.float32)[:, :1] * inv


def route(logits, sizes: dict, faults=(), among=None):
    """logits [T, E] -> (expert ids [T, k], weights): softmax over all
    experts, its largest (``among`` [T, E] bool: of those alone),
    renormalised to one."""
    scores = jax.nn.softmax(logits, -1)
    ranked = scores if among is None else jnp.where(among, scores, -jnp.inf)
    picked, ids = jax.lax.top_k(ranked, sizes["num_experts_per_tok"])
    if "softmax_over_picked" in faults:
        return ids, jax.nn.softmax(jnp.take_along_axis(logits, ids, -1), -1)
    if "top_6" in faults:   # of eight; at another k six eighths, rounded down
        keep = max(6 * picked.shape[1] // 8, 1)
        picked = jnp.where(jnp.arange(picked.shape[1]) < keep, picked, 0.0)
    if "no_route_renorm" not in faults:
        picked = picked / picked.sum(-1, keepdims=True)
    return ids, picked


def ties_broken_their_way(logits, theirs, tie_band: float):
    """``reference_laguna.ties_broken_their_way``: which rows of ``theirs``
    [R, k] are a rightful top-k of ``logits`` [R, E] once scores within
    ``tie_band`` of the cut count as tied."""
    picked = (theirs[:, :, None] == jnp.arange(logits.shape[1])).any(1)
    worst_pick = jnp.where(picked, logits, jnp.inf).min(-1)
    best_left = jnp.where(picked, -jnp.inf, logits).max(-1)
    return ((picked.sum(-1) == theirs.shape[1])
            & (worst_pick >= best_left - tie_band))


def near_the_cut(scores, visible, own, band: float):
    """[R, T] bool: the visible slots of ``scores`` [R, T] within ``band`` x
    the standard deviation of the row's visible scores of the cut — the
    smallest score the reference's ``own`` set kept."""
    cut = jnp.where(own, scores, jnp.inf).min(-1, keepdims=True)
    seen = jnp.maximum(visible.sum(-1, keepdims=True), 1)
    mean = jnp.where(visible, scores, 0.0).sum(-1, keepdims=True) / seen
    spread = jnp.sqrt(jnp.where(visible, jnp.square(scores - mean), 0.0
                                ).sum(-1, keepdims=True) / seen)
    return visible & (jnp.abs(scores - cut) <= band * spread)


def selection_is_rightful(scores, visible, own, theirs, band: float):
    """Which rows of ``theirs`` [R, T] bool (another implementation's sets)
    are a rightful selection of ``scores`` [R, T] once ties are allowed: as
    many slots as the reference's ``own``, all visible, and every slot the
    two sets do not share ``near_the_cut``. ``band`` 0 admits only the
    reference's own."""
    near = near_the_cut(scores, visible, own, band)
    return ((theirs.sum(-1) == own.sum(-1))
            & ~(theirs & ~visible).any(-1)
            & ~((own != theirs) & ~near).any(-1))


def their_slots_near_the_cut(scores, visible, own, theirs, band: float):
    """The reference's sets [R, T] with the other side's choice SLOT BY
    SLOT where, and only where, the slot is ``near_the_cut``: a near-tie
    broken their way is theirs, a slot far from the cut stays the
    reference's own whatever they did with it (so a row may keep a slot
    more or fewer than ``own`` does, by the far slots the two disagree on).
    A row of ``theirs`` that keeps another number of slots than ``own``, or
    one that is not visible, is not taken at all."""
    sane = ((theirs.sum(-1) == own.sum(-1))
            & ~(theirs & ~visible).any(-1))[:, None]
    return jnp.where(sane & near_the_cut(scores, visible, own, band),
                     theirs, own)


def top_by_sort(scores, visible, topk: int):
    """[R, T] bool: each row's ``topk`` visible slots of largest score by a
    full stable sort (equal scores to the lower slot)."""
    ranked = jnp.where(visible, scores, -jnp.inf)
    order = jnp.argsort(-ranked, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < topk) & visible


def expert_ffn(h, ids, weights, experts: dict, slot: int):
    held = jax.tree.leaves(experts["we_gate"])[0].shape[1]

    def one_expert(e, y):
        ew = _at(experts, slot, e)
        mine = jnp.sum(jnp.where(ids == e, weights, 0.0), -1)
        gate = jax.nn.silu(h @ _dense(ew["we_gate"], (0,)))
        up = h @ _dense(ew["we_up"], (0,))
        return y + mine[:, None] * ((gate * up) @ _dense(ew["we_down"], (0,)))

    return jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(h))


def _blocks_of_rows(fn, T: int):
    """``fn(lo)`` for row blocks [lo, lo + ROW_BLOCK) of T rows (T padded to
    whole blocks inside ``fn`` by clamping), concatenated along rows."""
    n = -(-T // ROW_BLOCK)
    if n == 1:
        return fn(0, T)
    starts = jnp.minimum(jnp.arange(n) * ROW_BLOCK, T - ROW_BLOCK)
    out = jax.lax.map(lambda lo: fn(lo, ROW_BLOCK), starts)

    def join(a):   # the last block overlaps its neighbour: drop the overlap
        a = a.reshape((n * ROW_BLOCK,) + a.shape[2:])
        keep = jnp.concatenate([
            jnp.arange((n - 1) * ROW_BLOCK),
            jnp.arange(n * ROW_BLOCK - (T - (n - 1) * ROW_BLOCK),
                       n * ROW_BLOCK)])
        return a[keep]

    return jax.tree.map(join, out)


def attention(x, w: dict, sizes: dict, positions, prev_sel, their_sel,
              sel_band: float, faults=(), keep_sel: bool = False):
    """x [T, D] -> (x + sparse attention, record). ``prev_sel`` [T, T] is
    the previous layer's selection (for one fault); ``their_sel`` [R, T] the
    other side's sets for the last R rows; ``keep_sel`` also records every
    row's set."""
    T = x.shape[0]
    sa = sizes["sa_config"]
    kv = sizes["num_key_value_heads"]
    eps = sizes["rms_norm_eps"]
    topk = sa["topk"] // 2 if "top_half" in faults else sa["topk"]
    h = _rmsnorm(x, w["attn_norm"], eps)
    q = jnp.einsum("sd,dhk->shk", h, _dense(w["wq"], (0,)))
    k = jnp.einsum("sd,dhk->shk", h, _dense(w["wk"], (0,)))
    v = jnp.einsum("sd,dhk->shk", h, _dense(w["wv"], (0,)))
    if "no_qk_norm" not in faults:
        q, k = _rmsnorm(q, w["q_norm"], eps), _rmsnorm(k, w["k_norm"], eps)
    ang = head_angles(positions, sizes, faults)
    q, k = _rotate(q, ang), _rotate(k, ang)
    qi = jnp.einsum("sd,dhk->shk", h, _dense(w["wq_idx"], (0,)))
    ki = h @ _dense(w["wk_idx"], (0,))
    if "no_index_norm" not in faults:
        ki = _layernorm(ki, w["idx_norm_g"], w["idx_norm_b"], eps)
    if "no_index_rotary" not in faults:
        iang = index_angles(positions, sizes)
        qi, ki = _rotate(qi, iang), _rotate(ki[:, None], iang)[:, 0]
    wi = h @ w["w_idx"].astype(jnp.float32)
    if "no_index_scale" not in faults:
        wi = wi / math.sqrt(sa["indexer_num_heads"]) \
            / math.sqrt(sa["indexer_head_dim"])
    if "no_head_weights" in faults:
        wi = jnp.ones_like(wi) * jnp.abs(wi).mean()
    group = q.shape[1] // kv
    P = PHANTOM_SLOTS if "pad_selectable" in faults else 0
    R = their_sel.shape[0]

    def block(lo, n, records: bool):
        rows = lo + jnp.arange(n)
        per_head = jnp.einsum("shk,ck->hsc", jax.lax.dynamic_slice_in_dim(
            qi, lo, n, 0), ki)                                  # [Hi, n, T]
        if "no_relu" not in faults:
            per_head = jnp.maximum(per_head, 0.0)
        weighted = per_head * jax.lax.dynamic_slice_in_dim(
            wi, lo, n, 0).T[:, :, None]
        scores = weighted.sum(0)
        visible = jnp.arange(T)[None, :] <= rows[:, None]
        if P:   # slots of nothing before the sequence: score 0, visible
            scores = jnp.concatenate([jnp.zeros((n, P)), scores], 1)
            visible = jnp.concatenate([jnp.ones((n, P), bool), visible], 1)
        own = top_by_sort(scores, visible, topk)
        sel = own
        took = jnp.zeros((n,), bool)
        if R:
            # the other side's sets for the sequence's last R rows
            r = jnp.clip(rows - (T - R), 0, R - 1)
            theirs = their_sel[r]
            if P:
                theirs = jnp.concatenate([jnp.zeros((n, P), bool), theirs], 1)
            took = (rows >= T - R) & selection_is_rightful(
                scores, visible, own, theirs, sel_band)
            sel = jnp.where((rows >= T - R)[:, None], their_slots_near_the_cut(
                scores, visible, own, theirs, sel_band), sel)
        if "select_a_kv_head" in faults:
            # a set a KV head from its share of the indexer's heads
            per = weighted.reshape(kv, -1, n, T).sum(1)          # [KV, n, T]
            sel = jax.vmap(lambda s: top_by_sort(s, visible, topk))(per)
        if "selection_of_previous_layer" in faults and prev_sel is not None:
            sel = jax.lax.dynamic_slice_in_dim(prev_sel, lo, n, 0)
        if "dense_attention" in faults:
            sel = visible

        def one_head(args):
            qh, head = args                                    # [n, hd]
            kh, vh = k[:, head // group], v[:, head // group]
            if P:
                kh = jnp.concatenate([jnp.zeros((P, kh.shape[1])), kh])
                vh = jnp.concatenate([jnp.zeros((P, vh.shape[1])), vh])
            mine = sel if sel.ndim == 2 else sel[head // group]
            score = qh @ kh.T / jnp.sqrt(jnp.float32(qh.shape[-1]))
            if "softmax_over_visible" in faults:
                # every visible key in the sum, the unselected zeroed after
                p = jax.nn.softmax(jnp.where(visible, score, -jnp.inf), -1)
                return jnp.where(mine, p, 0.0) @ vh
            return jax.nn.softmax(jnp.where(mine, score, -jnp.inf), -1) @ vh

        ctx = jax.lax.map(one_head, (
            jax.lax.dynamic_slice_in_dim(q, lo, n, 0).transpose(1, 0, 2),
            jnp.arange(q.shape[1])))                           # [H, n, hd]
        out = {"ctx": ctx.transpose(1, 0, 2)}
        if records:
            # [n, T] a row: kept for the rows that are asked for alone (a
            # sequence's square is 485 MB a matrix at the chip's prompt)
            out.update(took=took, own=own[:, P:], scores=scores[:, P:],
                       sel=(sel if sel.ndim == 2 else sel[0])[:, P:])
        return out

    out = _blocks_of_rows(lambda lo, n: block(lo, n, keep_sel), T)
    x = x + jnp.einsum("shk,hkd->sd", out["ctx"], _dense(w["wo"], (0, 1)))
    # the last R rows' records (their attention is in ``out`` already)
    tail = block(T - R, R, True) if R else {
        "own": jnp.zeros((0, T), bool), "scores": jnp.zeros((0, T)),
        "took": jnp.zeros((0,), bool)}
    return x, {"k": k, "v": v, "ki": ki, "sel": out.get("sel"),
               "qi": qi[T - R:], "wi": wi[T - R:],
               "own": tail["own"], "scores": tail["scores"],
               "took": tail["took"]}


def forward(params: dict, tokens, sizes: dict, *, last: int | None = None,
            theirs=None, tie_band: float = 0.0, their_sel=None,
            sel_band: float = 0.0, positions=None, keep_sel: bool = False,
            faults=()) -> dict:
    """One sequence of token ids [T] through the decoder, float32:
    ``logits`` [T, vocab] (with ``last`` only those of the last ``last``
    positions); ``k``, ``v`` [L, T, KV, hd] and ``ki`` [L, T, di] (what each
    layer's caches would hold of every token); ``ids`` [L, T, k] the
    routers' picks. ``theirs`` [L, R, k] are another implementation's picks
    for the last R tokens, taken where they are a rightful top-k within
    ``tie_band`` (``took`` [L, R]); ``their_sel`` [L, R, T] bool its
    selections for the same rows, taken slot by slot where the slot lies
    within ``sel_band`` of the reference's cut (``their_slots_near_the_cut``;
    ``sel_took`` [L, R]: the rows whose whole set was rightful), with the
    reference's own sets ``own`` [L, R, T] and index scores ``scores``
    [L, R, T] of those rows beside them, and the operands the scores were
    made from: the rows' indexer queries ``qi`` [L, R, Hi, di] and head
    weights ``wi`` [L, R, Hi] (their keys are ``ki``).
    ``keep_sel`` also returns every row's set, ``sel`` [L, T, T].
    ``positions`` [T, 3]: None is text, (t, t, t)."""
    unknown = set(faults) - set(FAULTS) - set(EQUIVALENT)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    T = tokens.shape[0]
    L = params["layers"]["attn_norm"].shape[0]
    k_top = sizes["num_experts_per_tok"]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[:, None], (T, 3))
    if theirs is None:
        theirs = jnp.zeros((L, 0, k_top), jnp.int32)
    if their_sel is None:
        their_sel = jnp.zeros((L, 0, T), bool)
    experts = {n: params["layers"][n] for n in ("we_gate", "we_up", "we_down")}
    rest = {n: w for n, w in params["layers"].items() if n not in experts}
    eps = sizes["rms_norm_eps"]
    kept = {n: [] for n in ("k", "v", "ki", "qi", "wi", "ids", "took", "own",
                            "scores", "sel_took", "sel")}
    with jax.default_matmul_precision("highest"):
        x = _rows(params["embed"], tokens)
        prev_sel = None
        for l in range(L):
            w = _at(rest, l)
            if "indexer_of_another_layer" in faults and l:
                w = {**w, **_at({n: rest[n] for n in INDEXER_LEAVES}, l - 1)}
            x, rec = attention(
                x, w, sizes, positions, prev_sel, their_sel[l], sel_band,
                faults,
                keep_sel or "selection_of_previous_layer" in faults)
            prev_sel = rec["sel"]
            z = _rmsnorm(x, w["mlp_norm"], eps)
            logits = z @ w["router"].astype(jnp.float32)
            ids, weights = route(logits, sizes, faults)
            R = theirs.shape[1]
            took = jnp.zeros((0,), bool)
            if R:
                tail = logits[-R:]
                took = ties_broken_their_way(tail, theirs[l], tie_band)
                among = (theirs[l][:, :, None]
                         == jnp.arange(tail.shape[1])).any(1)
                t_ids, t_w = route(tail, sizes, faults, among)
                ids = ids.at[-R:].set(jnp.where(took[:, None], t_ids, ids[-R:]))
                weights = weights.at[-R:].set(
                    jnp.where(took[:, None], t_w, weights[-R:]))
            x = x + expert_ffn(z, ids, weights, experts, l)
            for n, a in (("k", rec["k"]), ("v", rec["v"]), ("ki", rec["ki"]),
                         ("qi", rec["qi"]), ("wi", rec["wi"]), ("ids", ids), ("took", took), ("own", rec["own"]),
                         ("scores", rec["scores"]),
                         ("sel_took", rec["took"])):
                kept[n].append(a)
            if keep_sel:
                kept["sel"].append(rec["sel"])
        x = _rmsnorm(x if last is None else x[-last:], params["final_norm"],
                     eps)
        out = {n: jnp.stack(a) for n, a in kept.items() if a}
        # the head a block of columns at a time (whole, in float32, it is
        # 1.2 GB beside the weights)
        head = params["lm_head"]
        V = jax.tree.leaves(head)[0].shape[-1]
        cuts = list(range(0, V, -(-V // 8))) + [V]
        out["logits"] = jnp.concatenate([
            x @ _dense(jax.tree.map(lambda a: a[..., lo:hi], head), (0,))
            for lo, hi in zip(cuts, cuts[1:])], -1)
        return out


def logits(params: dict, tokens, sizes: dict, *, last: int | None = None,
           faults=(), positions=None) -> jax.Array:
    """``forward``'s logits alone."""
    return forward(params, tokens, sizes, last=last, faults=faults,
                   positions=positions)["logits"]
