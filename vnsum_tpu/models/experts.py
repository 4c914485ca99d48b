"""The sparse-expert layer two families share: from a router's picks to the
weighted sum of the experts this chip holds, with the counters.

A family owns its router's RULE (``models/deepseek.py``: softmax over all
experts, group-limited top-k, scaled; ``models/smallthinker.py``: top-k on
the logits, softmax over the picked ones) and hands it to ``expert_layer``
as a function. Everything after the rule is here, once: the picks become
local ids of the experts held (``expert_offset``, ``n_held`` of the config;
a pick outside them adds nothing on this chip), the counters are summed
into the state the program carries, and the product runs through
``grouped_experts`` (``ops/expert_matmul.py``, no capacity, nothing
dropped) or, with no kernel, ``dense_experts``.

``grouped_experts`` is one permutation around two products: a sort puts the
slots into expert order and carries each slot's routing weight and its
token's int8 scale along (``expert_layout``), ONE row gather builds the
experts' rows, the weight goes into the down product's per-row factor, and
the rows come back summed a token by ``expert_combine`` (a prefill piece)
or by one gather (a decode step). Which of the two, like the row tile,
follows the static token count alone.

What a config has to say: ``n_held``, ``expert_offset``,
``moe_intermediate``, ``num_experts_per_tok``, ``act`` (the gate's
activation; for experts with no gate, the front product's own: ``relu2``),
``w8a8_prefill``. Which of the two forms an expert has is read off the
leaves the family hands over: ``EXPERT_LEAVES`` (gate, up, down:
``down(act(gate x) * up x)``) or ``UNGATED_EXPERT_LEAVES`` (up, down:
``down(act(up x))``). What the state holds (``init_expert_state``):
``expert_tokens`` [expert layers, held experts], ``slots_routed``,
``slots_held``, ``picks`` (the routers' picks for the last token of the
latest forward) and, where a family asks for it, ``decode_touched`` /
``decode_layer_steps``: distinct experts with at least one token, summed
over the single-token forwards and layers, and how many such (step, layer)
pairs were counted — what a decode step's expert bytes are — with
``decode_tiles_used`` / ``decode_tiles_walked``: the row tiles of the
grouped product that hold a slot, and those its grid takes a step for (the
same sum since the grid's row bound is ``tiles_used``: no step for a tile
no slot fills), summed over the same pairs where the product runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .llama import _mlp_act

EXPERT_LEAVES = ("we_gate", "we_up", "we_down")
# an expert with no gate: two matrices (Nemotron-H's relu2 experts)
UNGATED_EXPERT_LEAVES = ("we_up", "we_down")
# the scalars a family asks for with ``decode_touched``
_DECODE_COUNTERS = ("decode_touched", "decode_layer_steps",
                    "decode_tiles_used", "decode_tiles_walked")
_COUNTERS = ("expert_tokens", "slots_routed", "slots_held", *_DECODE_COUNTERS)


def init_expert_state(n_layers: int, n_held: int, batch: int, top_k: int, *,
                      decode_touched: bool = False) -> dict:
    """The expert layer's part of what a program carries."""
    state = {
        "expert_tokens": jnp.zeros((n_layers, n_held), jnp.int32),
        "slots_routed": jnp.zeros((), jnp.int32),
        "slots_held": jnp.zeros((), jnp.int32),
        # what each expert layer's router picked for the last token of the
        # latest forward, row by row (``last_picks``)
        "picks": jnp.zeros((n_layers, batch, top_k), jnp.int32),
    }
    if decode_touched:
        state.update({k: jnp.zeros((), jnp.int32) for k in _DECODE_COUNTERS})
    return state


def counters(cache: dict) -> dict:
    """The expert counters of a program's final state."""
    return {k: cache[k] for k in _COUNTERS if k in cache}


def last_picks(cache: dict) -> jax.Array:
    """[expert layers, B, k] expert ids: the routers' picks for the last
    token of the latest forward. A parity check needs them: where two
    experts score within rounding of each other the program and a reference
    may each rightly pick another (``TpuBackend.prefill_then_decode_logits``
    hands them out position by position)."""
    return cache["picks"]


def sigmoid_route(logits: jax.Array, bias: jax.Array, top_k: int,
                  scaling: float, denominator_eps: float = 0.0):
    """logits [T, E] float32, bias [E] -> (expert ids [T, k] int32, weights
    [T, k]): sigmoid scores over ALL experts; the ``top_k`` largest of
    score + bias; the weights are the picked experts' scores WITHOUT the
    bias, renormalised to sum to one (``norm_topk_prob``; over the sum +
    ``denominator_eps`` where a family's published code adds one), times
    ``scaling``.

    One rule, two families: ``models/nemotron_h.py`` (128 experts top-6,
    scaling 2.5, no epsilon) and ``models/lfm2.py`` (32 experts top-4,
    scaling 1, ``+ 1e-6``)."""
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    total = jnp.sum(picked, axis=-1, keepdims=True)
    if denominator_eps:
        total = total + denominator_eps
    return ids.astype(jnp.int32), picked / total * scaling


def sigmoid_group_route(logits: jax.Array, bias: jax.Array, top_k: int,
                        n_group: int, topk_group: int, scaling: float,
                        group_top: int = 2):
    """``sigmoid_route`` under a GROUP LIMIT (DeepSeek-V3's ``noaux_tc``
    rule, which ``models/ling.py`` routes 512 experts by): logits [T, E]
    float32, bias [E] -> (expert ids [T, k] int32, weights [T, k]). With
    ``c = sigmoid(logits) + bias``: the experts fall into ``n_group`` runs of
    ``E / n_group``; a group scores the sum of its ``group_top`` largest
    ``c``; the ``topk_group`` best groups are kept and the ``top_k`` largest
    ``c`` among THEIR experts picked; the weights are the picked experts'
    scores WITHOUT the bias, renormalised to sum to one, times ``scaling``.
    (``models/deepseek.py::route`` is the softmax rule, a group scored by
    its largest member alone.)"""
    scores = jax.nn.sigmoid(logits)
    ranked = scores + bias
    T, E = ranked.shape
    best, _ = jax.lax.top_k(ranked.reshape(T, n_group, E // n_group),
                            group_top)
    _, keep = jax.lax.top_k(jnp.sum(best, axis=-1), topk_group)   # [T, kg]
    group_kept = jnp.any(
        keep[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
    kept = jnp.repeat(group_kept, E // n_group, axis=1)
    _, ids = jax.lax.top_k(jnp.where(kept, ranked, -jnp.inf), top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * scaling
    return ids.astype(jnp.int32), weights


def _quantize_rows(x: jax.Array):
    """x [M, K] -> (int8, per-row float32 scale [M, 1])."""
    x32 = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True), 1e-8) / 127.0
    return jnp.clip(jnp.round(x32 / s), -127, 127).astype(jnp.int8), s


# the most an int8 weight tile [K, tn] may take where the rule below falls
# back to the whole width: double-buffered it stays under a third of the
# product kernel's VMEM limit (Nemotron-H's 2688 x 1856 is 5.0 MB)
_WHOLE_WIDTH_TILE_BYTES = 8 * 1024 * 1024


def _column_tile(K: int, N: int) -> int:
    """Columns of a weight tile: the widest divisor of N in whole lanes
    whose int8 tile [K, tn] stays under ~2.8 MB of VMEM. A width with no
    such divisor — one that is not whole lanes (1,856 = 14.5 of them), or a
    K so deep that no whole-lane share of N fits — takes the WHOLE width:
    the one block that is not whole lanes Mosaic takes is the array's own
    dim (its last lane tile is masked). That tile may be larger than the
    rule's bound, and is refused past ``_WHOLE_WIDTH_TILE_BYTES``."""
    fits = [d for d in range(128, N + 1, 128)
            if N % d == 0 and d * K <= 2_800_000]
    if fits:
        return max(fits)
    if K * N > _WHOLE_WIDTH_TILE_BYTES:
        raise ValueError(
            f"no whole-lane divisor of {N} columns fits a [{K}, tn] int8 "
            f"tile, and the whole width is {K * N} bytes: store the experts "
            "padded to whole lanes")
    return N


# tokens one grouped product takes at once: bounds the worst-case row
# buffers (every pick of every token held here) at ~1.2 GB at DeepSeek-V2's
# widths, while an expert still sees ~300 rows a weight fetch
_EXPERT_PIECE_TOKENS = 8192


def _int8_rows(experts, cfg) -> bool:
    """int8 rows (s8 x s8) whenever the weights are int8 and the engine runs
    W8A8: in a decode step too, where converting each expert's weight tile
    to bf16 in the kernel would cost more than fetching it."""
    return isinstance(experts["we_down"], dict) and cfg.w8a8_prefill


def _row_tile(T: int, int8_rows: bool) -> int:
    """Rows of a tile of the grouped product over T tokens: a prefill
    piece's, or a decode step's few tokens'."""
    return 256 if T >= 1024 else (32 if int8_rows else 16)


def by_rows(fn, h, max_tokens: int):
    """``fn(h)`` over h [B, S, D], a few batch rows at a time where the
    whole is more than ``max_tokens`` tokens: a 12,288-wide SwiGLU over a
    chunk of 24 rows would hold gigabytes of intermediates."""
    B, S, _ = h.shape
    R = max((r for r in range(1, B + 1)
             if B % r == 0 and r * S <= max_tokens), default=1)
    if R == B:
        return fn(h)
    out = jax.lax.map(fn, h.reshape((B // R, R) + h.shape[1:]))
    return out.reshape((B,) + out.shape[2:])


def grouped_experts(x, local, weights, experts, slot, cfg, *,
                    interpret: bool):
    """The routed experts held here, through ``expert_grouped_matmul``.

    x [T, D]; ``local`` [T, k] the picks as local expert ids, -1 where a pick
    is not held (or the token is padding); ``weights`` [T, k]; ``experts``
    the STACKED ``we_gate``/``we_up``/``we_down`` of every expert layer
    (``we_up``/``we_down`` alone for experts with no gate) and ``slot`` this
    layer's index in them (the kernel reads the stack in place). Returns the
    weighted sum over each token's held picks, [T, D].

    Around the two products there is one permutation (``expert_layout``):
    token rows are gathered into expert order once, the routing weight goes
    into the down product's rows there (the per-row factor the kernel
    applies: the rows' int8 scale times the weight, or the weight alone),
    and a token's k rows come back summed in float32 (``expert_combine``,
    or one gather where T is a decode step's)."""
    from ..ops.expert_matmul import (
        expert_combine,
        expert_grouped_matmul,
        expert_layout,
    )

    T, D = x.shape
    k = local.shape[1]
    # a prefill piece, or a decode step's few tokens: the row tile and the
    # two ends of the permutation follow that, and nothing else
    prefill = T >= 1024
    int8_rows = _int8_rows(experts, cfg)
    tm = _row_tile(T, int8_rows)
    # the width the experts are stored at (a family may store them wider
    # than ``moe_intermediate``, padded with zeros to whole lanes)
    F = jax.tree.leaves(experts["we_down"])[0].shape[-2]

    def take(a, idx):   # every index below is a slot's or a row's own
        return a.at[idx].get(mode="promise_in_bounds")

    def piece(args):
        x, local, weights = args
        dtype = x.dtype
        carry = [weights.reshape(-1).astype(jnp.float32)]
        if int8_rows:
            x, xs = _quantize_rows(x)
            carry.append(jnp.repeat(xs[:, 0], k))
        row_of_slot, slot_of_row, tile_expert, used, _sizes, _M, carried = \
            expert_layout(local.reshape(-1), cfg.n_held, tm, carry)
        # a row of padding takes the token of its tile's first row (a real
        # one in every tile the product computes): nobody reads its product,
        # but ``expert_combine`` may meet it beside a row it asked for
        first = jnp.broadcast_to(slot_of_row.reshape(-1, tm)[:, :1],
                                 (slot_of_row.shape[0] // tm, tm))
        token_of_row = jnp.maximum(
            jnp.where(slot_of_row < 0, first.reshape(-1), slot_of_row),
            0) // k
        if prefill:
            rows = take(x, token_of_row)
        else:
            # a decode step builds rows of padding by the dozen for a real
            # one: a product with a 0/1 matrix picks them (exactly) faster
            # than a gather
            rows = jax.lax.dot(
                (token_of_row[:, None] == jnp.arange(x.shape[0])[None, :]
                 ).astype(x.dtype), x,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=(jnp.int32 if int8_rows
                                        else jnp.float32)).astype(x.dtype)
        factor = carried[0][:, None]
        call = dict(layer=slot, tile_expert=tile_expert, tiles_used=used,
                    tm=tm, interpret=interpret)
        # gated: act(gate x) * (up x); no gate: act(up x), one product
        hidden = expert_grouped_matmul(
            rows, carried[1][:, None] if int8_rows else None,
            *((experts["we_gate"], experts["we_up"]) if "we_gate" in experts
              else (experts["we_up"], None)),
            tn=_column_tile(D, F), act=cfg.act, out_dtype=dtype, **call)
        if int8_rows:
            hidden, scale = _quantize_rows(hidden)
            factor = factor * scale
        y = expert_grouped_matmul(
            hidden, factor, experts["we_down"], None, tn=_column_tile(F, D),
            out_dtype=dtype, **call)
        if prefill:
            # a tile of tokens fetches its rows a range an expert and sums
            # them in VMEM; it reads no row of a tile the product skipped
            # (the barrier keeps XLA from fusing the kernel into the map's
            # stacking of its results, where it loses its VMEM limit)
            return jax.lax.optimization_barrier(expert_combine(
                y, local.reshape(-1), row_of_slot, n_experts=cfg.n_held,
                k=k, interpret=interpret))
        # a decode step's few rows: one gather, pick-major. Rows of tiles
        # the product skipped are unspecified: select, never a product with
        # a zero weight
        picked = jnp.where(
            (local.T >= 0)[:, :, None],
            take(y, row_of_slot.reshape(-1, k).T).astype(jnp.float32), 0.0)
        out = picked[0]
        for j in range(1, k):
            out = out + picked[j]
        return out.astype(dtype)

    n = -(-T // _EXPERT_PIECE_TOKENS)
    if n == 1:
        return piece((x, local, weights))
    Tp = -(-T // n)
    pad = n * Tp - T
    x, weights = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                  for a in (x, weights))
    local = jnp.pad(local, ((0, pad), (0, 0)), constant_values=-1)
    out = jax.lax.map(piece, tuple(
        a.reshape((n, Tp) + a.shape[1:]) for a in (x, local, weights)))
    return out.reshape(n * Tp, D)[:T]


def dense_experts(x, local, weights, experts, slot, cfg):
    """The same sum with no kernel: every held expert over every token,
    masked. For the dense XLA path at small sizes."""
    from .quant import dequantize_leaf

    w = {n: dequantize_leaf(jax.tree.map(lambda a: a[slot], leaf), (1,)
                            ).astype(x.dtype)
         for n, leaf in experts.items()}
    gate = (local[:, :, None] == jnp.arange(cfg.n_held)[None, None, :])
    per_expert = jnp.sum(
        jnp.where(gate, weights[:, :, None], 0.0), axis=1)       # [T, E]
    h = jnp.einsum("td,edf->tef", x, w["we_up"])
    if "we_gate" in w:
        h = _mlp_act(jnp.einsum("td,edf->tef", x, w["we_gate"]), cfg.act) * h
    else:
        h = _mlp_act(h, cfg.act)
    y = jnp.einsum("tef,efd->ted", h, w["we_down"])
    return jnp.einsum("ted,te->td", y, per_expert.astype(x.dtype))


def expert_layer(x, picks, real, experts, slot, cache, cfg, experts_fn,
                 rows: int, cache_rows=None):
    """One layer's routed experts over x [T, D] (``rows`` batch rows of
    T / rows tokens each): ``picks()`` is the family's routing rule and
    gives (expert ids [T, k] int32 over ALL routed experts, weights [T, k]);
    ``real`` (T booleans, any shape) says which tokens are not under a
    row's left pad (such a token is routed nowhere and counted nowhere).
    ``cache_rows`` [rows] int32: x is a row piece of a batch whose state
    holds more rows (the engine's prefill), and row b's picks are kept at
    the state's batch row ``cache_rows[b]``. Returns (the weighted
    sum over each token's picks held here [T, D], the state with this
    layer's counts added)."""
    with jax.named_scope("router"):
        ids, weights = picks()
        real = real.reshape(-1, 1)
        local = ids - cfg.expert_offset
        held = (local >= 0) & (local < cfg.n_held) & real
        local = jnp.where(held, local, -1)
        tokens = jnp.sum(
            local.reshape(-1, 1) == jnp.arange(cfg.n_held)[None, :],
            axis=0, dtype=jnp.int32)
        picks_at = (cache["picks"].at[slot] if cache_rows is None
                    else cache["picks"].at[slot, cache_rows])
        cache = dict(
            cache,
            expert_tokens=cache["expert_tokens"].at[slot].add(tokens),
            slots_routed=cache["slots_routed"]
            + jnp.sum(real, dtype=jnp.int32) * ids.shape[1],
            slots_held=cache["slots_held"] + jnp.sum(held, dtype=jnp.int32),
            picks=picks_at.set(
                ids.reshape(rows, x.shape[0] // rows, -1)[:, -1]),
        )
        if "decode_touched" in cache and x.shape[0] == rows:
            # a single-token forward: a decode step. Each expert with a
            # token is read once, whatever the number of its tokens
            cache.update(
                decode_touched=cache["decode_touched"]
                + jnp.sum(tokens > 0, dtype=jnp.int32),
                decode_layer_steps=cache["decode_layer_steps"] + 1)
            if experts_fn is not None:
                # the grouped product's grid: a step a column tile for each
                # row tile that holds a slot, and for no other
                tm = _row_tile(rows, _int8_rows(experts, cfg))
                tiles = jnp.sum(-(-tokens // tm), dtype=jnp.int32)
                cache.update(
                    decode_tiles_used=cache["decode_tiles_used"] + tiles,
                    decode_tiles_walked=cache["decode_tiles_walked"] + tiles)
    with jax.named_scope("experts"):
        routed = (experts_fn or functools.partial(dense_experts, cfg=cfg))(
            x, local, weights, experts, slot)
    return routed, cache
