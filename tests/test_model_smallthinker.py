"""The SmallThinker family (models/smallthinker.py) and the expert layer it
shares with DeepSeek-V2 (models/experts.py), on the CPU at a tiny size: two
periods of [global, window, window, window], 8 experts top-2, a window
shorter than the prompts."""
from __future__ import annotations

import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_harness import engine as _engine, tokens as _tokens
from vnsum_tpu.models import MODEL_REGISTRY, experts, jitted_init, llama
from vnsum_tpu.models import smallthinker as st
from vnsum_tpu.models.family import family_of


@pytest.fixture(scope="module")
def tiny():
    cfg = st.tiny_smallthinker()
    return cfg, jitted_init(st.init_params, cfg, 0)


# -- the config ----------------------------------------------------------------


def test_published_config_and_its_period():
    cfg = st.smallthinker_21b_a3b()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.moe_intermediate, cfg.n_routed_experts,
            cfg.num_experts_per_tok, cfg.vocab_size, cfg.sliding_window) == (
        2560, 52, 28, 4, 128, 768, 64, 6, 151_936, 4096)
    assert cfg.q_per_kv == 7 and cfg.n_held == 64 and cfg.act == "relu"
    assert cfg.rope_theta == 1.5e6 and not cfg.tie_embeddings
    assert cfg.sliding_window_layout == cfg.rope_layout == (0, 1, 1, 1) * 13
    assert st.layer_windows(cfg) == (0, 4096, 4096, 4096) * 13
    assert MODEL_REGISTRY["smallthinker-21b-a3b"](n_layers=16) == \
        st.smallthinker_21b_a3b(n_layers=16)
    assert MODEL_REGISTRY["tiny-smallthinker"]() == st.tiny_smallthinker()


@pytest.mark.parametrize("kw, text", [
    (dict(sliding_window_layout=(0, 1)), "sliding_window_layout has 2"),
    (dict(rope_layout=(1,) * 9), "rope_layout has 9"),
    (dict(n_heads=5), "n_kv_heads must divide"),
])
def test_config_refuses_what_it_cannot_mean(kw, text):
    with pytest.raises(ValueError, match=text):
        st.tiny_smallthinker(**kw)


def test_llama_config_gained_no_field():
    """The family reuses llama's pieces, not its config."""
    names = {f.name for f in dataclasses.fields(llama.LlamaConfig)}
    assert names == {
        "vocab_size", "dim", "n_layers", "n_heads", "n_kv_heads", "head_dim",
        "intermediate", "rope_theta", "use_llama3_rope_scaling",
        "rope_scale_factor", "rope_low_freq_factor", "rope_high_freq_factor",
        "rope_original_max_len", "norm_eps", "max_seq_len", "tie_embeddings",
        "qk_norm", "act", "sandwich_norms", "norm_plus_one", "embed_scale",
        "query_scale", "sliding_window", "layer_is_global",
        "rope_local_theta", "rope_linear_factor", "w8a8_prefill",
        "loop_passes", "dtype"}


# -- routing -------------------------------------------------------------------


def test_route_is_top_k_on_the_logits_and_softmax_over_the_picked():
    logits = jnp.asarray([[0.1, 2.0, -1.0, 1.0], [3.0, 0.0, 0.5, 2.9]])
    ids, w = st.route(logits, 2)
    assert ids.tolist() == [[1, 3], [0, 3]] and ids.dtype == jnp.int32
    e = np.exp([2.0, 1.0])
    np.testing.assert_allclose(w[0], e / e.sum(), rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    # not the picks' shares of a softmax over all four
    assert abs(float(w[0, 0]) - float(jax.nn.softmax(logits[0])[1])) > 0.05


@pytest.mark.parametrize("act, fn", [
    ("relu", lambda x: np.maximum(x, 0)), ("silu", lambda x: x / (1 + np.exp(-x))),
    ("gelu_tanh", None)])
def test_mlp_act_knows_relu(act, fn):
    x = jnp.linspace(-3, 3, 13)
    got = np.asarray(llama._mlp_act(x, act))
    if fn is None:
        np.testing.assert_allclose(got, jax.nn.gelu(x, approximate=True))
    else:
        np.testing.assert_allclose(got, fn(np.asarray(x)), rtol=1e-6, atol=1e-7)


# -- the layer's mechanisms -----------------------------------------------------


def _shifted(cfg, params, toks, shift):
    """Logits with every position moved by ``shift``."""
    B, S = toks.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None] + shift, (B, S))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    return st.forward(params, cfg, toks, pos, st.init_cache(cfg, B, S), 0,
                      mask)[0]


@pytest.mark.parametrize("rope_layout", [
    (0,) * 8,           # position-free layers take no position
    (0, 1, 1, 1) * 2,   # rotary is relative: a common shift is none
])
def test_a_common_shift_of_the_positions_changes_nothing(tiny, rope_layout):
    _, params = tiny
    cfg = st.tiny_smallthinker(rope_layout=rope_layout)
    toks = _tokens()
    np.testing.assert_allclose(_shifted(cfg, params, toks, 0),
                               _shifted(cfg, params, toks, 11), atol=2e-5)


def test_rotary_layers_do_read_positions(tiny):
    """Stretching positions (not shifting them) changes rotary layers'
    result and leaves an all-global, position-free stack alone."""
    _, params = tiny
    toks = _tokens()
    B, S = toks.shape
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))

    def run(cfg, stretch):
        pos = jnp.broadcast_to(jnp.arange(S)[None] * stretch, (B, S))
        return st.forward(params, cfg, toks, pos, st.init_cache(cfg, B, S),
                          0, mask)[0]

    free = st.tiny_smallthinker(rope_layout=(0,) * 8)
    np.testing.assert_allclose(run(free, 1), run(free, 3), atol=2e-5)
    mixed = st.tiny_smallthinker()
    assert float(jnp.abs(run(mixed, 1) - run(mixed, 3)).max()) > 1e-3


@pytest.mark.parametrize("window, same", [(60, True), (256, True), (24, False),
                                          (8, False)])
def test_window_layers_drop_keys_past_the_window(tiny, window, same):
    """A window no shorter than the prompt is no window; a shorter one
    changes the result, on the window layers alone."""
    _, params = tiny
    toks = _tokens()
    whole = st.forward_dense(
        params, st.tiny_smallthinker(sliding_window_layout=(0,) * 8), toks)
    got = st.forward_dense(
        params, st.tiny_smallthinker(sliding_window=window), toks)
    assert (float(jnp.abs(got - whole).max()) < 2e-5) is same
    # the first ``window`` positions never leave it
    np.testing.assert_allclose(got[:, :min(window, 60)],
                               whole[:, :min(window, 60)], atol=2e-5)


def test_router_reads_the_layers_input_not_the_attention(tiny):
    """Zeroing the attention's output projection leaves every pick as it
    was: the router hangs on nothing the attention computes."""
    cfg, params = tiny
    toks = _tokens(rows=1)
    cut = dict(params, layers=dict(params["layers"],
                                   wo=jnp.zeros_like(params["layers"]["wo"])))
    S = toks.shape[1]
    pos = jnp.arange(S)[None]
    mask = jnp.tril(jnp.ones((S, S), bool))[None]

    def first_layer_picks(p):
        one = st.tiny_smallthinker(n_layers=1)
        p = dict(p, layers=jax.tree.map(lambda a: a[:1], p["layers"]))
        return st.forward(p, one, toks, pos, st.init_cache(one, 1, S), 0,
                          mask)[1]["picks"]

    np.testing.assert_array_equal(first_layer_picks(params),
                                  first_layer_picks(cut))


# -- state and counters ----------------------------------------------------------


def test_counters_count_every_real_token_and_pick(tiny):
    cfg, params = tiny
    toks = _tokens(rows=2)
    B, S = toks.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    # row 1 has a left pad of 7: those tokens attend nothing
    pads = jnp.asarray([0, 7])
    mask = llama.prefill_attention_mask(pads, S, S)
    _, cache = st.forward(params, cfg, toks, pos, st.init_cache(cfg, B, S),
                          0, mask)
    real = 2 * S - 7
    k, L = cfg.num_experts_per_tok, cfg.n_layers
    assert int(cache["slots_routed"]) == int(cache["slots_held"]) == real * k * L
    assert cache["expert_tokens"].shape == (L, cfg.n_held)
    assert int(cache["expert_tokens"].sum()) == real * k * L
    assert (np.asarray(cache["expert_tokens"]).sum(1) == real * k).all()
    assert st.last_picks(cache).shape == (L, B, k)
    # a multi-token forward is no decode step
    assert int(cache["decode_touched"]) == int(cache["decode_layer_steps"]) == 0
    assert set(st.counters(cache)) == {
        "expert_tokens", "slots_routed", "slots_held", "decode_touched",
        "decode_layer_steps", "decode_tiles_used", "decode_tiles_walked"}


def test_decode_steps_count_the_distinct_experts_they_touch(tiny):
    cfg, params = tiny
    B, C = 3, 16
    cache = st.init_cache(cfg, B, C)
    toks = _tokens(n=4, rows=B)
    touched = 0
    for t in range(4):
        mask = jnp.broadcast_to(jnp.arange(C)[None, None] <= t, (B, 1, C))
        before = np.asarray(cache["expert_tokens"])
        _, cache = st.forward(params, cfg, toks[:, t:t + 1],
                              jnp.full((B, 1), t), cache, t, mask)
        step = np.asarray(cache["expert_tokens"]) - before
        assert (step.sum(1) == B * cfg.num_experts_per_tok).all()
        touched += int((step > 0).sum())
    assert int(cache["decode_touched"]) == touched
    assert int(cache["decode_layer_steps"]) == 4 * cfg.n_layers
    k = cfg.num_experts_per_tok
    assert 4 * cfg.n_layers * k <= touched <= 4 * cfg.n_layers * min(8, B * k)


def test_decode_steps_count_the_tiles_their_slots_fill(tiny):
    """Beside the experts touched: the row tiles of the grouped product that
    held a slot, recomputed here from each step's picks, and the tiles its
    grid walked — the same number, since the grid's bound is the tiles
    used. 40 rows x 2 picks on 8 experts: some expert fills a second tile
    of 16. With no grouped product (the dense path) there are no tiles."""
    cfg, params = tiny
    B, C, tm = 40, 16, 16
    toks = _tokens(n=3, rows=B)
    grouped = functools.partial(experts.grouped_experts, cfg=cfg,
                                interpret=True)
    for experts_fn in (grouped, None):
        cache, tiles, touched = st.init_cache(cfg, B, C), 0, 0
        for t in range(3):
            mask = jnp.broadcast_to(jnp.arange(C)[None, None] <= t, (B, 1, C))
            before = np.asarray(cache["expert_tokens"])
            _, cache = st.forward(params, cfg, toks[:, t:t + 1],
                                  jnp.full((B, 1), t), cache, t, mask,
                                  experts_fn=experts_fn)
            step = np.asarray(cache["expert_tokens"]) - before
            tiles += int((-(-step // tm)).sum())
            touched += int((step > 0).sum())
        assert int(cache["decode_touched"]) == touched < tiles
        want = tiles if experts_fn else 0
        assert int(cache["decode_tiles_used"]) == want
        assert int(cache["decode_tiles_walked"]) == want


def test_deepseek_state_has_no_decode_counter():
    """The counter is the family's to ask for: DeepSeek-V2's programs carry
    what they carried."""
    from vnsum_tpu.models import deepseek as ds

    cache = ds.init_cache(ds.tiny_deepseek(), 2, 8)
    assert set(cache) == {"latent", "expert_tokens", "slots_routed",
                          "slots_held", "picks"}
    assert set(ds.counters(cache)) == {"expert_tokens", "slots_routed",
                                       "slots_held"}


# -- the shared expert layer ------------------------------------------------------


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("act", ["relu", "silu"])
def test_grouped_experts_agree_with_dense_experts(tiny, act, int8):
    """The kernel path (interpreted) against the plain masked sum, ReGLU and
    SwiGLU, float and int8 weights, some picks not held."""
    from vnsum_tpu.models.quant import quantize_params

    _, params = tiny
    cfg = st.tiny_smallthinker(act=act)
    if int8:
        params = quantize_params(params)
    stacked = {n: params["layers"][n] for n in experts.EXPERT_LEAVES}
    x = jax.random.normal(jax.random.key(4), (40, cfg.dim))
    ids = jax.random.randint(jax.random.key(5), (40, 2), -1, 8)
    w = jax.random.uniform(jax.random.key(6), (40, 2))
    dense = experts.dense_experts(x, ids, w, stacked, 3, cfg)
    grouped = experts.grouped_experts(x, ids, w, stacked, 3, cfg,
                                      interpret=True)
    assert float(jnp.abs(dense).max()) > 1e-3
    np.testing.assert_allclose(grouped, dense, atol=1e-5)


@pytest.mark.parametrize("rows", ["float", "int8"])
@pytest.mark.parametrize("n_experts", [8, 64])
@pytest.mark.parametrize("T", [4, 12, 24])
def test_a_decode_steps_grouped_experts_agree_with_dense_experts(T, n_experts,
                                                                 rows):
    """A decode step of 4, 12 and 24 rows (the reduce's, Laguna's and
    SmallThinker's map), fewer experts than slots and more: the rows built
    around T * k slots, the grid over the tiles they fill, one gather
    back."""
    cfg = st.tiny_smallthinker(n_routed_experts=n_experts,
                               w8a8_prefill=rows == "int8")
    stacked = _stacked(st.init_params(jax.random.key(2), cfg), rows == "int8")
    x, ids, w = _layer_inputs(cfg, T, 2)
    dense = experts.dense_experts(x, ids, w, stacked, 5, cfg)
    grouped = experts.grouped_experts(x, ids, w, stacked, 5, cfg,
                                      interpret=True)
    scale = float(jnp.abs(dense).max())
    assert scale > 1e-3
    np.testing.assert_allclose(grouped, dense,
                               atol=0.03 * scale if rows == "int8" else 1e-5)


def test_a_decode_step_with_no_slot_held_adds_nothing(tiny):
    """No pick held here (a DeepSeek-V2 reduce step can be one): the product
    takes no grid step, and the way back selects zeros, never its rows."""
    cfg, params = tiny
    x, ids, w = _layer_inputs(cfg, 12, 2, not_held=1.1)
    assert int(ids.max()) == -1
    got = experts.grouped_experts(x, ids, w, _stacked(params, True), 1, cfg,
                                  interpret=True)
    assert got.shape == x.shape and not bool(jnp.any(got))


def _layer_inputs(cfg, T, k, seed=4, not_held=0.2):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (T, cfg.dim))
    _, ids = jax.lax.top_k(jax.random.normal(ks[1], (T, cfg.n_held)), k)
    ids = jnp.where(jax.random.uniform(ks[2], (T, k)) < not_held, -1, ids)
    return x, ids.astype(jnp.int32), jax.random.uniform(ks[3], (T, k)) + 0.1


def _stacked(params, int8):
    from vnsum_tpu.models.quant import quantize_params

    if int8:
        params = quantize_params(params)
    return {n: params["layers"][n] for n in experts.EXPERT_LEAVES}


@pytest.mark.parametrize("T", [150, 1100])   # the gather back, the kernel back
@pytest.mark.parametrize("rows", ["float", "int8"])
@pytest.mark.parametrize("act", ["relu", "silu"])
def test_grouped_experts_across_a_piece_boundary(tiny, monkeypatch, T, rows,
                                                 act):
    """Three pieces of the map, the last one padded: float rows on int8
    weights and int8 rows (W8A8), both gates, below and above the size at
    which ``expert_combine`` brings the rows back."""
    _, params = tiny
    cfg = st.tiny_smallthinker(act=act, w8a8_prefill=rows == "int8")
    stacked = _stacked(params, True)
    monkeypatch.setattr(experts, "_EXPERT_PIECE_TOKENS", -(-T // 3) + 1)
    x, ids, w = _layer_inputs(cfg, T, 2)
    dense = experts.dense_experts(x, ids, w, stacked, 2, cfg)
    grouped = experts.grouped_experts(x, ids, w, stacked, 2, cfg,
                                      interpret=True)
    scale = float(jnp.abs(dense).max())
    assert scale > 1e-3
    # int8 rows round the activations twice; float rows only reorder sums
    np.testing.assert_allclose(grouped, dense,
                               atol=0.03 * scale if rows == "int8" else 1e-5)


@pytest.mark.parametrize("T", [40, 1024])
@pytest.mark.parametrize("rows", ["float", "int8"])
def test_rows_of_a_skipped_tile_never_reach_the_output(tiny, monkeypatch, T,
                                                       rows):
    """What the product leaves in the tiles past ``tiles_used`` is
    unspecified: made NaN here, in both products. The way back selects (or
    never fetches); a sum weighted with zeros would give NaN."""
    from vnsum_tpu.ops import expert_matmul as em

    product = em.expert_grouped_matmul

    def poisoned(lhs, lhs_scale, w, w_up, layer, tile_expert, tiles_used,
                 **kw):
        out = product(lhs, lhs_scale, w, w_up, layer, tile_expert,
                      tiles_used, **kw)
        row_tile = jnp.arange(out.shape[0])[:, None] // kw["tm"]
        return jnp.where(row_tile < tiles_used[0], out, jnp.nan)

    monkeypatch.setattr(em, "expert_grouped_matmul", poisoned)
    _, params = tiny
    cfg = st.tiny_smallthinker(w8a8_prefill=rows == "int8")
    stacked = _stacked(params, True)
    x, ids, w = _layer_inputs(cfg, T, 2, not_held=0.5)
    got = experts.grouped_experts(x, ids, w, stacked, 1, cfg, interpret=True)
    assert bool(jnp.isfinite(got).all())
    dense = experts.dense_experts(x, ids, w, stacked, 1, cfg)
    scale = float(jnp.abs(dense).max())
    np.testing.assert_allclose(got, dense,
                               atol=0.03 * scale if rows == "int8" else 1e-5)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("T", [24, 2048])   # a decode step, a prefill piece
def test_expert_layer_lowers_to_one_permutation(tiny, T):
    """The structure around the grouped product: no scatter, no running sum
    down an [N, E] one-hot, and the row-wide gathers do not multiply with k:
    one in all (a prefill piece gathers its rows into expert order and
    ``expert_combine`` brings them back; a decode step picks its rows with
    a 0/1 product and gathers them back)."""
    _, params = tiny
    cfg = st.tiny_smallthinker(w8a8_prefill=True)
    stacked = _stacked(params, True)

    def wide_gathers(k):
        x, ids, w = _layer_inputs(cfg, T, k, not_held=0.0)
        jaxpr = jax.make_jaxpr(lambda *a: experts.grouped_experts(
            *a, stacked, 0, cfg, interpret=True))(x, ids, w)
        names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
        assert not any("scatter" in n for n in names)
        for e in _eqns(jaxpr.jaxpr):
            if e.primitive.name.startswith("cum"):   # tables, not slots
                assert T * k not in e.invars[0].aval.shape, e
        assert "sort" in names
        return sum(1 for e in _eqns(jaxpr.jaxpr)
                   if e.primitive.name == "gather"
                   and e.outvars[0].aval.shape[-1:] == (cfg.dim,))

    few, many = wide_gathers(2), wide_gathers(6)
    assert few == many == 1


def test_expert_matmul_refuses_an_unknown_gate():
    from vnsum_tpu.ops.expert_matmul import expert_grouped_matmul

    w = jnp.zeros((1, 1, 8, 128))
    with pytest.raises(ValueError, match="silu or relu"):
        expert_grouped_matmul(
            jnp.zeros((16, 8)), None, w, w, 0, jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.int32), tm=16, tn=128, out_dtype=jnp.float32,
            act="gelu", interpret=True)


# -- the engine's seam ------------------------------------------------------------


def test_family_resolves_and_names_what_it_lacks():
    fam = family_of(st.tiny_smallthinker())
    assert fam is st.FAMILY and fam.name == "smallthinker"
    assert fam.int8_cache and fam.counts_prefill_blocks
    assert fam.layer_windows(st.tiny_smallthinker()) == (0, 24, 24, 24) * 2
    assert set(fam.missing) == {"slot loop", "prefix cache", "mesh",
                                "speculative decoding",
                                "long-context backend"}


@pytest.mark.parametrize("entry", sorted(st.FAMILY.missing))
def test_family_refuses_by_the_text_of_what_it_lacks(entry):
    with pytest.raises(NotImplementedError) as e:
        st.FAMILY.refuse(entry)
    assert "smallthinker" in str(e.value) and entry in str(e.value)
    assert st.FAMILY.missing[entry] in str(e.value)
    assert len(st.FAMILY.missing[entry]) > 60   # says what, not just no


@pytest.mark.parametrize("kw", [dict(cache_blocks=8), dict(mesh=object())])
def test_engine_refuses_the_entries_at_construction(tiny, kw):
    from vnsum_tpu.backend.engine import TpuBackend

    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="smallthinker"):
        TpuBackend(model_config=cfg, params=params, interpret=True, **kw)


def test_llama_family_hands_out_gemmas_windows():
    from vnsum_tpu.models import gemma3_4b, qwen3_8b

    windows = llama.FAMILY.layer_windows(gemma3_4b())
    assert len(windows) == 34 and set(windows) == {0, 1024}
    assert [i for i, w in enumerate(windows) if not w] == [5, 11, 17, 23, 29]
    assert llama.FAMILY.layer_windows(qwen3_8b()) is None
    with pytest.raises(ValueError, match="layer_is_global has 2"):
        llama.FAMILY.layer_windows(
            gemma3_4b(layer_is_global=(True, False)))


@pytest.mark.parametrize("quantize_kv", [False, True])
def test_engine_generates_through_the_kernels_with_a_window(tiny, quantize_kv):
    """``TpuBackend.generate`` with the kernels interpreted: chunked
    prefill, the window a per-layer scalar, counters returned with the
    output, the prefill's cells counted by class with the window."""
    cfg, params = tiny
    be = _engine(cfg, params, batch_size=2, max_new_tokens=6,
                 quantize_kv=quantize_kv, fresh=True)
    # the engine's logger does not propagate: listen on it directly
    log, heard = logging.getLogger("vnsum.engine"), []
    listener = logging.Handler()
    listener.emit = lambda record: heard.append(record.getMessage())
    log.addHandler(listener)
    try:
        outs = be.generate(["xin chào " * 22, "một hai ba"],
                           max_new_tokens=6)
    finally:
        log.removeHandler(listener)
    st_ = be.stats
    assert len(outs) == 2
    assert list(st_.attention_paths.values()) == [
        {"prefill": "kernel", "decode": "kernel"}]
    assert st_.expert_slots_held == st_.expert_slots_routed > 0
    assert np.asarray(st_.expert_tokens).shape == (8, 8)
    assert int(np.sum(st_.expert_tokens)) == st_.expert_slots_held
    assert st_.expert_decode_layer_steps == 6 * 8
    assert 6 * 8 * 2 <= st_.expert_decode_touched <= 6 * 8 * 4
    # 4 slots a step: a touched expert fills one tile, and the grid walks
    # the tiles used
    assert st_.expert_decode_tiles_used == st_.expert_decode_tiles_walked \
        == st_.expert_decode_touched
    (line,) = [m for m in heard if m.startswith("dispatch B=")]
    assert line.endswith(
        f"expert tiles used {st_.expert_decode_tiles_used} of "
        f"{st_.expert_decode_tiles_walked} walked (1.000)")
    assert sum(st_.prefill_blocks.values()) > 0
    window = be._layer_window_fn()
    assert [int(window(i)) for i in range(8)] == [0, 24, 24, 24] * 2
    with pytest.raises(NotImplementedError, match="slot loop"):
        be.start_slot_loop(2)
