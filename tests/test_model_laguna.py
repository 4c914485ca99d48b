"""The Laguna family (models/laguna.py) on the CPU at a tiny size: the dense
layer and two periods of [sliding, sliding, sliding, full], 3 and 2 query
heads a KV head, 16 experts top-4 with a shared one, a window shorter than
the prompts."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_harness import engine as _engine, tokens as _tokens
from vnsum_tpu.models import MODEL_REGISTRY, jitted_init, llama
from vnsum_tpu.models import laguna as lg
from vnsum_tpu.models.family import family_of


@pytest.fixture(scope="module")
def tiny():
    cfg = lg.tiny_laguna()
    return cfg, jitted_init(lg.init_params, cfg, 0)


# -- the config ----------------------------------------------------------------


def test_published_config_and_its_period():
    cfg = lg.laguna_s_2_1()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_heads_sliding,
            cfg.n_kv_heads, cfg.head_dim, cfg.intermediate,
            cfg.moe_intermediate, cfg.shared_intermediate,
            cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.vocab_size,
            cfg.sliding_window) == (
        3072, 48, 48, 72, 8, 128, 12_288, 1024, 1024, 256, 10, 100_352, 512)
    assert cfg.n_held == 256 and cfg.act == "silu" and not cfg.tie_embeddings
    assert cfg.routed_scaling_factor == 2.5 and cfg.n_dense_layers == 1
    assert (cfg.rope_theta, cfg.rope_local_theta, cfg.rotary_dims) == (
        500_000.0, 10_000.0, 64)
    assert cfg.sliding_layout == (0, 1, 1, 1) * 12
    assert cfg.heads_per_layer == (48, 72, 72, 72) * 12
    assert lg.layer_windows(cfg) == (0, 512, 512, 512) * 12
    assert lg.layer_groups(cfg) == (6, 9, 9, 9) * 12
    # after the dense layer: eleven whole periods and three sliding layers
    assert cfg.period == (1, 1, 1, 0) and cfg.periods == (11, (1, 1, 1))
    cut = lg.laguna_s_2_1(n_layers=5)
    assert cut.sliding_layout == (0, 1, 1, 1, 0) and cut.periods == (1, ())
    assert not hasattr(cfg, "q_per_kv")   # no one number says it
    assert MODEL_REGISTRY["laguna-s-2.1"](n_layers=5) == cut
    assert MODEL_REGISTRY["tiny-laguna"]() == lg.tiny_laguna()


@pytest.mark.parametrize("kw, text", [
    (dict(sliding_layout=(0, 1)), "sliding_layout has 2"),
    (dict(sliding_layout=(1,) + (0,) * 8), "leading dense layers"),
    (dict(sliding_layout=(0, 1, 1, 0, 1, 0, 1, 1, 0)), "does not repeat"),
    (dict(n_heads_sliding=5), "n_kv_heads must divide"),
    (dict(partial_rotary_factor=0.45), "no even width"),
])
def test_config_refuses_what_it_cannot_mean(kw, text):
    with pytest.raises(ValueError, match=text):
        lg.tiny_laguna(**kw)


def test_parameters_are_stacked_by_kind_and_not_padded():
    cfg = lg.tiny_laguna()
    p = jax.eval_shape(lambda k: lg.init_params(k, cfg), jax.random.key(0))
    assert p["dense"]["wq"].shape == (1, 64, 4, 16)
    assert p["full"]["wq"].shape == (2, 64, 4, 16)
    assert p["sliding"]["wq"].shape == (6, 64, 6, 16)
    assert p["sliding"]["wo"].shape == (6, 6, 16, 64)
    assert p["sliding"]["attn_gate"].shape == (6, 64, 6)
    assert p["full"]["attn_gate"].shape == (2, 64, 4)
    assert p["dense"]["w_gate"].shape == (1, 64, 96)
    assert p["layers"]["we_gate"].shape == (8, 16, 64, 32)
    assert p["layers"]["ws_down"].shape == (8, 32, 64)
    assert p["layers"]["router"].shape == (8, 64, 16)
    assert "wq" not in p["layers"] and "router" not in p["dense"]


def test_int8_keeps_the_gate_the_router_and_the_norms_in_full():
    from vnsum_tpu.models.quant import init_params_quantized, quantize_params

    cfg = lg.tiny_laguna()
    for tree in (quantize_params(jitted_init(lg.init_params, cfg, 0)),
                 init_params_quantized(jax.random.key(0), cfg)):
        for group in ("dense", "full", "sliding"):
            assert set(tree[group]["wq"]) == {"q", "s"}
            assert tree[group]["wq"]["q"].dtype == jnp.int8
            assert not isinstance(tree[group]["attn_gate"], dict)
        assert tree["sliding"]["wo"]["s"].shape == (6, 64)
        assert tree["layers"]["we_gate"]["s"].shape == (8, 16, 32)
        assert not isinstance(tree["layers"]["router"], dict)
        assert set(tree["layers"]["ws_up"]) == {"q", "s"}


# -- the mechanisms, each against a hand-written case ---------------------------


def test_route_is_softmax_over_all_then_top_k_renormalised_and_scaled():
    logits = jnp.log(jnp.asarray([[0.4, 0.1, 0.3, 0.2],
                                  [0.05, 0.05, 0.2, 0.7]]))
    ids, w = lg.route(logits, 2, 2.5)
    assert ids.dtype == jnp.int32
    np.testing.assert_array_equal(ids, [[0, 2], [3, 2]])
    # 0.4 and 0.3 of a softmax that sums to one: 4/7 and 3/7, times 2.5
    np.testing.assert_allclose(
        w, [[2.5 * 4 / 7, 2.5 * 3 / 7], [2.5 * 7 / 9, 2.5 * 2 / 9]], rtol=1e-6)
    # the softmax runs over ALL experts: a constant added to every logit
    # changes nothing, a logit outside the picks changes no weight's share
    np.testing.assert_allclose(lg.route(logits + 3.0, 2, 2.5)[1], w, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, -1), 2.5, rtol=1e-6)


def test_the_gate_scales_each_head_before_the_output_projection(tiny):
    """One full layer by hand: with W_o the identity on a head's dims the
    layer's output is x + g_head * a_head, head by head."""
    cfg = lg.tiny_laguna(n_layers=1, n_dense_layers=1, dim=64, n_heads=4,
                         head_dim=16)
    p = lg.init_params(jax.random.key(3), cfg)["dense"]
    lp = jax.tree.map(lambda a: a[0], p)
    lp["wo"] = jnp.eye(64).reshape(4, 16, 64)
    x = jax.random.normal(jax.random.key(4), (1, 6, 64))
    S = 6
    pos = jnp.arange(S)[None]
    mask = jnp.tril(jnp.ones((S, S), bool))[None]
    ropes = lg.rope_tables(cfg, pos)

    def run(gate):
        out, _ = lg._attend(x, dict(lp, attn_gate=gate), 0, False, ropes,
                            mask, lg.init_cache(cfg, 1, S), 0, cfg, None)
        return (out - x).reshape(1, S, 4, 16)

    # a gate weight of zero is sigmoid(0) = 1/2 on every head
    half = run(jnp.zeros((64, 4)))
    # a huge bias-like column opens head 1 and shuts head 2
    h = llama._rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    big = jnp.zeros((64, 4)).at[:, 1].set(1e3 * jnp.sign(h[0, 0]))
    big = big.at[:, 2].set(-1e3 * jnp.sign(h[0, 0]))
    got = run(big)
    np.testing.assert_allclose(got[0, 0, 1], 2 * half[0, 0, 1], atol=1e-6)
    np.testing.assert_allclose(got[0, 0, 2], 0.0, atol=1e-6)
    np.testing.assert_allclose(got[0, 0, 0], half[0, 0, 0], atol=1e-6)
    # one scalar a head AND token: the other tokens' gates are their own
    g = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", h, big))
    np.testing.assert_allclose(got, 2 * half * g[..., None], atol=1e-5)


def test_partial_rotary_turns_the_leading_dims_and_passes_the_rest():
    cfg = lg.tiny_laguna()
    pos = jnp.arange(7)[None] + 5
    (cos_f, sin_f), (cos_s, sin_s) = lg.rope_tables(cfg, pos)
    assert cos_f.shape == (1, 7, 4) and cos_s.shape == (1, 7, 8)
    x = jax.random.normal(jax.random.key(0), (1, 7, 3, 16))
    full = lg.apply_rope(x, cos_f, sin_f)
    np.testing.assert_array_equal(full[..., 8:], x[..., 8:])
    assert float(jnp.abs(full[..., :8] - x[..., :8]).max()) > 0.1
    # pairs are (i, i + 4) inside the 8 rotary dims, scaled by the factor
    m = cfg.rope_attention_factor
    a, b = x[0, 2, 1, 0], x[0, 2, 1, 4]
    c, s = cos_f[0, 2, 0], sin_f[0, 2, 0]
    np.testing.assert_allclose(full[0, 2, 1, 0], a * c - b * s, rtol=1e-5)
    np.testing.assert_allclose(full[0, 2, 1, 4], b * c + a * s, rtol=1e-5)
    np.testing.assert_allclose(c * c + s * s, m * m, rtol=1e-5)
    # sliding layers: every dim turns, plainly
    local = lg.apply_rope(x, cos_s, sin_s)
    assert float(jnp.abs(local[..., 8:] - x[..., 8:]).max()) > 0.1
    np.testing.assert_allclose(cos_s[0, :, 1],
                               jnp.cos((jnp.arange(7) + 5) / 10_000 ** (1 / 8)),
                               rtol=1e-5)


def test_yarn_frequencies_ramp_between_the_correction_dims():
    """theta 500,000 over 64 dims, factor 128, original 8,192, beta 32 / 1:
    by hand the correction dims are 9.04 -> 9 and 17.49 -> 18; frequencies
    up to dim 9 are kept, from dim 18 on divided by 128, a line between."""
    inv = np.asarray(llama.yarn_inv_freq(64, 500_000.0, 128.0, 8192, 32.0, 1.0))
    plain = 500_000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[18:], plain[18:] / 128, rtol=1e-6)
    ramp = (16 - 9) / (18 - 9)
    np.testing.assert_allclose(
        inv[16], plain[16] / 128 * ramp + plain[16] * (1 - ramp), rtol=1e-5)
    # the family DeepSeek-V2 computes its own table with the same function
    from vnsum_tpu.models import deepseek as ds

    cfg = ds.tiny_deepseek()
    np.testing.assert_array_equal(
        ds.yarn_inv_freq(cfg), llama.yarn_inv_freq(
            cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
            cfg.rope_original_max_len, cfg.rope_beta_fast, cfg.rope_beta_slow))


@pytest.mark.parametrize("window, same", [(60, True), (256, True), (24, False),
                                          (8, False)])
def test_sliding_layers_drop_keys_past_the_window(tiny, window, same):
    """A window no shorter than the prompt is no window; a shorter one
    changes the result."""
    _, params = tiny
    toks = _tokens()
    whole = lg.forward_dense(params, lg.tiny_laguna(sliding_window=10_000),
                             toks)
    got = lg.forward_dense(params, lg.tiny_laguna(sliding_window=window), toks)
    assert (float(jnp.abs(got - whole).max()) < 2e-5) is same
    np.testing.assert_allclose(got[:, :min(window, 60)],
                               whole[:, :min(window, 60)], atol=2e-5)


def test_a_common_shift_of_the_positions_changes_nothing(tiny):
    """Both rotary schemes are relative."""
    cfg, params = tiny
    toks = _tokens()
    B, S = toks.shape
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))

    def run(shift, stretch=1):
        pos = jnp.broadcast_to(jnp.arange(S)[None] * stretch + shift, (B, S))
        return lg.forward(params, cfg, toks, pos, lg.init_cache(cfg, B, S),
                          0, mask)[0]

    np.testing.assert_allclose(run(0), run(11), atol=3e-5)
    assert float(jnp.abs(run(0) - run(0, stretch=3)).max()) > 1e-3


def test_what_is_left_of_a_period_runs_after_the_whole_ones(tiny):
    """7 layers: the dense one, one whole period, two sliding layers. The
    stack's scan and its unrolled tail give what nine layers give when the
    last two layers are cut off by hand."""
    cfg, params = tiny
    cfg7 = lg.tiny_laguna(n_layers=7)
    assert cfg7.periods == (1, (1, 1))
    cut = dict(params,
               full=jax.tree.map(lambda a: a[:1], params["full"]),
               sliding=jax.tree.map(lambda a: a[:5], params["sliding"]),
               layers=jax.tree.map(lambda a: a[:6], params["layers"]))
    toks = _tokens()
    got = lg.forward_dense(cut, cfg7, toks)
    # by hand: the same layers unrolled one by one
    B, S = toks.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    ropes = lg.rope_tables(cfg7, pos)
    cache = lg.init_cache(cfg7, B, S)
    at = lambda t, i: jax.tree.map(lambda a: a[i], t)  # noqa: E731
    x = llama._embed_lookup(cut["embed"], toks, cfg7.dtype)
    x, cache = lg._attend(x, at(cut["dense"], 0), 0, False, ropes, mask,
                          cache, 0, cfg7, None)
    x = lg._dense_ffn(x, at(cut["dense"], 0), cfg7)
    experts = {n: cut["layers"][n] for n in lg.EXPERT_LEAVES}
    seen = {"full": 0, "sliding": 0}
    for slot, sliding in enumerate(cfg7.sparse_layout):
        kind = "sliding" if sliding else "full"
        x, cache = lg._attend(x, at(cut[kind], seen[kind]), 1 + slot,
                              bool(sliding), ropes, mask, cache, 0, cfg7, None)
        seen[kind] += 1
        x, cache = lg._sparse_ffn(x, at(cut["layers"], slot), experts, slot,
                                  jnp.ones((B, S), bool), cache, cfg7, None)
    want = llama._lm_head_logits(
        llama._rmsnorm(x, cut["final_norm"], cfg7.norm_eps), cut, cfg7)
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- state and counters ----------------------------------------------------------


def test_counters_count_every_real_token_and_pick_of_the_sparse_layers(tiny):
    cfg, params = tiny
    toks = _tokens(rows=2)
    B, S = toks.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    pads = jnp.asarray([0, 7])   # row 1: seven tokens that attend nothing
    mask = llama.prefill_attention_mask(pads, S, S)
    _, cache = lg.forward(params, cfg, toks, pos, lg.init_cache(cfg, B, S),
                          0, mask)
    real, k, Ls = 2 * S - 7, cfg.num_experts_per_tok, cfg.n_sparse_layers
    assert Ls == 8 and cache["k"].shape[0] == cfg.n_layers == 9
    assert int(cache["slots_routed"]) == int(cache["slots_held"]) == real * k * Ls
    assert cache["expert_tokens"].shape == (Ls, cfg.n_held)
    assert (np.asarray(cache["expert_tokens"]).sum(1) == real * k).all()
    assert lg.last_picks(cache).shape == (Ls, B, k)
    assert int(cache["decode_touched"]) == int(cache["decode_layer_steps"]) == 0
    assert set(lg.counters(cache)) == {
        "expert_tokens", "slots_routed", "slots_held", "decode_touched",
        "decode_layer_steps", "decode_tiles_used", "decode_tiles_walked"}


def test_decode_steps_count_the_distinct_experts_they_touch(tiny):
    cfg, params = tiny
    B, C = 3, 16
    cache = lg.init_cache(cfg, B, C)
    toks = _tokens(n=4, rows=B)
    touched = 0
    for t in range(4):
        mask = jnp.broadcast_to(jnp.arange(C)[None, None] <= t, (B, 1, C))
        before = np.asarray(cache["expert_tokens"])
        _, cache = lg.forward(params, cfg, toks[:, t:t + 1],
                              jnp.full((B, 1), t), cache, t, mask)
        step = np.asarray(cache["expert_tokens"]) - before
        assert (step.sum(1) == B * cfg.num_experts_per_tok).all()
        touched += int((step > 0).sum())
    assert int(cache["decode_touched"]) == touched
    assert int(cache["decode_layer_steps"]) == 4 * cfg.n_sparse_layers
    # the dense path runs no grouped product: no tiles
    assert int(cache["decode_tiles_used"]) == 0


def test_decode_steps_count_the_tiles_their_slots_fill(tiny):
    """A step's 48 rows x 4 picks on 16 experts fill 12 + 4 tiles of 16 or
    so a layer; the grouped product's grid walks exactly those (the count
    recomputed here from the step's picks), not the 16 + 12 + 1 of the
    layout's worst case."""
    from vnsum_tpu.models import experts

    cfg, params = tiny
    B, C, tm = 48, 16, 16
    cache, tiles = lg.init_cache(cfg, B, C), 0
    toks = _tokens(n=2, rows=B)
    grouped = functools.partial(experts.grouped_experts, cfg=cfg,
                                interpret=True)
    for t in range(2):
        mask = jnp.broadcast_to(jnp.arange(C)[None, None] <= t, (B, 1, C))
        before = np.asarray(cache["expert_tokens"])
        _, cache = lg.forward(params, cfg, toks[:, t:t + 1],
                              jnp.full((B, 1), t), cache, t, mask,
                              experts_fn=grouped)
        step = np.asarray(cache["expert_tokens"]) - before
        tiles += int((-(-step // tm)).sum())
    assert int(cache["decode_tiles_used"]) == tiles \
        == int(cache["decode_tiles_walked"])
    assert int(cache["decode_touched"]) < tiles \
        < 2 * cfg.n_sparse_layers * (B * 4 // tm + 16 + 1)


# -- the engine's seam ------------------------------------------------------------


def test_family_resolves_and_names_what_it_lacks():
    fam = family_of(lg.tiny_laguna())
    assert fam is lg.FAMILY and fam.name == "laguna"
    assert fam.int8_cache and fam.counts_prefill_blocks
    assert fam.layer_windows(lg.tiny_laguna()) == (0, 24, 24, 24) * 2 + (0,)
    assert fam.layer_groups(lg.tiny_laguna()) == (2, 3, 3, 3) * 2 + (2,)
    assert set(fam.missing) == {"slot loop", "prefix cache", "mesh",
                                "speculative decoding",
                                "long-context backend"}
    # the other families say nothing of their groups: one number holds
    from vnsum_tpu.models import smallthinker as st

    assert llama.FAMILY.layer_groups(None) is None
    assert st.FAMILY.layer_groups(None) is None


@pytest.mark.parametrize("entry", sorted(lg.FAMILY.missing))
def test_family_refuses_by_the_text_of_what_it_lacks(entry):
    with pytest.raises(NotImplementedError) as e:
        lg.FAMILY.refuse(entry)
    assert "laguna" in str(e.value) and entry in str(e.value)
    assert lg.FAMILY.missing[entry] in str(e.value)
    assert len(lg.FAMILY.missing[entry]) > 60   # says what, not just no


@pytest.mark.parametrize("kw", [dict(cache_blocks=8), dict(mesh=object())])
def test_engine_refuses_the_entries_at_construction(tiny, kw):
    from vnsum_tpu.backend.engine import TpuBackend

    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="laguna"):
        TpuBackend(model_config=cfg, params=params, interpret=True, **kw)


def test_attention_functions_take_the_group_from_the_queries():
    """``models.llama._prefill_attention`` / ``_decode_attention`` hand the
    kernels H / KV of the queries they are given, whatever the config says:
    one program runs them at two groups."""
    cfg = lg.tiny_laguna()
    cache = llama.init_kv_cache(cfg, 2, 32)
    pads = jnp.zeros((2,), jnp.int32)
    window = lambda li: jnp.int32(0)  # noqa: E731
    pre = llama._prefill_attention(None, None, True, pads, window)
    dec = llama._decode_attention(None, None, True, pads, 16, 0, window)
    for heads in (4, 6):
        q = jax.random.normal(jax.random.key(heads), (2, 16, heads, 16))
        assert pre(q, cache, 0).shape == q.shape
        assert dec(q[:, :1], cache, 0).shape == (2, 1, heads, 16)


@pytest.mark.parametrize("quantize_kv", [False, True])
def test_engine_prefill_and_decode_agree_with_forward_dense(tiny, quantize_kv):
    """The engine's chunked prefill (two chunks, both kernels interpreted at
    BOTH groups in one program, the per-layer window, a left pad) and then
    teacher-forced decode steps through the cache, against the family's
    cache-free forward over the whole sequence, with a prompt six windows
    long: float weights, so what is left is the cache's own rounding."""
    cfg = lg.tiny_laguna(max_seq_len=400)
    _, params = tiny
    be = _engine(cfg, params, quantize_kv=quantize_kv)
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    assert 150 > 6 * cfg.sliding_window
    got = be.prefill_then_decode_logits(ids[:150], ids[150:], bucket=256)
    want = np.asarray(lg.forward_dense(params, cfg, jnp.asarray([ids])))[0, -6:]
    assert got.shape == want.shape == (6, cfg.vocab_size)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    if quantize_kv:
        # an int8 cache may turn a router's near-tie: all rows but one close
        assert np.sort(err)[-2] < 0.02 and err.max() < 0.1, err
    else:
        assert err.max() < 1e-5, err
    assert be.stats.attention_paths["logits[B=1,S=256]"] == {
        "prefill": "kernel", "decode": "kernel"}


def test_engine_generates_and_counts_its_cells_by_layer_kind(tiny):
    """``TpuBackend.generate`` with the kernels interpreted: counters
    returned with the output, the prefill's cells counted by layer kind
    (each kind's own group), and beside the classes the scores the sliding
    layers computed and needed."""
    from vnsum_tpu.ops.flash_attention import prefill_block_classes

    cfg, params = tiny
    be = _engine(cfg, params, batch_size=2, max_new_tokens=6,
                 quantize_kv=True, fresh=True)
    packed = []
    pack = be._pack_group
    be._pack_group = lambda *a: packed.append(pack(*a)) or packed[-1]
    outs = be.generate(["xin chào " * 22, "một hai ba"], max_new_tokens=6)
    st_ = be.stats
    assert len(outs) == 2
    assert list(st_.attention_paths.values()) == [
        {"prefill": "kernel", "decode": "kernel"}]
    assert st_.expert_slots_held == st_.expert_slots_routed > 0
    assert np.asarray(st_.expert_tokens).shape == (8, 16)
    assert st_.expert_decode_layer_steps == 6 * 8
    assert 6 * 8 * 4 <= st_.expert_decode_touched <= 6 * 8 * 8
    assert st_.expert_decode_tiles_used == st_.expert_decode_tiles_walked \
        == st_.expert_decode_touched     # 8 slots a step: a tile an expert
    (_, pad_lens, B, S), = packed
    C = S + 6
    want = dict.fromkeys(("dead_causal", "dead_pad", "interior", "edge"), 0)
    for window, group, n_layers in ((0, 2, 3), (24, 3, 6)):
        for lo in range(0, S, 128):
            for name, n in prefill_block_classes(
                    pad_lens, min(128, S - lo), C, lo, window, group,
                    cfg.head_dim).items():
                want[name] += n * n_layers
    got = dict(st_.prefill_blocks)
    needed = got.pop("window_scores_needed")
    computed = got.pop("window_scores_computed")
    assert got == want
    # by hand: six sliding layers of six heads; a real row at slot i sees
    # min(i + 1 - pad, 24) keys
    rows = sum(min(i + 1 - int(p), 24) for p in pad_lens
               for i in range(int(p), S))
    assert needed == rows * 6 * 6
    # at this size a tile is the whole chunk: every fetched cell is 128 wide
    assert computed > needed and computed % (6 * 6) == 0
    window = be._layer_window_fn()
    assert [int(window(i)) for i in range(9)] == [0, 24, 24, 24] * 2 + [0]
    with pytest.raises(NotImplementedError, match="slot loop"):
        be.start_slot_loop(2)


def test_the_one_shot_program_names_the_familys_scopes(tiny):
    """``attn_gate`` in both phases, ``mlp`` for the dense layer, ``router``,
    ``experts`` and ``shared_experts`` for the sparse ones: what
    ``scripts/trace_by_scope.py`` books this family by."""
    from vnsum_tpu.backend.engine import TpuBackend

    cfg, params = tiny
    be = TpuBackend(model_config=cfg, tokenizer="byte", params=params,
                    batch_size=2, max_new_tokens=4, flash=False)
    be._get_fn(2, 64, 4, be.gen_cfg)
    (m,) = be.scope_maps()
    got = {"/".join(p.split("/")[:2]) for p in m["scopes"].values()}
    for phase in ("prefill", "decode"):
        assert {f"{phase}/{c}" for c in (
            "qkv", "kv_write", "attn", "attn_gate", "attn_out", "mlp",
            "router", "experts", "shared_experts", "lm_head", "embed")} <= got
