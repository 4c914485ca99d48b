"""The Nemotron-H family (models/nemotron_h.py, models/mamba_mixer.py,
ops/ssd_scan.py at several groups, the single-product expert form) on the
CPU at a tiny size: nine layers ``MEM*EMEME`` — all three kinds in an order
with no period —, 2 groups of B and C over 8 Mamba heads of 16, a state of
16, scan chunks of 8, 8 query heads of 16 over 2 KV heads (4 a KV head), 16
experts of 24 (not whole lanes of anything) top-4 with a shared one of 48."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_setup_nemotron_h as setup
from benchmarks import reference_nemotron_h as reference
from family_harness import (
    alone_and_in_a_batch,
    engine as _engine,
    picks_agree as _picks_agree,
    reference as jitted,
    reference_of,
    rel as _rel,
    sizes,
    through_the_engine as _through_the_engine,
    tokens as _tokens,
)
from vnsum_tpu.models import MODEL_REGISTRY, experts, jitted_init
from vnsum_tpu.models import nemotron_h as nh
from vnsum_tpu.models.family import family_of
from vnsum_tpu.ops import ssd_scan


_sizes = functools.partial(sizes, setup)


@pytest.fixture(scope="module")
def tiny():
    """The tiny config and its weights, the query and key products thirty
    times the usual draw (a 0.02-normal draw gives scores flat to 1e-3: no
    rotary would show) and the router ten times (so that its scores spread
    as the published widths' do: 0.02 x sqrt(2688) = 1.0 a logit there)."""
    cfg = nh.tiny_nemotron_h()
    params = jitted_init(nh.init_params, cfg, 0)
    attn = dict(params["attn"], wq=params["attn"]["wq"] * 30.0,
                wk=params["attn"]["wk"] * 30.0)
    layers = dict(params["layers"], router=params["layers"]["router"] * 10.0)
    return cfg, dict(params, attn=attn, layers=layers)


# -- the config and the parameters ---------------------------------------------


def test_published_config_and_its_pattern():
    cfg = MODEL_REGISTRY["nemotron-3-nano-30b-a3b"]()
    assert (cfg.dim, cfg.vocab_size, cfg.n_layers) == (2688, 131_072, 52)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv) == (
        32, 2, 128, 16)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_d_conv) == (64, 64, 128, 8, 4)
    assert (cfg.moe_intermediate, cfg.shared_intermediate,
            cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.n_held) == (
        1856, 3712, 128, 6, 2.5, 128)
    assert (cfg.n_mamba, cfg.n_sparse, cfg.n_attention) == (23, 23, 6)
    assert not cfg.tie_embeddings and cfg.act == "relu2"
    # a model cut in depth takes the leading layers of the pattern
    cut = MODEL_REGISTRY["nemotron-3-nano-30b-a3b"](n_layers=16)
    assert cut.layer_pattern == "MEMEM*EMEMEM*EME"
    assert (cut.n_mamba, cut.n_sparse, cut.n_attention) == (7, 7, 2)
    assert MODEL_REGISTRY["tiny-nemotron-h"]().layer_pattern == "MEM*EMEME"


@pytest.mark.parametrize("kw, text", [
    (dict(layer_pattern="MEMX"), "letters of M, E and"),
    (dict(n_layers=12), "letters of M, E and"),
    (dict(n_kv_heads=3), "n_kv_heads must divide"),
    (dict(mamba_n_groups=3), "mamba_n_groups must divide"),
    (dict(n_held=8, expert_offset=12), "past the last expert"),
])
def test_config_refuses_what_it_cannot_mean(kw, text):
    with pytest.raises(ValueError, match=text):
        nh.tiny_nemotron_h(**kw)


def test_parameters_are_stacked_by_kind_and_the_experts_have_no_gate(tiny):
    cfg, p = tiny
    assert p["mamba"]["in_xbc"].shape == (4, 64, 128 + 2 * 2 * 16)
    assert p["attn"]["wq"].shape == (1, 64, 8, 16)
    layers = p["layers"]
    # stored at whole lanes (24 -> 128), zero past the expert's own width
    assert cfg.moe_intermediate == 24 and cfg.moe_stored == 128
    assert layers["we_up"].shape == (4, 16, 64, 128)
    assert layers["we_down"].shape == (4, 16, 128, 64)
    assert not layers["we_up"][..., 24:].any()
    assert not layers["we_down"][:, :, 24:].any()
    assert layers["we_up"][..., :24].all() and layers["we_down"][:, :, :24].all()
    assert layers["ws_up"].shape == (4, 64, 48)
    assert "we_gate" not in layers and "ws_gate" not in layers
    assert layers["router"].dtype == layers["router_bias"].dtype == jnp.float32
    assert "lm_head" in p and "mlp_norm" not in layers


def test_int8_keeps_the_sensitive_leaves_in_float32():
    """The direct int8 init draws the scan's vectors, the router and its
    bias the family's own way (``float_leaves``), float32, and quantizes the
    two-matrix experts a scale an expert and output channel."""
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = nh.tiny_nemotron_h(dtype=jnp.bfloat16)
    q = init_params_quantized(jax.random.key(3), cfg)
    own = nh.float_leaves(jax.random.fold_in(jax.random.key(3), 1), cfg)
    for group, leaves in own.items():
        for name, leaf in leaves.items():
            assert q[group][name].dtype == jnp.float32
            assert (q[group][name] == leaf).all(), name
    assert q["layers"]["we_up"]["q"].shape == (4, 16, 64, 128)
    assert q["layers"]["we_up"]["s"].shape == (4, 16, 128)
    assert q["layers"]["we_down"]["s"].shape == (4, 16, 64)
    # every stored value is drawn, and the padding zeroed after
    assert not q["layers"]["we_up"]["q"][..., 24:].any()
    assert not q["layers"]["we_down"]["q"][:, :, 24:].any()
    assert q["layers"]["we_up"]["q"][..., :24].any()
    assert q["mamba"]["in_xbc"]["q"].dtype == jnp.int8


def test_the_seeded_bias_moves_a_tenth_of_the_picks_at_the_published_widths():
    """``e_score_correction_bias`` is drawn wide enough that leaving it out
    is a fault a check can see: against a zero bias more than a tenth of
    the picks of a router of the published shape change."""
    cfg = nh.nemotron_3_nano_30b_a3b(n_layers=2)        # M, E
    leaves = nh.init_router(jax.random.key(5), cfg)
    h = jax.random.normal(jax.random.key(6), (512, cfg.dim), jnp.float32)
    logits = h @ leaves["router"][0]
    with_bias, _ = nh.route(logits, leaves["router_bias"][0], 6, 2.5)
    without, _ = nh.route(logits, jnp.zeros(128), 6, 2.5)
    moved = np.mean([len(set(a) - set(b)) / 6 for a, b in zip(
        np.asarray(with_bias).tolist(), np.asarray(without).tolist())])
    assert 0.1 < moved < 0.5, moved


def test_route_takes_the_weight_from_the_score_and_the_choice_from_the_bias():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 10.0])
    ids, w = nh.route(logits, bias, 2, 2.5)
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 3]
    s = jax.nn.sigmoid(logits[0])
    want = {0: s[0] / (s[0] + s[3]) * 2.5, 3: s[3] / (s[0] + s[3]) * 2.5}
    for i, e in enumerate(np.asarray(ids[0]).tolist()):
        assert abs(float(w[0, i]) - float(want[e])) < 1e-6


def test_the_state_is_three_kinds_side_by_side():
    cfg = nh.tiny_nemotron_h()
    cache = nh.init_cache(cfg, 3, 40, quantized=True)
    assert cache["k"].shape == (1, 3, 2, 40, 16)            # 1 attention layer
    assert cache["ssm"].shape == (4, 3, 16, 128)            # 4 Mamba layers
    assert cache["conv"].shape == (4, 3, 3, 128 + 64)
    assert cache["expert_tokens"].shape == (4, 16)          # 4 sparse layers
    assert cache["picks"].shape == (4, 3, 4)
    assert "decode_touched" in cache and cache["ssm"].dtype == jnp.float32


# -- the kernels at several groups -------------------------------------------------


def _scan_case(G, seed=0, rows=3, S=20, H=8, P=16, N=16):
    k = jax.random.split(jax.random.key(seed + G), 8)
    return dict(
        x=jax.random.normal(k[0], (rows, S, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (rows, S, H))) * 0.3,
        A=-jnp.exp(jax.random.normal(k[2], (H,))),
        Bm=jax.random.normal(k[3], (rows, S, G, N)),
        Cm=jax.random.normal(k[4], (rows, S, G, N)),
        D=jax.random.normal(k[5], (H,)),
        state=jax.random.normal(k[6], (3, rows, N, H * P)))


def _token_by_token(x, dt, A, Bm, Cm, D, state):
    """The recurrence as the equations have it, head h on group
    h // (H / G), in float64."""
    x, dt, A, Bm, Cm, D = (np.asarray(a, np.float64)
                           for a in (x, dt, A, Bm, Cm, D))
    rows, S, H, P = x.shape
    G, N = Bm.shape[-2:]
    h = np.asarray(state, np.float64).reshape(rows, N, H, P).transpose(
        0, 2, 3, 1)                                          # [rows, H, P, N]
    ys = []
    for t in range(S):
        Bh = np.repeat(Bm[:, t], H // G, axis=1)             # [rows, H, N]
        Ch = np.repeat(Cm[:, t], H // G, axis=1)
        h = (np.exp(dt[:, t] * A)[..., None, None] * h
             + (dt[:, t][..., None] * x[:, t])[..., None] * Bh[:, :, None])
        ys.append((h * Ch[:, :, None]).sum(-1) + D[None, :, None] * x[:, t])
    return np.stack(ys, 1), h.transpose(0, 3, 1, 2).reshape(rows, N, H * P)


@pytest.mark.parametrize("G", [1, 2, 8])
@pytest.mark.parametrize("S", [8, 20])
def test_scan_forms_equal_the_recurrence_at_any_number_of_groups(G, S):
    """Both XLA forms and both kernels (interpreted), a state coming in,
    against the recurrence token by token: one group (Granite's), two, and
    as many groups as heads; the kernel writes its own layer of the stacked
    state and no other."""
    c = _scan_case(G, S=S)
    want_y, want_h = _token_by_token(c["x"], c["dt"], c["A"], c["Bm"],
                                     c["Cm"], c["D"], c["state"][1])
    y, h = ssd_scan.ssd_chunked_xla(c["x"], c["dt"], c["A"], c["Bm"],
                                    c["Cm"], c["D"], c["state"][1], 8)
    assert _rel(y, want_y) < 1e-5 and _rel(h, want_h) < 1e-5
    y, hs = ssd_scan.ssd_prefill_scan(
        c["x"], c["dt"], c["A"], c["Bm"], c["Cm"], c["D"], c["state"], 1,
        jnp.zeros((3,), jnp.int32), chunk=8, interpret=True)
    assert _rel(y, want_y) < 1e-5 and _rel(hs[1], want_h) < 1e-5
    assert (hs[0] == c["state"][0]).all() and (hs[2] == c["state"][2]).all()
    # one token: the step's XLA form and the state-update kernel
    one_y, one_h = _token_by_token(c["x"][:, :1], c["dt"][:, :1], c["A"],
                                   c["Bm"][:, :1], c["Cm"][:, :1], c["D"],
                                   c["state"][1])
    step = (c["x"][:, 0], c["dt"][:, 0], c["A"], c["Bm"][:, 0],
            c["Cm"][:, 0], c["D"])
    y, h = ssd_scan.ssm_step_xla(*step, c["state"][1])
    assert _rel(y, one_y[:, 0]) < 1e-5 and _rel(h, one_h) < 1e-5
    y, hs = ssd_scan.ssm_decode_update(*step, c["state"], 1, interpret=True)
    assert _rel(y, one_y[:, 0]) < 1e-5 and _rel(hs[1], one_h) < 1e-5
    assert (hs[0] == c["state"][0]).all()


def test_one_group_may_leave_its_dim_out():
    """Granite's callers hand B and C as [B, S, N]: the same as one group
    of [B, S, 1, N], bit for bit."""
    c = _scan_case(1)
    args = (c["x"], c["dt"], c["A"])
    flat = ssd_scan.ssd_chunked_xla(*args, c["Bm"][:, :, 0], c["Cm"][:, :, 0],
                                    c["D"], c["state"][0], 8)
    grouped = ssd_scan.ssd_chunked_xla(*args, c["Bm"], c["Cm"], c["D"],
                                       c["state"][0], 8)
    assert all((a == b).all() for a, b in zip(flat, grouped))


def test_a_lane_tile_never_holds_heads_of_two_groups():
    assert ssd_scan._heads_per_tile(64, 64) == 2            # Granite
    assert ssd_scan._heads_per_tile(64, 64, 8) == 2         # Nemotron-H
    assert ssd_scan._heads_per_tile(8, 16, 2) == 4          # the tiny preset
    assert ssd_scan._heads_per_tile(8, 16, 8) == 1
    with pytest.raises(ValueError, match="groups do not divide"):
        c = _scan_case(3)
        ssd_scan.ssd_prefill_scan(
            c["x"], c["dt"], c["A"], c["Bm"], c["Cm"], c["D"], c["state"], 0,
            jnp.zeros((3,), jnp.int32), chunk=8, interpret=True)


# -- the expert layer in its single-product form ----------------------------------


def _expert_case(cfg, T=40, seed=2, quantized=False):
    k = jax.random.split(jax.random.key(seed), 6)
    E, D, F, L = cfg.n_held, cfg.dim, cfg.moe_intermediate, 2
    x = jax.random.normal(k[0], (T, D), jnp.float32)
    stacked = {"we_up": jax.random.normal(k[1], (L, E, D, F)) * 0.1,
               "we_down": jax.random.normal(k[2], (L, E, F, D)) * 0.1}
    if quantized:
        from vnsum_tpu.models.quant import _quantize

        stacked = {n: _quantize(w, (2,)) for n, w in stacked.items()}
    ids = jax.vmap(lambda kk: jax.random.permutation(kk, E)[:4])(
        jax.random.split(k[3], T)).astype(jnp.int32)
    weights = jax.random.uniform(k[4], (T, 4), jnp.float32, 0.1, 1.0)
    return x, ids, weights, stacked


@pytest.mark.parametrize("T", [40, 1100])
def test_single_product_relu2_form_equals_dense_experts(T):
    """``grouped_experts`` on two-matrix experts — one product with relu2
    in the kernel, then the down product — against ``dense_experts`` and a
    plain loop, at a decode step's few tokens and at a prefill piece's
    (row tile 256, ``expert_combine``), at a width of 24 that is no whole
    lanes: the column tile is the whole width."""
    cfg = nh.tiny_nemotron_h()
    x, ids, weights, stacked = _expert_case(cfg, T)
    got = experts.grouped_experts(x, ids, weights, stacked, 1, cfg,
                                  interpret=True)
    dense = experts.dense_experts(x, ids, weights, stacked, 1, cfg)
    want = np.zeros((T, cfg.dim))
    xs, up, down = (np.asarray(a, np.float64) for a in (
        x, stacked["we_up"][1], stacked["we_down"][1]))
    for t in range(T):
        for j in range(4):
            e = int(ids[t, j])
            want[t] += float(weights[t, j]) * (
                np.maximum(xs[t] @ up[e], 0) ** 2 @ down[e])
    assert _rel(dense, want) < 1e-5
    assert _rel(got, want) < 1e-5


def test_single_product_form_on_int8_rows_is_w8a8s_rounding_away():
    cfg = nh.tiny_nemotron_h(w8a8_prefill=True)
    x, ids, weights, stacked = _expert_case(cfg, 1100, quantized=True)
    got = experts.grouped_experts(x, ids, weights, stacked, 0, cfg,
                                  interpret=True)
    want = experts.dense_experts(x, ids, weights, stacked, 0, cfg)
    assert _rel(got, want) < 0.03


def test_expert_matmul_refuses_an_activation_it_has_no_form_for():
    from vnsum_tpu.ops.expert_matmul import expert_grouped_matmul

    w = jnp.zeros((1, 1, 8, 128))
    call = dict(tm=16, tn=128, out_dtype=jnp.float32, interpret=True)
    args = (0, jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32))
    with pytest.raises(ValueError, match="plain under those names or relu2"):
        expert_grouped_matmul(jnp.zeros((16, 8)), None, w, None, *args,
                              act="gelu", **call)
    with pytest.raises(ValueError, match="a gate is silu or relu"):
        expert_grouped_matmul(jnp.zeros((16, 8)), None, w, w, *args,
                              act="relu2", **call)
    # a gate's name on a single product leaves it plain, as it always has
    x = jnp.full((16, 8), -1.0)
    plain = expert_grouped_matmul(x, None, w + 1.0, None, *args, act="silu",
                                  **call)
    squared = expert_grouped_matmul(x, None, w + 1.0, None, *args,
                                    act="relu2", **call)
    assert float(plain[0, 0]) == -8.0 and float(squared[0, 0]) == 0.0


@pytest.mark.parametrize("K, N, want", [
    (5120, 1536, 512), (1536, 5120, 1280),      # DeepSeek-V2's two products
    (2560, 768, 768), (3072, 1024, 512),        # SmallThinker's, Laguna's up
    (1856, 2688, 896),                          # Nemotron-H's down product
    (2688, 1856, 1856),     # its up product: 14.5 lane tiles, the whole width
    (64, 24, 24),                               # the tiny preset's
])
def test_column_tile_is_whole_lanes_or_the_whole_width(K, N, want):
    assert experts._column_tile(K, N) == want


def test_column_tile_refuses_a_whole_width_too_large_for_vmem():
    with pytest.raises(ValueError, match="padded to whole lanes"):
        experts._column_tile(8192, 1856)


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The expert layer is told what it holds (the model-configs guide's
    section 4 test): with ``n_held`` 8 at ``expert_offset`` 0 and 8, the two
    routed parts plus the shared expert counted once equal the layer that
    holds all 16 — through the kernels and through ``dense_experts``."""
    cfg, params = tiny
    lp = jax.tree.map(lambda a: a[1], {
        n: w for n, w in params["layers"].items()
        if n not in experts.UNGATED_EXPERT_LEAVES})
    h = jax.random.normal(jax.random.key(9), (2, 30, cfg.dim), jnp.float32)
    valid = jnp.ones((2, 30), bool)

    def layer(cfg, offset, fn):
        stacked = {n: params["layers"][n][:, offset:offset + cfg.n_held]
                   for n in experts.UNGATED_EXPERT_LEAVES}
        cache = experts.init_expert_state(cfg.n_sparse, cfg.n_held, 2, 4,
                                          decode_touched=True)
        experts_fn = None if fn is None else (
            lambda *a: experts.grouped_experts(*a, cfg, interpret=True))
        out, cache = nh._expert_mixer(h, lp, stacked, 1, valid, cache, cfg,
                                      experts_fn)
        return out, cache

    with jax.default_matmul_precision("highest"):
        up = lp["ws_up"]
        shared = jnp.square(jnp.maximum(h @ up, 0)) @ lp["ws_down"]
        for fn in (None, "kernels"):
            whole, counted = layer(cfg, 0, fn)
            parts = [layer(dataclasses.replace(cfg, n_held=8,
                                               expert_offset=offset),
                           offset, fn) for offset in (0, 8)]
            total = parts[0][0] + parts[1][0] - shared
            assert _rel(total, whole) < 1e-5
            assert int(counted["slots_held"]) == 2 * 30 * 4 == sum(
                int(c["slots_held"]) for _, c in parts)
            assert (np.concatenate([c["expert_tokens"][1] for _, c in parts])
                    == np.asarray(counted["expert_tokens"][1])).all()


# -- against the reference ---------------------------------------------------------


def test_cache_free_forward_equals_the_reference(tiny):
    cfg, params = tiny
    toks = _tokens(37)
    with jax.default_matmul_precision("highest"):
        got = nh.forward_dense(params, cfg, toks)
        want = jnp.stack([jitted(reference, _sizes(cfg))(params, t)["logits"]
                          for t in toks])
    assert got.shape == (2, 37, cfg.vocab_size)
    assert _rel(got, want) < 1e-5


@pytest.fixture(scope="module")
def clean_logits(tiny):
    cfg, params = tiny
    with jax.default_matmul_precision("highest"):
        return jitted(reference, _sizes(cfg))(
            params, _tokens(37)[0])["logits"]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_every_departure_of_the_reference_shows_in_the_logits(
        tiny, clean_logits, fault):
    cfg, params = tiny
    with jax.default_matmul_precision("highest"):
        other = jitted(reference, _sizes(cfg), faults=(fault,))(
            params, _tokens(37)[0])["logits"]
    assert _rel(other, clean_logits) > 1e-3


def test_reference_refuses_an_unknown_fault(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="unknown faults"):
        reference.logits(params, _tokens(5)[0], _sizes(cfg), faults=("x",))


def test_reference_takes_rightful_picks_inside_the_band_alone():
    ranked = jnp.asarray([[0.9, 0.5, 0.495, 0.1]] * 3)
    theirs = jnp.asarray([[0, 2], [0, 3], [0, 0]])
    took = reference.ties_broken_their_way(ranked, theirs, 0.01)
    assert np.asarray(took).tolist() == [True, False, False]
    assert not reference.ties_broken_their_way(ranked, theirs, 0.0)[0]


@pytest.mark.parametrize("flash", [True, False])
def test_engine_prefill_and_decode_agree_with_the_reference(tiny, flash):
    """The engine's chunked prefill — a left pad of 106 in a bucket of 256,
    two prefill chunks of 128, so the boundary between them falls inside
    the prompt and scan chunks of 8 inside and across it — and then
    teacher-forced decode steps through the state, the cache and the
    counters, against the reference's one forward over the whole sequence:
    logits, the first and last Mamba layer's state after each scored
    position, every final state and tail, the routers' picks, the
    counters. All kernels interpreted, and the XLA forms."""
    _, params = tiny
    cfg = nh.tiny_nemotron_h(max_seq_len=400)
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    kw = {} if flash else {"flash": False, "interpret": False}
    with jax.default_matmul_precision("highest"):
        be, got, state = _through_the_engine(cfg, params, ids, 150, 256, **kw)
        sizes = _sizes(cfg)
        want = reference_of(reference, sizes, params, ids, last=6)
        assert _rel(got, want["logits"]) < 1e-5
        assert got.shape == (6, cfg.vocab_size)
        lay = reference.state_as_the_program_lays_it
        for row in range(6):
            for which in (0, 1):
                assert _rel(state["rows"]["ssm"][row, which, 0],
                            lay(want["ssm_rows"][which, row])) < 1e-5
    cache = state["cache"]
    assert _picks_agree(state, want, 6)
    assert _rel(cache["ssm"][:, 0], lay(want["ssm"])) < 1e-5
    assert _rel(cache["conv"][:, 0], want["conv"]) < 1e-5
    # keys and values of the one attention layer: the prompt's rows end at
    # slot 256, each forced token's follows
    assert _rel(cache["k"][0, 0, :, 106:261].swapaxes(0, 1),
                want["k"][0]) < 1e-5
    # every real token routed to 4 experts on each of 4 sparse layers, all
    # held; each expert's tokens the reference's own count
    assert int(cache["slots_routed"]) == int(cache["slots_held"]) \
        == 155 * 4 * 4
    theirs = np.stack([np.bincount(np.asarray(layer).ravel(), minlength=16)
                       for layer in want["ids"]])
    assert (np.asarray(cache["expert_tokens"]) == theirs).all()
    assert int(cache["decode_layer_steps"]) == 5 * 4
    if flash:
        assert be.stats.attention_paths["logits[B=1,S=256]"] == {
            "prefill": "kernel", "decode": "kernel"}


@pytest.fixture(scope="module")
def unpadded(tiny):
    """A 56-token prompt and 4 forced tokens through the engine with no
    pad at all, and the reference's forward over the 60."""
    cfg = nh.tiny_nemotron_h(max_seq_len=400)
    _, params = tiny
    ids = np.asarray(_tokens(60, 1, seed=4))[0].tolist()
    with jax.default_matmul_precision("highest"):
        want = reference_of(reference, _sizes(cfg), params, ids, last=5)
        _, got, state = _through_the_engine(cfg, params, ids, 56, 56)
    return cfg, ids, want, got, state["cache"]


@pytest.mark.parametrize("pad", [0, 1, 7, 170])
def test_pad_length_changes_neither_logits_nor_state(tiny, unpadded, pad):
    """The same prompt under a left pad of 0, 1, a scan chunk less one and
    more than a prefill chunk: the state is zero when the first real token
    arrives and a pad position is routed nowhere, so logits, final state,
    tails and the experts' counts are the unpadded run's and the
    reference's."""
    cfg, ids, want, plain, plain_cache = unpadded
    _, params = tiny
    with jax.default_matmul_precision("highest"):
        _, got, state = _through_the_engine(cfg, params, ids, 56, 56 + pad)
    cache = state["cache"]
    assert _rel(got, plain) < 3e-6
    assert _rel(cache["ssm"], plain_cache["ssm"]) < 3e-6
    assert _rel(cache["conv"], plain_cache["conv"]) < 3e-6
    assert (cache["expert_tokens"] == plain_cache["expert_tokens"]).all()
    assert int(cache["slots_routed"]) == 60 * 4 * 4
    assert _rel(got, want["logits"]) < 1e-5
    assert _picks_agree(state, want, 5)
    assert _rel(cache["ssm"][:, 0],
                reference.state_as_the_program_lays_it(want["ssm"])) < 1e-5
    assert _rel(cache["conv"][:, 0], want["conv"]) < 1e-5


def test_a_bf16_state_fails_the_states_tolerance(tiny):
    """The check is tight enough to see a precision cut: with the recurrent
    state held in bfloat16 the final state misses 1e-4 by far, and a float32
    state meets 1e-5 (the tests above)."""
    cfg = nh.tiny_nemotron_h(max_seq_len=400, state_dtype=jnp.bfloat16)
    _, params = tiny
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    with jax.default_matmul_precision("highest"):
        _, got, state = _through_the_engine(cfg, params, ids, 150, 256)
        want = reference_of(reference, _sizes(cfg), params, ids, last=6)
    err = _rel(np.asarray(state["cache"]["ssm"][:, 0], np.float32),
               reference.state_as_the_program_lays_it(want["ssm"]))
    assert err > 1e-4, err
    assert state["cache"]["ssm"].dtype.name == "bfloat16"


def test_parity_check_passes_clean_and_fails_each_fault_it_can_see(tiny):
    """``engine_setup_nemotron_h.parity_with_reference`` at the rehearsal's
    size on the tiny weights in float32, its limits a float32 engine's
    (logits 1e-3, a tie band of 1e-4): ok with no fault; every fault of the
    equations fails one of its limits."""
    from benchmarks import cells, engine_setup_nemotron_h as setup

    config = cells.load_json(
        cells.ROOT / "benchmarks/configs/nemotron-3-nano-l16-int8.json")
    config["rehearsal"]["parity"].update(tolerance=1e-3, tie_band=1e-4,
                                        decode_tolerance=1e-3)
    cfg = setup.model_config(config, rehearsal=True)
    params = nh.init_params(jax.random.key(0), cfg)
    params["layers"]["router"] = params["layers"]["router"] * 10.0
    params["attn"]["wq"] = params["attn"]["wq"] * 30.0
    params["attn"]["wk"] = params["attn"]["wk"] * 30.0
    be = _engine(cfg, params, prefill_chunk_tokens=config["rehearsal"][
        "prefill_chunk_tokens"])
    clean = setup.parity_with_reference(be, config, 11, True)
    assert clean["ok"] and clean["kernel"], clean
    assert clean["state_dtype"] == "float32"
    for fault in reference.FAULTS:
        got = setup.parity_with_reference(be, config, 11, True,
                                          faults=(fault,))
        assert not got["ok"], fault


# -- the seam --------------------------------------------------------------------


def test_family_resolves_and_names_what_it_lacks():
    fam = family_of(nh.tiny_nemotron_h())
    assert fam is nh.FAMILY and fam.name == "nemotron-h"
    assert set(fam.missing) == {"slot loop", "prefix cache", "mesh",
                                "speculative decoding",
                                "long-context backend"}
    # the first family to supply all three
    assert fam.prefill_counts and fam.counters and fam.row_record
    assert fam.attention_layers(nh.tiny_nemotron_h()) == 1


@pytest.mark.parametrize("entry", sorted(nh.FAMILY.missing))
def test_family_refuses_by_the_text_of_what_it_lacks(entry):
    with pytest.raises(NotImplementedError) as e:
        nh.FAMILY.refuse(entry)
    assert nh.FAMILY.missing[entry] in str(e.value)
    assert "nemotron-h" in str(e.value)


@pytest.mark.parametrize("kw", [dict(cache_blocks=8), dict(mesh=object())])
def test_engine_refuses_the_entries_at_construction(tiny, kw):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="nemotron-h family"):
        _engine(cfg, params, **kw)


def test_engine_generates_and_counts_scan_cells_and_experts(tiny):
    """``TpuBackend.generate`` with every kernel interpreted: the prefill's
    attention cells counted over the ONE attention layer at 4 query heads a
    KV head, the scan's tokens over 4 Mamba layers beside them in
    ``prefill_blocks``, the expert counters on ``EngineStats``."""
    from vnsum_tpu.ops.flash_attention import prefill_block_classes

    cfg, params = tiny
    be = _engine(cfg, params, batch_size=2, max_new_tokens=6,
                 quantize_kv=True, fresh=True)
    packed = []
    pack = be._pack_group
    be._pack_group = lambda *a: packed.append(pack(*a)) or packed[-1]
    outs = be.generate(["xin chào " * 22, "một hai ba"], max_new_tokens=6)
    assert len(outs) == 2
    assert list(be.stats.attention_paths.values()) == [
        {"prefill": "kernel", "decode": "kernel"}]
    (_, pad_lens, B, S), = packed
    C = S + 6
    want = dict.fromkeys(("dead_causal", "dead_pad", "interior", "edge"), 0)
    for lo in range(0, S, 128):
        for name, n in prefill_block_classes(
                pad_lens, min(128, S - lo), C, lo, 0, 4, cfg.head_dim).items():
            want[name] += n
    real = int((S - np.asarray(pad_lens)).sum())
    want.update(nh.prefill_counts(
        cfg, pad_lens, [(lo, min(S, lo + 128)) for lo in range(0, S, 128)]))
    assert be.stats.prefill_blocks == want
    assert want["scan_tokens_real"] == real * 4
    st = be.stats
    # every real prompt token and every decode step's token, 4 picks on
    # each of 4 sparse layers, all held
    assert st.expert_slots_routed == st.expert_slots_held
    assert st.expert_slots_routed >= real * 4 * 4
    assert np.asarray(st.expert_tokens).shape == (4, 16)
    assert int(np.asarray(st.expert_tokens).sum()) == st.expert_slots_held
    assert st.expert_decode_layer_steps % 4 == 0
    assert 0 < st.expert_decode_touched <= st.expert_decode_layer_steps * 8
    per_row = be.describe()["state_bytes_per_row"]
    assert per_row["ssm"] == 4 * 16 * 128 * 4
    assert per_row["k"] == 1 * 2 * cfg.max_seq_len * 16


def test_generate_gives_the_same_rows_alone_and_in_a_batch(tiny):
    """A row's tokens do not hang on its neighbours or its pad: neither the
    state nor an expert's rows of one row reach another's (greedy, kernels
    interpreted)."""
    both, alone = alone_and_in_a_batch(*tiny)
    assert both == alone
