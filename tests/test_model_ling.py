"""The Ling-3.0-flash family (models/ling.py, ops/kda_scan.py, the shared
``sigmoid_group_route`` of models/experts.py and the latent attention of
models/deepseek.py at 4 heads with no compressed query) on the CPU at a tiny
size: six layers ``K K M | K K M`` — the first dense —, hidden 64, four
taps, 4 heads of 16, a scan chunk of 32, latent rank 32 + 8 rotated, 16
experts of 32 top-3 in 4 groups of which 2 are kept, a shared expert,
vocabulary 384 (the byte tokenizer's 256 bytes and its special ids)."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_setup_ling as setup
from benchmarks import reference_ling as reference
from family_harness import (
    alone_and_in_a_batch,
    engine as _engine,
    reference as jitted,
    reference_of,
    rel as _rel,
    sizes,
    through_the_engine as _through_the_engine,
    tokens as _tokens,
)
from vnsum_tpu.models import MODEL_REGISTRY, experts, jitted_init, ling
from vnsum_tpu.models.family import family_of


_sizes = functools.partial(sizes, setup)


def _reference(cfg, last=None, faults=()):
    return jitted(reference, _sizes(cfg), last=last, faults=faults)


def _reference_of(cfg, params, ids, last):
    return reference_of(reference, _sizes(cfg), params, ids, last=last,
                        faults=())


@pytest.fixture(scope="module")
def tiny():
    """The tiny config and its weights, the MLA layers' query and latent
    products thirty times the usual draw (a 0.02-normal draw gives scores
    flat to 1e-3: no rotary and no norm would show) and the router ten times
    (so that its scores spread as the published widths' do: 0.02 x
    sqrt(2560) = 1.0 a logit there)."""
    cfg = ling.tiny_ling()
    params = jitted_init(ling.init_params, cfg, 0)
    mla = dict(params["mla"], wq_b=params["mla"]["wq_b"] * 30.0,
               wkv_a=params["mla"]["wkv_a"] * 30.0)
    layers = dict(params["layers"], router=params["layers"]["router"] * 10.0)
    return cfg, dict(params, mla=mla, layers=layers)


# -- the config and the parameters ---------------------------------------------


def test_published_config_and_its_pattern():
    cfg = MODEL_REGISTRY["ling-3.0-flash"]()
    assert isinstance(cfg, ling.LingConfig)
    assert (cfg.dim, cfg.n_layers, cfg.vocab_size) == (2560, 42, 157184)
    assert [l for l, k in enumerate(cfg.layer_kinds) if k == "mla"] == [
        5, 11, 17, 23, 29, 35, 41]
    assert (cfg.n_kda, cfg.n_mla, cfg.n_sparse) == (35, 7, 40)
    assert (cfg.n_heads, cfg.head_dim, cfg.short_conv_kernel_size,
            cfg.kda_lower_bound) == (32, 128, 4, -5.0)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.latent_width) == (512, 128, 64, 128, 576)
    assert (cfg.first_k_dense_replace, cfg.intermediate, cfg.moe_intermediate,
            cfg.shared_intermediate, cfg.n_routed_experts, cfg.n_held,
            cfg.num_experts_per_tok, cfg.n_group, cfg.topk_group,
            cfg.routed_scaling_factor) == (2, 6144, 768, 768, 512, 512, 8, 8,
                                           4, 2.5)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.tie_embeddings) == (
        6e6, 1e-6, False)
    assert cfg.state_dtype == jnp.float32 and cfg.dtype == jnp.bfloat16
    assert cfg.mla.head_dim == 192 and cfg.mla.softmax_scale == 192 ** -0.5
    # the cell's cut: layers 0-11, two whole periods, 128 of 512 held
    cut = ling.ling_3_0_flash(n_layers=12, experts_held=128)
    assert (cut.n_kda, cut.n_mla, cut.n_sparse, cut.n_held) == (10, 2, 10,
                                                                128)
    kinds = tuple((m, l < 2) for l, m in enumerate(cut.layer_kinds))
    assert [(first, len(period), repeats)
            for first, period, repeats in ling._plan(kinds)] == [
        (0, 1, 2), (2, 1, 3), (5, 1, 1), (6, 1, 5), (11, 1, 1)]
    tiny = MODEL_REGISTRY["tiny-ling"]()
    assert tiny == ling.tiny_ling()
    assert (tiny.n_kda, tiny.n_mla, tiny.n_sparse) == (4, 2, 5)


@pytest.mark.parametrize("kw, text", [
    (dict(n_group=3), "n_group must divide"),
    (dict(topk_group=5), "between 1 and n_group"),
    (dict(num_experts_per_tok=9), "more picks than"),
    (dict(experts_held=3), "no whole share"),
    (dict(experts_held=4, expert_offset=2), "no whole share"),
    (dict(first_k_dense_replace=7), "past the depth"),
    (dict(qk_rope_head_dim=7), "is even"),
    (dict(n_kv_heads=2), "as many KV heads"),
])
def test_config_refuses_what_it_cannot_mean(kw, text):
    with pytest.raises(ValueError, match=text):
        ling.tiny_ling(**kw)


def test_parameters_are_stacked_by_kind(tiny):
    cfg, params = tiny
    assert set(params) == {"embed", "kda", "mla", "dense", "layers",
                           "final_norm", "lm_head"}
    kda, mla = params["kda"], params["mla"]
    assert set(kda) == {"mixer_norm", "wq", "wk", "wv", "wa", "w_beta",
                        "wg_head", "o_norm", "wo", "conv_w", "A_log",
                        "dt_bias"}
    # [.., D, H, hd] as stored, whatever view the mixer takes of them:
    # ``benchmarks/reference_ling.py`` reads them ``sd,dhk->shk``
    assert kda["wq"].shape == kda["wa"].shape == (4, 64, 4, 16)
    assert kda["w_beta"].shape == kda["wg_head"].shape == (4, 64, 4)
    assert kda["conv_w"].shape == (4, 3 * 64, 4)
    assert kda["A_log"].shape == (4, 4) and kda["dt_bias"].shape == (4, 4, 16)
    assert all(kda[n].dtype == jnp.float32
               for n in ("conv_w", "A_log", "dt_bias"))
    # no compressed query: one projection to every head's nope + rope
    assert set(mla) == {"mixer_norm", "wq_b", "wkv_a", "kv_norm", "wk_b",
                        "wv_b", "wg_head", "wo"}
    assert mla["wq_b"].shape == (2, 64, 4, 24)
    assert mla["wkv_a"].shape == (2, 64, 40)
    assert params["dense"]["w_gate"].shape == (1, 64, 128)
    layers = params["layers"]
    assert layers["router"].shape == (5, 64, 16)
    assert layers["expert_bias"].shape == (5, 16)
    assert layers["we_gate"].shape == (5, 16, 64, 32)
    assert layers["ws_down"].shape == (5, 32, 64)


def test_int8_init_keeps_the_float_leaves_and_the_routers_outputs():
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = ling.tiny_ling(experts_held=4)
    q = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                       jax.random.key(0))
    for group, leaves in (("kda", ("conv_w", "A_log", "dt_bias")),
                          ("layers", ("router", "expert_bias"))):
        for n in leaves:
            assert q[group][n].dtype == jnp.float32, n
    for n in ("wq", "wk", "wv", "wa", "w_beta", "wg_head", "wo"):
        assert q["kda"][n]["q"].dtype == jnp.int8, n
    for n in ("wq_b", "wkv_a", "wk_b", "wv_b", "wg_head", "wo"):
        assert q["mla"][n]["q"].dtype == jnp.int8, n
    # the router keeps all 16 outputs, the tree holds 4 experts a layer
    assert q["layers"]["router"].shape == (5, 64, 16)
    assert q["layers"]["we_up"]["q"].shape == (5, 4, 64, 32)
    assert q["lm_head"]["q"].shape == (64, 384)
    drawn = init_params_quantized(jax.random.key(3), cfg)
    dt = np.asarray(drawn["kda"]["dt_bias"])
    assert -8.0 <= dt.min() < -7.0 and -2.0 < dt.max() <= -1.0


def test_the_state_is_three_kinds_side_by_side():
    cfg = ling.tiny_ling()
    cache = ling.init_cache(cfg, 3, 40)
    assert cache["latent"].shape == (2, 3, 40, 40)       # 2 MLA layers
    assert cache["kda"].shape == (4, 3, 4, 16, 16)       # 4 KDA layers
    assert cache["kda"].dtype == jnp.float32
    assert cache["conv"].shape == (4, 3, 3, 192)         # q | k | v
    assert cache["expert_tokens"].shape == (5, 16)
    assert cache["picks"].shape == (5, 3, 3)
    assert "decode_touched" in cache
    with pytest.raises(ValueError, match="no int8 form"):
        ling.init_cache(cfg, 3, 40, quantized=True)
    full = jax.eval_shape(lambda: ling.init_cache(
        ling.ling_3_0_flash(n_layers=12, experts_held=128), 24, 8448))
    assert full["latent"].shape == (2, 24, 8448, 576)
    assert full["latent"].dtype == jnp.bfloat16
    assert full["kda"].shape == (10, 24, 32, 128, 128)
    assert full["kda"].dtype == jnp.float32
    assert full["conv"].shape == (10, 24, 3, 12288)
    assert full["conv"].dtype == jnp.bfloat16
    assert full["expert_tokens"].shape == (10, 128)


# -- the router ----------------------------------------------------------------


def test_group_route_by_hand():
    """Two groups of three, one kept, top-2: group 0 holds the best expert
    (0.9) but group 1 the better two (0.8 + 0.7 > 0.9 + 0.1)."""
    logits = jnp.log(jnp.asarray([[0.9, 0.1, 0.05, 0.8, 0.7, 0.2]])
                     / (1 - jnp.asarray([[0.9, 0.1, 0.05, 0.8, 0.7, 0.2]])))
    ids, w = experts.sigmoid_group_route(logits, jnp.zeros(6), 2, 2, 1, 2.5)
    assert sorted(np.asarray(ids)[0].tolist()) == [3, 4]
    assert np.allclose(np.sort(np.asarray(w)[0]),
                       [0.7 / 1.5 * 2.5, 0.8 / 1.5 * 2.5], atol=1e-6)
    # the bias steers the choice and is no part of the weight
    bias = jnp.asarray([0.0, 0.9, 0.0, 0.0, 0.0, 0.0])
    ids, w = experts.sigmoid_group_route(logits, bias, 2, 2, 1, 1.0)
    assert sorted(np.asarray(ids)[0].tolist()) == [0, 1]
    assert np.allclose(np.sort(np.asarray(w)[0]), [0.1, 0.9], atol=1e-6)
    # a group by its largest member would keep group 0
    ids, _ = experts.sigmoid_group_route(logits, jnp.zeros(6), 2, 2, 1, 1.0,
                                         group_top=1)
    assert sorted(np.asarray(ids)[0].tolist()) == [0, 1]


def test_group_route_is_the_references_rule_at_the_published_counts():
    logits = jax.random.normal(jax.random.key(5), (400, 512)) * 1.0
    bias = jax.random.normal(jax.random.key(6), (512,)) * 0.05
    sizes = dict(n_group=8, topk_group=4, num_experts_per_tok=8,
                 routed_scaling_factor=2.5)
    ids, w = experts.sigmoid_group_route(logits, bias, 8, 8, 4, 2.5)
    rid, rw = reference.route(logits, bias, sizes)
    assert (np.sort(np.asarray(ids), -1) == np.sort(np.asarray(rid), -1)).all()
    assert np.allclose(np.sort(np.asarray(w), -1),
                       np.sort(np.asarray(rw), -1), atol=1e-6)
    # 8 picks in at most 4 groups of 64
    assert (np.asarray([len(set(r // 64)) for r in np.asarray(ids)]) <= 4
            ).all()
    assert np.allclose(np.asarray(w).sum(-1), 2.5, atol=1e-5)
    free, _ = reference.route(logits, bias, sizes, ("no_group_limit",))
    moved = np.mean([len(set(a) - set(b)) for a, b in zip(
        np.asarray(rid).tolist(), np.asarray(free).tolist())])
    assert moved > 0.5, moved


def test_reference_takes_rightful_picks_inside_the_band_alone():
    sizes = dict(n_group=2, topk_group=1, num_experts_per_tok=2)
    ranked = jnp.asarray([[0.9, 0.5, 0.495, 0.1, 0.1, 0.1]] * 4)
    theirs = jnp.asarray([[0, 2], [0, 3], [0, 0], [0, 1]])
    took = reference.ties_broken_their_way(ranked, theirs, sizes, 0.01)
    # [0, 2]: 0.495 within the band of 0.5; [0, 3]: two groups; [0, 0]: twice
    assert np.asarray(took).tolist() == [True, False, False, True]
    assert np.asarray(reference.ties_broken_their_way(
        ranked, theirs, sizes, 0.0)).tolist() == [False, False, False, True]


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The expert layer is told what it holds (the model-configs guide's
    section 4 test): with 4 experts held at ``expert_offset`` 0, 4, 8, 12
    the four routed parts plus the shared expert ONCE add up to the layer
    that holds all 16, which is the uncut reference's — through the kernels
    and through ``dense_experts``."""
    cfg, params = tiny
    slot = 1
    lp = jax.tree.map(lambda a: a[slot], {
        n: w for n, w in params["layers"].items()
        if n not in experts.EXPERT_LEAVES})
    x = jax.random.normal(jax.random.key(9), (2, 30, cfg.dim), jnp.float32)
    valid = jnp.ones((2, 30), bool)
    no_shared = dict(lp, ws_down=jnp.zeros_like(lp["ws_down"]))

    def layer(cfg, offset, fn, lp=lp):
        stacked = {n: params["layers"][n][:, offset:offset + cfg.n_held]
                   for n in experts.EXPERT_LEAVES}
        cache = experts.init_expert_state(cfg.n_sparse, cfg.n_held, 2, 3,
                                          decode_touched=True)
        experts_fn = None if fn is None else (
            lambda *a: experts.grouped_experts(*a, cfg, interpret=True))
        return ling._sparse_ffn(x, lp, stacked, slot, valid, cache, cfg,
                                experts_fn)

    with jax.default_matmul_precision("highest"):
        u = reference._rmsnorm(x[0], lp["ffn_norm"], cfg.norm_eps)
        want, _, _ = reference.sparse_ffn(
            u, lp, {n: params["layers"][n] for n in experts.EXPERT_LEAVES},
            slot, _sizes(cfg), jnp.zeros((0, 3), jnp.int32), 0.0)
        for fn in (None, "kernels"):
            whole, counted = layer(cfg, 0, fn)
            parts = [layer(dataclasses.replace(cfg, experts_held=4,
                                               expert_offset=offset),
                           offset, fn, lp if offset == 0 else no_shared)
                     for offset in (0, 4, 8, 12)]
            total = sum(out for out, _ in parts)
            assert _rel(total, whole) < 1e-5
            assert _rel(total[0], want) < 1e-5
            assert int(counted["slots_held"]) == 2 * 30 * 3 == sum(
                int(c["slots_held"]) for _, c in parts)
            assert all(int(c["slots_routed"]) == 2 * 30 * 3
                       for _, c in parts)
            assert (np.concatenate([c["expert_tokens"][slot]
                                    for _, c in parts])
                    == np.asarray(counted["expert_tokens"][slot])).all()


# -- against the reference ---------------------------------------------------------


@pytest.mark.parametrize("int8", [False, True])
def test_cache_free_forward_equals_the_reference(tiny, int8):
    from vnsum_tpu.models.quant import quantize_params

    cfg, params = tiny
    if int8:
        params = quantize_params(params)
    toks = _tokens(37)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: ling.forward_dense(p, cfg, t))(params,
                                                                  toks)
    want = jnp.stack([_reference(cfg)(params, t)["logits"] for t in toks])
    assert got.shape == (2, 37, cfg.vocab_size)
    assert float(jnp.abs(want).max()) > 0.1
    assert _rel(got, want) < 1e-5


@pytest.fixture(scope="module")
def clean_logits(tiny):
    cfg, params = tiny
    return _reference(cfg)(params, _tokens(37)[0])["logits"]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_every_departure_of_the_reference_shows_in_the_logits(
        tiny, clean_logits, fault):
    cfg, params = tiny
    other = _reference(cfg, None, (fault,))(params, _tokens(37)[0])["logits"]
    assert _rel(other, clean_logits) > 1e-3


def test_reference_refuses_an_unknown_fault(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="unknown faults"):
        reference.logits(params, _tokens(5)[0], _sizes(cfg), faults=("x",))


def test_reference_is_plain_float32_and_reads_nothing_of_the_program():
    from pathlib import Path

    src = (Path(__file__).resolve().parents[1] / "benchmarks"
           / "reference_ling.py").read_text()
    code = src.split('"""', 2)[2]
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in code
    assert "jax.lax.scan(step, S" in code          # the rule, token by token
    assert "for j in range(K)" in code             # the taps, an explicit sum
    assert "fori_loop(0, held, one_expert" in code   # ONE expert at a time
    for word in ("pallas", "bfloat16", "import vnsum", "from vnsum",
                 "lax.conv", "cumsum", "solve_triangular"):
        assert word not in code, word
    assert len(reference.FAULTS) == 17


def _agrees_with_the_reference(cfg, params, ids, n, bucket, rows, **kw):
    """Logits, the first and last KDA layer's state position by position,
    every layer's final state and tail, the latent rows, picks and every
    expert's token count of a prompt of ``n`` tokens in ``bucket`` and
    ``len(ids) - n`` forced tokens, against the reference's one forward."""
    with jax.default_matmul_precision("highest"):
        be, got, state = _through_the_engine(cfg, params, ids, n, bucket,
                                             **kw)
    want = _reference_of(cfg, params, ids, rows)
    assert got.shape == (rows, cfg.vocab_size)
    assert _rel(got, want["logits"]) < 2e-5
    for row in range(rows):
        for which in (0, 1):
            assert _rel(state["rows"]["state"][row, which, 0],
                        want["state_rows"][which, row]) < 2e-5
    cache = state["cache"]
    mine = np.sort(np.asarray(state["rows"]["picks"])[:, :, 0], -1)
    theirs = np.sort(np.asarray(want["ids"])[:, -rows:], -1).swapaxes(0, 1)
    assert (mine == theirs).all()
    assert _rel(cache["kda"][:, 0], want["kda"]) < 2e-5
    assert _rel(cache["conv"][:, 0], want["conv"]) < 2e-5
    pad, total = bucket - n, len(ids)
    assert _rel(cache["latent"][:, 0, pad:pad + total], want["latent"]) < 2e-5
    sparse, k = cfg.n_sparse, cfg.num_experts_per_tok
    assert int(cache["slots_routed"]) == int(cache["slots_held"]) \
        == total * k * sparse
    counts = np.stack([np.bincount(np.asarray(layer).ravel(),
                                   minlength=cfg.n_routed_experts)
                       for layer in want["ids"]])
    assert (np.asarray(cache["expert_tokens"]) == counts).all()
    assert int(cache["decode_layer_steps"]) == (rows - 1) * sparse
    return be, got, state, want


@pytest.mark.parametrize("flash", [True, False])
def test_engine_prefill_and_decode_agree_with_the_reference(tiny, flash):
    """The engine's chunked prefill — a left pad of 106 in a bucket of 256,
    two prefill chunks of 128 and eight scan chunks of 32, boundaries of
    both kinds inside the prompt — and then teacher-forced decode steps
    through state, tails, latent cache and counters, against the
    reference's one forward over the whole sequence. All kernels
    interpreted, and the XLA forms."""
    _, params = tiny
    cfg = ling.tiny_ling(max_seq_len=400)
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    kw = {} if flash else {"flash": False, "interpret": False}
    be, *_ = _agrees_with_the_reference(cfg, params, ids, 150, 256, 6, **kw)
    if flash:
        assert be.stats.attention_paths["logits[B=1,S=256]"] == {
            "prefill": "kernel", "decode": "kernel"}


@pytest.mark.parametrize("pad", [31, 32, 33, 127, 128, 129])
def test_the_first_real_token_on_either_side_of_a_boundary(tiny, pad):
    """The first real token one before, at and one after a SCAN-chunk
    boundary (32) and a PREFILL-chunk boundary (128, which is both), in a
    bucket of 384 (three prefill chunks): what a chunk's first positions
    read of the one before is the tail and the state, zero where they lay
    under the pad. Kernels interpreted at the prefill boundary, the XLA
    forms at the scan's."""
    _, params = tiny
    cfg = ling.tiny_ling(max_seq_len=512)
    n = 384 - pad
    ids = np.asarray(_tokens(n + 3, 1, seed=pad))[0].tolist()
    kw = {} if pad > 100 else {"flash": False, "interpret": False}
    _agrees_with_the_reference(cfg, params, ids, n, 384, 4, **kw)


@pytest.fixture(scope="module")
def unpadded(tiny):
    """A 56-token prompt and 4 forced tokens through the engine with no
    pad at all, and the reference's forward over the 60."""
    cfg = ling.tiny_ling(max_seq_len=400)
    _, params = tiny
    ids = np.asarray(_tokens(60, 1, seed=4))[0].tolist()
    _, got, state, want = _agrees_with_the_reference(
        cfg, params, ids, 56, 56, 5, flash=False, interpret=False)
    return cfg, ids, want, got, state["cache"]


@pytest.mark.parametrize("pad", [1, 3, 127, 170])
def test_pad_length_changes_neither_logits_nor_state(tiny, unpadded, pad):
    """The same prompt under a left pad of 1, 3 = taps - 1, a prefill chunk
    less one and more than a prefill chunk: state and tails are exactly
    zero when the first real token arrives and a pad position is routed
    nowhere, so logits, states, tails and the experts' counts are the
    unpadded run's and the reference's."""
    cfg, ids, want, plain, plain_cache = unpadded
    _, params = tiny
    # the kernels interpreted under the pad of a chunk less one, the XLA
    # forms under the others
    kw = {} if pad == 127 else {"flash": False, "interpret": False}
    _, got, state, _ = _agrees_with_the_reference(
        cfg, params, ids, 56, 56 + pad, 5, **kw)
    cache = state["cache"]
    assert _rel(got, plain) < 5e-6
    assert _rel(cache["kda"], plain_cache["kda"]) < 5e-6
    assert _rel(cache["conv"], plain_cache["conv"]) < 5e-6
    assert (cache["expert_tokens"] == plain_cache["expert_tokens"]).all()


@pytest.mark.parametrize("kernels", [True, False])
def test_state_and_tails_are_exactly_zero_under_a_pad_of_any_length(
        tiny, kernels):
    """No bias anywhere in the mixer and beta zeroed, said by a test and not
    assumed: a forward over nothing but pad positions leaves every state and
    tail exactly zero, with W8A8 products too, whatever the gate reads."""
    from vnsum_tpu.models.quant import quantize_params

    cfg, params = tiny
    toks = _tokens(40, 2)
    positions = jnp.zeros((2, 40), jnp.int32)
    mask = jnp.zeros((2, 40, 40), bool)
    kw = dict(scan_kernels=True, interpret=True) if kernels else {}
    for c, p in ((cfg, params),
                 (dataclasses.replace(cfg, w8a8_prefill=True),
                  quantize_params(params))):
        _, cache = jax.jit(lambda p, c=c: ling.forward(
            p, c, toks, positions, ling.init_cache(c, 2, 40), 0, mask, **kw)
        )(p)
        assert not np.asarray(cache["kda"]).any()
        assert not np.asarray(cache["conv"]).any()
        assert int(cache["slots_routed"]) == 0


def test_a_state_kept_a_precision_below_fails_the_states_tolerance(tiny):
    """The check is tight enough to see a precision cut: with the matrix
    state held in bfloat16 under the float32 configuration the states miss
    2e-5 by far."""
    cfg = ling.tiny_ling(max_seq_len=400, state_dtype=jnp.bfloat16)
    _, params = tiny
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    with jax.default_matmul_precision("highest"):
        _, got, state = _through_the_engine(cfg, params, ids, 150, 256)
    want = _reference_of(cfg, params, ids, 6)
    assert state["cache"]["kda"].dtype.name == "bfloat16"
    err = _rel(np.asarray(state["cache"]["kda"][:, 0], np.float32),
               want["kda"])
    assert err > 1e-3, err


def test_a_latent_rounded_to_int8_sits_on_the_grid_and_off_the_reference(
        tiny):
    from benchmarks.engine_setup_ling import grid_distance

    _, params = tiny
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    cfg = ling.tiny_ling(max_seq_len=400, latent_int8=True)
    with jax.default_matmul_precision("highest"):
        _, _, state = _through_the_engine(cfg, params, ids, 150, 256,
                                          flash=False, interpret=False)
    want = _reference_of(cfg, params, ids, 6)
    rows = np.asarray(state["cache"]["latent"][0, 0, 106:261])
    assert _rel(rows, want["latent"][0]) > 1e-3
    assert grid_distance(rows) < 0.01
    # the reference's own rows, float32, lie between the grid's points
    assert grid_distance(want["latent"][0]) > 0.2


# -- the mixer's views (PR 62) -------------------------------------------------------


def _mixer_as_stored(u, lp, slot, valid, cache, cfg, cache_rows=None):
    """``_kda_mixer``'s arithmetic as it was written before PR 62, XLA
    path: every projection ``bsd,dhk->bshk`` on the leaf as it is stored,
    the unit norms and the head norm on the [B, S, H, hd] reshape."""
    from vnsum_tpu.models.llama import _cache_write, _proj
    from vnsum_tpu.models.mamba_mixer import causal_conv
    from vnsum_tpu.ops import kda_scan

    B, S, _ = u.shape
    H, hd, W = cfg.n_heads, cfg.head_dim, cfg.kda_width
    aq = cfg.w8a8_prefill and S > 1
    f32 = jnp.float32
    parts = [_proj("bsd,dhk->bshk", u, lp[n], aq).reshape(B, S, W)
             for n in ("wq", "wk", "wv")]
    a = _proj("bsd,dhk->bshk", u, lp["wa"], aq)
    b = _proj("bsd,dh->bsh", u, lp["w_beta"], aq)
    gate = _proj("bsd,dh->bsh", u, lp["wg_head"], aq)
    tail = cache["conv"][slot]
    if cache_rows is not None:
        tail = tail[cache_rows]
    out = [causal_conv(x, tail[..., i * W:(i + 1) * W],
                       lp["conv_w"][i * W:(i + 1) * W])
           for i, x in enumerate(parts)]
    conv = _cache_write(
        cache["conv"], jnp.concatenate([t for _, t in out], -1).astype(
            cache["conv"].dtype), slot, 0, cache_rows)
    q, k, v = (x.reshape(B, S, H, hd) for x, _ in out)

    def unit(x):
        return x / (jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)) + ling.L2_EPS)

    q = (unit(q) * hd ** -0.5).astype(u.dtype)
    k = unit(k).astype(u.dtype)
    v = v.astype(u.dtype)
    g = kda_scan.kda_gate(a, A_log=lp["A_log"], dt_bias=lp["dt_bias"],
                          lower_bound=cfg.kda_lower_bound)
    beta = jnp.where(valid[..., None], jax.nn.sigmoid(b.astype(f32)), 0.0)
    mine = cache["kda"][slot].astype(f32)
    if S == 1:
        o, mine = kda_scan.kda_step_xla(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], mine)
        o = o[:, None]
    else:
        o, mine = kda_scan.kda_chunked_xla(
            q, k, v, g, beta, mine, cfg.kda_chunk_size, cache_rows)
    state = cache["kda"].at[slot].set(mine.astype(cache["kda"].dtype))
    o = o.astype(f32)
    o = o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    y = (o * lp["o_norm"].astype(f32)
         * jax.nn.sigmoid(gate.astype(f32))[..., None]).astype(u.dtype)
    return _proj("bshk,hkd->bsd", y, lp["wo"], aq), dict(
        cache, conv=conv, kda=state)


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("S, pads, rows", [
    (1, (0, 0), None),            # a decode step
    (12, (0, 5), None),           # no whole tile of eight tokens
    (64, (3, 40), (4, 1)),        # two chunks under left pads, a row piece
])
def test_the_mixers_views_change_no_value(S, pads, rows, int8):
    """The projections against ``_lane_views``' [D, H * hd], the unit norms
    and the head norm in ``_head_tiles``' order where S is whole tiles of
    eight tokens (as it is, elsewhere): output, tails and matrix state
    BIT-EQUAL to the arithmetic on the stored shapes — both are views, no
    sum changes its terms or their order — in bfloat16 on int8 weights
    under W8A8 as in float32."""
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = ling.tiny_ling(w8a8_prefill=int8,
                         dtype=jnp.bfloat16 if int8 else jnp.float32)
    if int8:
        kda = init_params_quantized(jax.random.key(5), cfg)["kda"]
    else:
        kda = jitted_init(ling.init_params, cfg, 5)["kda"]
    slot, batch = 2, 5 if rows else 2
    keys = jax.random.split(jax.random.key(S), 3)
    cache = ling.init_cache(cfg, batch, 8)
    cache = dict(
        cache,
        kda=jax.random.normal(keys[0], cache["kda"].shape, jnp.float32) * 0.1,
        conv=jax.random.normal(keys[1], cache["conv"].shape).astype(cfg.dtype))
    valid = jnp.arange(S)[None, :] >= jnp.asarray(pads)[:, None]
    u = jax.random.normal(keys[2], (2, S, cfg.dim)).astype(cfg.dtype)
    u = jnp.where(valid[..., None], u, jnp.zeros_like(u))
    cache_rows = None if rows is None else jnp.asarray(rows, jnp.int32)
    layer = lambda tree: jax.tree.map(lambda w: w[slot], tree)  # noqa: E731

    # operation by operation: a jitted program's fusions round in their own
    # ways (the two forms sit a last bit apart there, as any two programs)
    want, kept = _mixer_as_stored(
        u, layer(kda), slot, valid, cache, cfg, cache_rows)
    got, mine = ling._kda_mixer(
        u, layer(ling._lane_views(kda)), slot, valid, cache, cfg, False,
        False, cache_rows)
    assert got.dtype == want.dtype == cfg.dtype
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)).all()
    for name in ("conv", "kda"):
        assert (np.asarray(mine[name], np.float32)
                == np.asarray(kept[name], np.float32)).all(), name
    touched = list(rows or range(batch))
    assert not (np.asarray(mine["kda"][slot, touched])
                == np.asarray(cache["kda"][slot, touched])).all()


def test_lane_views_turn_the_four_projections_alone():
    """[L, D, H, hd] -> [L, D, H * hd], an int8 leaf's scales with it; ``wo``
    [L, H, hd, D] -> [L, H * hd, D], its scales as they are; every other
    leaf is the stored array itself."""
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = ling.tiny_ling()
    kda = init_params_quantized(jax.random.key(0), cfg)["kda"]
    views = ling._lane_views(kda)
    assert set(views) == set(kda)
    for n in ling.LANE_LEAVES:
        assert views[n]["q"].shape == (4, 64, 64)
        assert views[n]["s"].shape == (4, 64)
        assert (np.asarray(views[n]["q"]).reshape(kda[n]["q"].shape)
                == np.asarray(kda[n]["q"])).all()
    assert views["wo"]["q"].shape == (4, 64, 64)
    assert views["wo"]["s"] is kda["wo"]["s"]
    for n in set(kda) - set(ling.LANE_LEAVES) - {"wo"}:
        assert all(a is b for a, b in zip(jax.tree.leaves(views[n]),
                                          jax.tree.leaves(kda[n])))
    x = jnp.arange(2 * 16 * 3 * 5.0).reshape(2, 16, 3, 5)
    tiles = ling._head_tiles(x)
    assert tiles.shape == (2, 2, 3, 8, 5)
    assert (tiles[1, 1, 2, 3] == x[1, 11, 2]).all()
    assert (ling._head_rows(tiles, 16) == x).all()
    assert ling._head_tiles(x[:, :12]).shape == (2, 12, 3, 5)


# -- row pieces --------------------------------------------------------------------


@pytest.mark.parametrize("flash", [True, False])
def test_row_pieces_give_what_the_whole_batch_gives(tiny, flash):
    """A batch of four rows under pads that put the first real token around
    a chunk boundary, in pieces of two rows and as a whole: the same tokens
    (greedy), the same counters; the piece whose rows are all pad in the
    first chunk is not run."""
    from vnsum_tpu.core.config import GenerationConfig

    cfg, params = tiny
    cfg = dataclasses.replace(cfg, max_seq_len=400)
    prompts = ["a" * 130, "b" * 129, "c" * 128, "d" * 250]
    kw = dict(batch_size=4, max_new_tokens=5,
              generation=GenerationConfig(temperature=0.0))
    if not flash:
        kw.update(flash=False, interpret=False)
    whole = _engine(cfg, params, piece_tokens=10 ** 6, **kw)
    pieces = _engine(cfg, params, piece_tokens=256, **kw)
    assert pieces._prefill_piece_rows(4, 128) == 2
    assert whole._prefill_piece_rows(4, 128) == 0
    assert whole.generate(prompts, max_new_tokens=5) \
        == pieces.generate(prompts, max_new_tokens=5)
    for name in ("expert_slots_routed", "expert_slots_held",
                 "expert_decode_touched"):
        assert getattr(whole.stats, name) == getattr(pieces.stats, name)
    assert (np.asarray(whole.stats.expert_tokens)
            == np.asarray(pieces.stats.expert_tokens)).all()
    assert pieces.stats.prefill_row_chunks_dead == 0
    short = _engine(cfg, params, piece_tokens=256, **kw)
    short.generate(["a" * 100, "b" * 90, "c" * 250, "d" * 200],
                   max_new_tokens=5)
    assert short.stats.prefill_row_chunks_dead == 2


# -- the seam --------------------------------------------------------------------


def test_family_resolves_and_names_what_it_lacks():
    fam = family_of(ling.tiny_ling())
    assert fam is ling.FAMILY and fam.name == "ling"
    assert set(fam.missing) == {"slot loop", "prefix cache", "mesh",
                                "speculative decoding",
                                "long-context backend"}
    assert fam.prefill_counts and fam.counters and fam.row_record
    assert not fam.int8_cache and not fam.counts_prefill_blocks
    assert fam.attention_layers(ling.tiny_ling()) == 2
    assert fam.prefill_piece_tokens == ling.PREFILL_PIECE_TOKENS == 8192
    assert fam.kernels_supported(ling.ling_3_0_flash(), False)
    assert not fam.kernels_supported(ling.tiny_ling(), False)
    assert fam.kernels_supported(ling.tiny_ling(), True)


@pytest.mark.parametrize("entry", sorted(ling.FAMILY.missing))
def test_family_refuses_by_the_text_of_what_it_lacks(entry):
    with pytest.raises(NotImplementedError) as e:
        ling.FAMILY.refuse(entry)
    assert ling.FAMILY.missing[entry] in str(e.value)
    assert "ling" in str(e.value)
    assert "matrix state" in ling.FAMILY.missing[entry]


@pytest.mark.parametrize("kw", [dict(cache_blocks=8), dict(mesh=object())])
def test_engine_refuses_the_entries_at_construction(tiny, kw):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="ling family"):
        _engine(cfg, params, **kw)


def test_engine_refuses_the_slot_loop_and_the_ring(tiny):
    cfg, params = tiny
    be = _engine(cfg, params, batch_size=2)
    with pytest.raises(NotImplementedError, match="slot loop"):
        be._get_seg_fn("slot_seg", 2, 64, 8, be.gen_cfg)
    from vnsum_tpu.backend.long_context import LongContextBackend

    with pytest.raises(NotImplementedError, match="long-context backend"):
        LongContextBackend(model_config=cfg, tokenizer="byte",
                           params=params, interpret=True)
    with pytest.raises(ValueError, match="no int8 form"):
        _engine(cfg, params, quantize_kv=True)


def test_prefill_counts_by_hand():
    """Four rows of a 256 bucket in two prefill chunks of 128, scan chunks
    of 32 — four a prefill chunk, one group of the scan kernel's state-free
    phase, computed whole or not at all: pads 6 and 0 skip nothing, 200 and
    130 the first prefill chunk and none of the second."""
    from vnsum_tpu.ops.mla_attention import prefill_tile_classes

    cfg = ling.tiny_ling()
    pads = [6, 200, 0, 130]
    got = ling.prefill_counts(cfg, pads, [(0, 128), (128, 256)])
    real = 250 + 56 + 256 + 126
    computed = (128 + 0 + 128 + 0) + (128 + 128 + 128 + 128)
    keys = sum(prefill_tile_classes(pads, 128, hi, lo)["keys_expanded"]
               for lo, hi in [(0, 128), (128, 256)])
    assert got == {"kda_tokens_real": real * 4,
                   "kda_tokens_computed": computed * 4,
                   "latent_keys_expanded": keys * 2,
                   "latent_keys_real": real * 2}


def test_engine_generates_and_counts_tokens_keys_and_experts(tiny):
    """``TpuBackend.generate`` with every kernel interpreted: the scan's
    tokens over 4 KDA layers and the latent kernel's keys over 2 MLA layers
    in ``prefill_blocks``, the expert counters on ``EngineStats``."""
    cfg, params = tiny
    be = _engine(cfg, params, batch_size=2, max_new_tokens=6, fresh=True)
    packed = []
    pack = be._pack_group
    be._pack_group = lambda *a: packed.append(pack(*a)) or packed[-1]
    outs = be.generate(["xin chào " * 22, "một hai ba"], max_new_tokens=6)
    assert len(outs) == 2
    assert list(be.stats.attention_paths.values()) == [
        {"prefill": "kernel", "decode": "kernel"}]
    (_, pad_lens, B, S), = packed
    spans = [(lo, min(S, lo + 128)) for lo in range(0, S, 128)]
    assert be.stats.prefill_blocks == ling.prefill_counts(
        cfg, pad_lens, spans)
    real = int((S - np.asarray(pad_lens)).sum())
    assert be.stats.prefill_blocks["kda_tokens_real"] == real * 4
    st = be.stats
    assert st.expert_slots_routed == st.expert_slots_held
    assert st.expert_slots_routed >= real * 3 * 5
    assert np.asarray(st.expert_tokens).shape == (5, 16)
    assert int(np.asarray(st.expert_tokens).sum()) == st.expert_slots_held
    assert st.expert_decode_layer_steps % 5 == 0
    assert 0 < st.expert_decode_touched <= st.expert_decode_layer_steps * 6
    per_row = be.describe()["state_bytes_per_row"]
    assert set(per_row) >= {"latent", "kda", "conv"}
    assert per_row["kda"] == 4 * 4 * 16 * 16 * 4
    assert per_row["conv"] == 4 * 3 * 192 * 4


def test_engine_counts_the_held_share_of_the_routed_slots(tiny):
    """A quarter of the experts held: every real token is still routed to
    three experts, and the slots held are those that fell on experts 0-3."""
    cfg, params = tiny
    cut = dataclasses.replace(cfg, experts_held=4)
    held = dict(params, layers={
        n: (w[:, :4] if n in experts.EXPERT_LEAVES else w)
        for n, w in params["layers"].items()})
    be = _engine(cut, held, batch_size=2, max_new_tokens=4)
    be.generate(["xin chào " * 22, "một hai ba"], max_new_tokens=4)
    st = be.stats
    assert 0 < st.expert_slots_held < st.expert_slots_routed
    assert np.asarray(st.expert_tokens).shape == (5, 4)
    assert int(np.asarray(st.expert_tokens).sum()) == st.expert_slots_held


def test_generate_gives_the_same_rows_alone_and_in_a_batch(tiny):
    """A row's tokens do not hang on its neighbours or its pad: neither the
    state, the tail, the latent rows nor an expert's rows of one row reach
    another's (greedy, kernels interpreted)."""
    both, alone = alone_and_in_a_batch(*tiny)
    assert both == alone
