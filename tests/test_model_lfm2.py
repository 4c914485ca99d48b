"""The LFM2-MoE family (models/lfm2.py, the shared ``causal_conv`` of
models/mamba_mixer.py and ``sigmoid_route`` of models/experts.py) on the
CPU at a tiny size: ten layers ``c c | A c c c | A c c c`` — two dense
layers and two periods after them —, hidden 64, three taps, 4 query heads
of 16 over 2 KV heads, 8 gated experts of 32 top-2, vocabulary 384 (the byte tokenizer's
256 bytes and its special ids)."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_setup_lfm2 as setup
from benchmarks import reference_lfm2 as reference
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
from vnsum_tpu.models import MODEL_REGISTRY, experts, jitted_init, lfm2, mamba_mixer
from vnsum_tpu.models.family import family_of


_sizes = functools.partial(sizes, setup)


@pytest.fixture(scope="module")
def tiny():
    """The tiny config and its weights, the query and key products thirty
    times the usual draw (a 0.02-normal draw gives scores flat to 1e-3: no
    rotary would show) and the router ten times (so that its scores spread
    as the published widths' do: 0.02 x sqrt(2048) = 0.9 a logit there)."""
    cfg = lfm2.tiny_lfm2()
    params = jitted_init(lfm2.init_params, cfg, 0)
    attn = dict(params["attn"], wq=params["attn"]["wq"] * 30.0,
                wk=params["attn"]["wk"] * 30.0)
    layers = dict(params["layers"], router=params["layers"]["router"] * 10.0)
    return cfg, dict(params, attn=attn, layers=layers)


# -- the config and the parameters ---------------------------------------------


def test_published_config_and_its_pattern():
    cfg = MODEL_REGISTRY["lfm2-8b-a1b"]()
    assert isinstance(cfg, lfm2.Lfm2Config)
    assert (cfg.dim, cfg.n_layers, cfg.vocab_size) == (2048, 24, 65536)
    assert len(cfg.layer_types) == 24
    assert (cfg.n_conv, cfg.n_attention, cfg.n_sparse) == (18, 6, 22)
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv) == (
        32, 8, 64, 4)
    assert (cfg.conv_L_cache, cfg.conv_bias, cfg.rope_theta,
            cfg.norm_eps) == (3, False, 1e6, 1e-5)
    assert (cfg.num_dense_layers, cfg.intermediate, cfg.n_routed_experts,
            cfg.n_held, cfg.moe_intermediate, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor) == (2, 7168, 32, 32, 1792, 4, 1.0)
    assert cfg.tie_embeddings and cfg.max_seq_len == 128_000
    assert cfg.state_dtype == cfg.dtype == jnp.bfloat16
    # five layer bodies whatever the depth: [c]x2, [A c c c]x4, [A c c]x2
    kinds = tuple((op, l < 2) for l, op in enumerate(cfg.layer_types))
    assert [(first, len(period), repeats)
            for first, period, repeats in lfm2._plan(kinds)] == [
        (0, 1, 2), (2, 4, 4), (18, 3, 2)]
    tiny = MODEL_REGISTRY["tiny-lfm2"]()
    assert tiny == lfm2.tiny_lfm2()
    assert (tiny.n_conv, tiny.n_attention, tiny.n_sparse) == (8, 2, 8)
    # a model cut in depth takes the leading layers
    assert lfm2.lfm2_8b_a1b(n_layers=7).layer_types.count(
        "full_attention") == 2


@pytest.mark.parametrize("kw, text", [
    (dict(conv_bias=True), "conv_bias true"),
    (dict(layer_types=("conv", "mamba") * 5), "entries of 'conv' or"),
    (dict(layer_types=("conv",) * 4), "needs 10 entries"),
    (dict(num_dense_layers=11), "past the depth"),
    (dict(n_held=3), "no whole share"),
    (dict(n_held=4, expert_offset=2), "no whole share"),
    (dict(use_expert_bias=False), "use_expert_bias"),
    (dict(n_kv_heads=3), "must divide"),
])
def test_config_refuses_what_it_cannot_mean(kw, text):
    with pytest.raises(ValueError, match=text):
        lfm2.tiny_lfm2(**kw)


def test_parameters_are_stacked_by_kind_with_three_matrix_experts(tiny):
    cfg, params = tiny
    assert set(params) == {"embed", "conv", "attn", "dense", "layers",
                           "final_norm"}           # tied: no lm_head
    conv, attn = params["conv"], params["attn"]
    assert set(conv) == {"op_norm", "in_b", "in_c", "in_x", "out_proj",
                         "conv_w"}
    assert conv["in_b"].shape == conv["out_proj"].shape == (8, 64, 64)
    assert conv["conv_w"].shape == (8, 64, 3)
    assert attn["wq"].shape == (2, 64, 4, 16)
    assert attn["wk"].shape == (2, 64, 2, 16)
    assert attn["q_norm"].shape == attn["k_norm"].shape == (2, 16)
    assert params["dense"]["w_gate"].shape == (2, 64, 128)
    layers = params["layers"]
    assert set(experts.EXPERT_LEAVES) <= set(layers)
    assert layers["we_gate"].shape == layers["we_up"].shape == (8, 8, 64, 32)
    assert layers["we_down"].shape == (8, 8, 32, 64)
    assert layers["router"].shape == (8, 64, 8)
    assert layers["expert_bias"].shape == (8, 8)
    # the taps as a depth-wise Conv1d draws them at three taps
    assert float(jnp.abs(conv["conv_w"]).max()) <= 3 ** -0.5


def test_int8_keeps_the_sensitive_leaves_in_float32():
    from vnsum_tpu.models.quant import (
        dequantize_params,
        init_params_quantized,
        quantize_params,
    )

    cfg = lfm2.tiny_lfm2(dtype=jnp.bfloat16)
    direct = init_params_quantized(jax.random.key(3), cfg)
    via = quantize_params(lfm2.init_params(jax.random.key(3), cfg))
    for tree in (direct, via):
        for group, names in (("conv", ("in_b", "in_c", "in_x", "out_proj")),
                             ("attn", ("wq", "wk", "wv", "wo")),
                             ("dense", ("w_gate", "w_up", "w_down")),
                             ("layers", experts.EXPERT_LEAVES)):
            for name in names:
                assert tree[group][name]["q"].dtype == jnp.int8, name
        assert isinstance(tree["embed"], dict) and "lm_head" not in tree
        assert tree["conv"]["conv_w"].dtype == jnp.float32
        assert tree["layers"]["router"].dtype == jnp.float32
        assert tree["layers"]["expert_bias"].dtype == jnp.float32
        assert tree["attn"]["q_norm"].dtype == jnp.bfloat16
    assert direct["layers"]["we_gate"]["s"].shape == (8, 8, 32)
    # the family's own draws, not the direct init's router-like normal
    assert float(jnp.abs(direct["conv"]["conv_w"]).max()) <= 3 ** -0.5
    assert float(jnp.std(direct["layers"]["expert_bias"])) < 0.1
    assert float(jnp.abs(direct["attn"]["q_norm"] - 1).max()) > 0.1
    assert jax.tree.structure(dequantize_params(direct)) \
        == jax.tree.structure(lfm2.init_params(jax.random.key(3), cfg))


# -- the shared routing rule and the shared convolution --------------------------


def test_route_is_one_rule_for_two_families():
    from vnsum_tpu.models import nemotron_h as nh

    assert nh.route is experts.sigmoid_route
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 10.0])
    # the choice from the bias, the weight from the score alone
    ids, w = experts.sigmoid_route(logits, bias, 2, 1.0,
                                   lfm2.ROUTE_DENOMINATOR_EPS)
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 3]
    s = jax.nn.sigmoid(logits[0])
    total = s[0] + s[3] + 1e-6
    want = {0: s[0] / total, 3: s[3] / total}
    for i, e in enumerate(np.asarray(ids[0]).tolist()):
        assert abs(float(w[0, i]) - float(want[e])) < 1e-7
    # Nemotron's call: no epsilon, its scaling
    _, w0 = experts.sigmoid_route(logits, bias, 2, 2.5)
    assert abs(float(w0.sum()) - 2.5) < 1e-6
    assert float(w.sum()) < 1.0


def test_the_seeded_bias_moves_a_tenth_of_the_picks_at_the_published_widths():
    """``expert_bias`` is drawn wide enough that leaving it out is a fault
    a check can see: against a zero bias more than a tenth of the picks of
    a router of the published shape (32 x top-4) change."""
    cfg = lfm2.lfm2_8b_a1b(n_layers=3)
    leaves = lfm2.float_leaves(jax.random.key(5), cfg)["layers"]
    h = jax.random.normal(jax.random.key(6), (512, cfg.dim), jnp.float32)
    logits = h @ leaves["router"][0]
    with_bias, _ = experts.sigmoid_route(logits, leaves["expert_bias"][0],
                                         4, 1.0)
    without, _ = experts.sigmoid_route(logits, jnp.zeros(32), 4, 1.0)
    moved = np.mean([len(set(a) - set(b)) / 4 for a, b in zip(
        np.asarray(with_bias).tolist(), np.asarray(without).tolist())])
    assert 0.1 < moved < 0.5, moved


def test_causal_conv_with_no_bias_and_no_activation_is_the_explicit_sum():
    k = jax.random.split(jax.random.key(2), 3)
    y = jax.random.normal(k[0], (2, 9, 5), jnp.float32)
    tail = jax.random.normal(k[1], (2, 2, 5), jnp.float32)
    w = jax.random.normal(k[2], (5, 3), jnp.float32)
    z, new = mamba_mixer.causal_conv(y, tail, w, None, None)
    ext = np.concatenate([np.asarray(tail), np.asarray(y)], 1)
    want = sum(np.asarray(w)[:, j] * ext[:, j:j + 9] for j in range(3))
    assert np.abs(np.asarray(z) - want).max() < 1e-6
    assert (np.asarray(new) == ext[:, -2:]).all()
    # a single decode step: the tail slides by one
    z1, new1 = mamba_mixer.causal_conv(y[:, :1], tail, w, None, None)
    assert np.abs(np.asarray(z1) - want[:, :1]).max() < 1e-6
    assert (np.asarray(new1) == ext[:, 1:3]).all()


# ``causal_conv(xbc, tail, w, b)`` of commit 53dbfac on the inputs below
PINNED_MAMBA_CONV = [[[2.6308581829071045, -0.27771636843681335],
                      [-0.13706906139850616, -0.2592622637748718],
                      [5.032526969909668, -0.2777210772037506]]]


def test_causal_conv_at_mambas_call_gives_what_it_gave():
    """Bias, silu, four taps: values pinned from the parent commit's
    ``causal_conv(xbc, tail, w, b)`` on the same seeded inputs."""
    k = jax.random.split(jax.random.key(7), 4)
    xbc = jax.random.normal(k[0], (1, 3, 2), jnp.float32)
    tail = jax.random.normal(k[1], (1, 3, 2), jnp.float32)
    w = jax.random.normal(k[2], (2, 4), jnp.float32)
    b = jax.random.normal(k[3], (2,), jnp.float32)
    out, new = mamba_mixer.causal_conv(xbc, tail, w, b)
    ext = np.concatenate([np.asarray(tail), np.asarray(xbc)], 1)
    acc = np.asarray(b) + sum(np.asarray(w)[:, j] * ext[:, j:j + 3]
                              for j in range(4))
    assert np.abs(np.asarray(out) - acc / (1 + np.exp(-acc))).max() < 1e-6
    assert np.allclose(np.asarray(out), PINNED_MAMBA_CONV, atol=1e-6)
    assert (np.asarray(new) == ext[:, 3:]).all()


def test_the_state_is_three_kinds_side_by_side():
    cfg = lfm2.tiny_lfm2()
    cache = lfm2.init_cache(cfg, 3, 40, quantized=True)
    assert cache["k"].shape == (2, 3, 2, 40, 16)         # 2 attention layers
    assert cache["k"].dtype == jnp.int8 and cache["ks"].shape == (2, 3, 2, 40)
    assert cache["conv"].shape == (8, 3, 2, 64)          # 8 conv layers
    assert cache["conv"].dtype == jnp.float32
    assert cache["expert_tokens"].shape == (8, 8)        # 8 sparse layers
    assert cache["picks"].shape == (8, 3, 2)
    assert "decode_touched" in cache
    full = jax.eval_shape(lambda: lfm2.init_cache(
        lfm2.lfm2_8b_a1b(), 24, 8448, quantized=True))
    assert full["conv"].shape == (18, 24, 2, 2048)
    assert full["conv"].dtype == jnp.bfloat16
    # 8 KV heads of 64, two a lane tile; the scales a head (PR 55)
    assert full["k"].shape == full["v"].shape == (6, 24, 4, 8448, 128)
    assert full["ks"].shape == full["vs"].shape == (6, 24, 8, 8448)
    assert full["expert_tokens"].shape == (22, 32)


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The expert layer is told what it holds (the model-configs guide's
    section 4 test): with ``n_held`` 2 at ``expert_offset`` 0, 2, 4, 6 the
    four routed parts add up to the layer that holds all 8, which is the
    uncut reference's — through the kernels and through ``dense_experts``."""
    cfg, params = tiny
    slot = 1
    lp = jax.tree.map(lambda a: a[slot], {
        n: w for n, w in params["layers"].items()
        if n not in experts.EXPERT_LEAVES})
    x = jax.random.normal(jax.random.key(9), (2, 30, cfg.dim), jnp.float32)
    valid = jnp.ones((2, 30), bool)

    def layer(cfg, offset, fn):
        stacked = {n: params["layers"][n][:, offset:offset + cfg.n_held]
                   for n in experts.EXPERT_LEAVES}
        cache = experts.init_expert_state(cfg.n_sparse, cfg.n_held, 2, 2,
                                          decode_touched=True)
        experts_fn = None if fn is None else (
            lambda *a: experts.grouped_experts(*a, cfg, interpret=True))
        out, cache = lfm2._sparse_ffn(x, lp, stacked, slot, valid, cache,
                                      cfg, experts_fn)
        return out, cache

    with jax.default_matmul_precision("highest"):
        u = reference._rmsnorm(x[0], lp["ffn_norm"], cfg.norm_eps)
        want, _, _ = reference.sparse_ffn(
            u, lp, {n: params["layers"][n] for n in experts.EXPERT_LEAVES},
            slot, _sizes(cfg), jnp.zeros((0, 2), jnp.int32), 0.0)
        for fn in (None, "kernels"):
            whole, counted = layer(cfg, 0, fn)
            parts = [layer(dataclasses.replace(cfg, n_held=2,
                                               expert_offset=offset),
                           offset, fn) for offset in (0, 2, 4, 6)]
            total = sum(out for out, _ in parts)
            assert _rel(total, whole) < 1e-5
            assert _rel(total[0], want) < 1e-5
            assert int(counted["slots_held"]) == 2 * 30 * 2 == sum(
                int(c["slots_held"]) for _, c in parts)
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
        got = lfm2.forward_dense(params, cfg, toks)
    want = jnp.stack([jitted(reference, _sizes(cfg))(params, t)["logits"]
                      for t in toks])
    assert got.shape == (2, 37, cfg.vocab_size)
    assert float(jnp.abs(want).max()) > 0.1
    assert _rel(got, want) < 1e-5


@pytest.fixture(scope="module")
def clean_logits(tiny):
    cfg, params = tiny
    return jitted(reference, _sizes(cfg))(params, _tokens(37)[0])["logits"]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_every_departure_of_the_reference_shows_in_the_logits(
        tiny, clean_logits, fault):
    cfg, params = tiny
    other = jitted(reference, _sizes(cfg), faults=(fault,))(
        params, _tokens(37)[0])["logits"]
    assert _rel(other, clean_logits) > 1e-3


def test_reference_refuses_an_unknown_fault(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="unknown faults"):
        reference.logits(params, _tokens(5)[0], _sizes(cfg), faults=("x",))


def test_reference_is_plain_float32_and_reads_nothing_of_the_program():
    from pathlib import Path

    src = (Path(__file__).resolve().parents[1] / "benchmarks"
           / "reference_lfm2.py").read_text()
    code = src.split('"""', 2)[2]
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in code
    assert "for j in range(K)" in code             # the taps, an explicit sum
    assert "fori_loop(0, held, one_expert" in code   # ONE expert at a time
    for word in ("pallas", "bfloat16", "import vnsum", "from vnsum",
                 "lax.conv"):
        assert word not in code, word
    assert "1e-6" in code and "denominator" not in " ".join(reference.FAULTS)


def test_reference_takes_rightful_picks_inside_the_band_alone():
    ranked = jnp.asarray([[0.9, 0.5, 0.495, 0.1]] * 3)
    theirs = jnp.asarray([[0, 2], [0, 3], [0, 0]])
    took = reference.ties_broken_their_way(ranked, theirs, 0.01)
    assert np.asarray(took).tolist() == [True, False, False]
    assert not reference.ties_broken_their_way(ranked, theirs, 0.0)[0]


def _agrees_with_the_reference(cfg, params, ids, n, bucket, rows, **kw):
    """Logits, the first and last convolution layer's tail position by
    position, every layer's final tail, keys, picks and every expert's
    token count of a prompt of ``n`` tokens in ``bucket`` and
    ``len(ids) - n`` forced tokens, against the reference's one forward."""
    with jax.default_matmul_precision("highest"):
        be, got, state = _through_the_engine(cfg, params, ids, n, bucket,
                                             **kw)
    want = reference_of(reference, _sizes(cfg), params, ids, last=rows)
    assert got.shape == (rows, cfg.vocab_size)
    assert _rel(got, want["logits"]) < 1e-5
    for row in range(rows):
        for which in (0, 1):
            assert _rel(state["rows"]["tail"][row, which, 0],
                        want["tail_rows"][which, row]) < 1e-5
    cache = state["cache"]
    assert _picks_agree(state, want, rows)
    assert _rel(cache["conv"][:, 0], want["conv"]) < 1e-5
    pad, total = bucket - n, len(ids)
    # keys of the attention layers: the prompt's rows end at slot
    # ``bucket``, each forced token's follows
    assert _rel(cache["k"][:, 0, :, pad:pad + total].swapaxes(1, 2),
                want["k"]) < 1e-5
    sparse, k = cfg.n_sparse, cfg.num_experts_per_tok
    assert int(cache["slots_routed"]) == int(cache["slots_held"]) \
        == total * k * sparse
    theirs = np.stack([np.bincount(np.asarray(layer).ravel(),
                                   minlength=cfg.n_routed_experts)
                       for layer in want["ids"]])
    assert (np.asarray(cache["expert_tokens"]) == theirs).all()
    assert int(cache["decode_layer_steps"]) == (rows - 1) * sparse
    return be, got, state, want


@pytest.mark.parametrize("flash", [True, False])
def test_engine_prefill_and_decode_agree_with_the_reference(tiny, flash):
    """The engine's chunked prefill — a left pad of 106 in a bucket of 256,
    two prefill chunks of 128, so the boundary between them falls inside
    the prompt — and then teacher-forced decode steps through the tails,
    the cache and the counters, against the reference's one forward over
    the whole sequence. All kernels interpreted, and the XLA forms."""
    _, params = tiny
    cfg = lfm2.tiny_lfm2(max_seq_len=400)
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    kw = {} if flash else {"flash": False, "interpret": False}
    be, *_ = _agrees_with_the_reference(cfg, params, ids, 150, 256, 6, **kw)
    if flash:
        assert be.stats.attention_paths["logits[B=1,S=256]"] == {
            "prefill": "kernel", "decode": "kernel"}


@pytest.mark.parametrize("pad", [126, 127, 128])
def test_a_chunk_boundary_at_each_tap_offset(tiny, pad):
    """The first real token two before, one before and at the boundary
    between two prefill chunks of 128 — each of the three tap offsets —
    (bucket 384: the tail crosses two boundaries): what the second chunk's
    first positions read of the first chunk is the tail, zero where it lay
    under the pad."""
    _, params = tiny
    cfg = lfm2.tiny_lfm2(max_seq_len=512)
    n = 384 - pad
    ids = np.asarray(_tokens(n + 3, 1, seed=pad))[0].tolist()
    _agrees_with_the_reference(cfg, params, ids, n, 384, 4, flash=False,
                               interpret=False)


@pytest.fixture(scope="module")
def unpadded(tiny):
    """A 56-token prompt and 4 forced tokens through the engine with no
    pad at all, and the reference's forward over the 60."""
    cfg = lfm2.tiny_lfm2(max_seq_len=400)
    _, params = tiny
    ids = np.asarray(_tokens(60, 1, seed=4))[0].tolist()
    _, got, state, want = _agrees_with_the_reference(
        cfg, params, ids, 56, 56, 5)
    return cfg, ids, want, got, state["cache"]


@pytest.mark.parametrize("pad", [1, 2, 127, 170])
def test_pad_length_changes_neither_logits_nor_tail(tiny, unpadded, pad):
    """The same prompt under a left pad of 1, 2 = K - 1, a prefill chunk
    less one and more than a prefill chunk: the tail is exactly zero when
    the first real token arrives and a pad position is routed nowhere, so
    logits, tails and the experts' counts are the unpadded run's and the
    reference's."""
    cfg, ids, want, plain, plain_cache = unpadded
    _, params = tiny
    _, got, state, _ = _agrees_with_the_reference(
        cfg, params, ids, 56, 56 + pad, 5)
    cache = state["cache"]
    assert _rel(got, plain) < 3e-6
    assert _rel(cache["conv"], plain_cache["conv"]) < 3e-6
    assert (cache["expert_tokens"] == plain_cache["expert_tokens"]).all()


def test_the_tail_is_exactly_zero_under_a_pad_of_any_length(tiny):
    """No bias anywhere in the operator, said by a test and not assumed: a
    forward over nothing but pad positions leaves every tail exactly zero,
    with W8A8 products too."""
    from vnsum_tpu.models.quant import quantize_params

    cfg, params = tiny
    toks = _tokens(40, 2)
    positions = jnp.zeros((2, 40), jnp.int32)
    mask = jnp.zeros((2, 40, 40), bool)
    for c, p in ((cfg, params),
                 (dataclasses.replace(cfg, w8a8_prefill=True),
                  quantize_params(params))):
        _, cache = lfm2.forward(p, c, toks, positions,
                                lfm2.init_cache(c, 2, 40), 0, mask)
        assert not np.asarray(cache["conv"]).any()
        assert int(cache["slots_routed"]) == 0


def test_a_tail_kept_a_precision_below_fails_the_tails_tolerance(tiny):
    """The check is tight enough to see a precision cut: with the tail held
    in bfloat16 under float32 activations the tails miss 1e-4 by far."""
    cfg = lfm2.tiny_lfm2(max_seq_len=400, state_dtype=jnp.bfloat16)
    _, params = tiny
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    with jax.default_matmul_precision("highest"):
        _, got, state = _through_the_engine(cfg, params, ids, 150, 256)
    want = reference_of(reference, _sizes(cfg), params, ids, last=6)
    assert state["cache"]["conv"].dtype.name == "bfloat16"
    err = _rel(np.asarray(state["cache"]["conv"][:, 0], np.float32),
               want["conv"])
    assert err > 1e-4, err


# -- row pieces --------------------------------------------------------------------


@pytest.fixture()
def small_pieces(monkeypatch):
    """Row pieces of 256 tokens (two rows of a 128-token chunk) in the
    engine and in the family's own count of them."""
    monkeypatch.setattr(lfm2, "PREFILL_PIECE_TOKENS", 256)
    return 256


@pytest.mark.parametrize("flash", [True, False])
def test_row_pieces_give_what_the_whole_batch_gives(tiny, small_pieces,
                                                    flash):
    """A batch of four rows under pads that put the first real token at
    each tap offset around a chunk boundary, in pieces of two rows and as a
    whole: the same tokens (greedy), the same counters; the piece whose
    rows are all pad in the first chunk is not run."""
    from vnsum_tpu.core.config import GenerationConfig

    cfg, params = tiny
    cfg = dataclasses.replace(cfg, max_seq_len=400)
    prompts = ["a" * 130, "b" * 129, "c" * 128, "d" * 250]
    kw = dict(batch_size=4, max_new_tokens=5,
              generation=GenerationConfig(temperature=0.0))
    if not flash:
        kw.update(flash=False, interpret=False)
    whole = _engine(cfg, params, piece_tokens=10 ** 6, **kw)
    pieces = _engine(cfg, params, piece_tokens=small_pieces, **kw)
    assert pieces._prefill_piece_rows(4, 128) == 2
    assert whole._prefill_piece_rows(4, 128) == 0
    assert whole.generate(prompts, max_new_tokens=5) \
        == pieces.generate(prompts, max_new_tokens=5)
    for name in ("expert_slots_routed", "expert_slots_held",
                 "expert_decode_touched"):
        assert getattr(whole.stats, name) == getattr(pieces.stats, name)
    assert (np.asarray(whole.stats.expert_tokens)
            == np.asarray(pieces.stats.expert_tokens)).all()
    # bucket 256: rows of 128-130 tokens are all pad in no chunk, none dead
    assert pieces.stats.prefill_row_chunks_dead == 0
    short = _engine(cfg, params, piece_tokens=small_pieces, **kw)
    short.generate(["a" * 100, "b" * 90, "c" * 250, "d" * 200],
                   max_new_tokens=5)
    assert short.stats.prefill_row_chunks_dead == 2


# -- the seam --------------------------------------------------------------------


def test_family_resolves_and_names_what_it_lacks():
    fam = family_of(lfm2.tiny_lfm2())
    assert fam is lfm2.FAMILY and fam.name == "lfm2"
    assert set(fam.missing) == {"slot loop", "prefix cache", "mesh",
                                "speculative decoding",
                                "long-context backend"}
    assert fam.prefill_counts and fam.counters and fam.row_record
    assert fam.attention_layers(lfm2.tiny_lfm2()) == 2
    assert fam.attention_layers(lfm2.lfm2_8b_a1b()) == 6
    assert fam.prefill_piece_tokens == lfm2.PREFILL_PIECE_TOKENS == 8192
    # four rows of a 2,048-token chunk a piece; the reduce's four rows whole
    assert lfm2.piece_rows(24, 2048) == 4 and lfm2.piece_rows(4, 2048) == 4
    assert lfm2.piece_rows(1, 2048) == 0


@pytest.mark.parametrize("entry", sorted(lfm2.FAMILY.missing))
def test_family_refuses_by_the_text_of_what_it_lacks(entry):
    with pytest.raises(NotImplementedError) as e:
        lfm2.FAMILY.refuse(entry)
    assert lfm2.FAMILY.missing[entry] in str(e.value)
    assert "lfm2" in str(e.value) and "tail" in lfm2.FAMILY.missing[entry]


@pytest.mark.parametrize("kw", [dict(cache_blocks=8), dict(mesh=object())])
def test_engine_refuses_the_entries_at_construction(tiny, kw):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="lfm2 family"):
        _engine(cfg, params, **kw)


def test_engine_refuses_the_slot_loop_and_speculation(tiny):
    cfg, params = tiny
    be = _engine(cfg, params, batch_size=2)
    with pytest.raises(NotImplementedError, match="slot loop"):
        be._get_seg_fn("slot_seg", 2, 64, 8, be.gen_cfg)
    from vnsum_tpu.backend.long_context import LongContextBackend

    with pytest.raises(NotImplementedError, match="long-context backend"):
        LongContextBackend(model_config=cfg, tokenizer="byte",
                           params=params, interpret=True)


def test_prefill_counts_by_hand(small_pieces):
    """Four rows of a 256 bucket in two chunks of 128, pieces
    of two rows: pads 200 and 130 are all pad in the first chunk (one dead
    piece), pads 6 and 0 are not."""
    cfg = lfm2.tiny_lfm2()
    got = lfm2.prefill_counts(cfg, [6, 200, 0, 130], [(0, 128), (128, 256)])
    real = (250 + 56 + 256 + 126)
    assert got == {"conv_tokens_real": real * 8,
                   "conv_tokens_computed": (2 * 128 + 4 * 128) * 8}
    # one pad short of the chunk's end: nothing dead
    alive = lfm2.prefill_counts(cfg, [6, 200, 0, 127], [(0, 128), (128, 256)])
    assert alive["conv_tokens_computed"] == 8 * 128 * 8
    # no pieces (the whole batch a chunk): every token of every chunk
    whole = lfm2.prefill_counts(cfg, [200], [(0, 128), (128, 256)])
    assert whole == {"conv_tokens_real": 56 * 8,
                     "conv_tokens_computed": 256 * 8}


def test_engine_generates_and_counts_conv_tokens_blocks_and_experts(tiny):
    """``TpuBackend.generate`` with every kernel interpreted: the prefill's
    attention cells counted over the TWO attention layers at 2 query heads
    a KV head, the convolution's tokens over 8 layers beside them in
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
    spans = [(lo, min(S, lo + 128)) for lo in range(0, S, 128)]
    for lo, hi in spans:
        for name, n in prefill_block_classes(
                pad_lens, hi - lo, C, lo, 0, 2, cfg.head_dim).items():
            want[name] += n * 2                       # two attention layers
    real = int((S - np.asarray(pad_lens)).sum())
    want.update(conv_tokens_real=real * 8,
                conv_tokens_computed=2 * S * 8)   # no piece: every token
    assert be.stats.prefill_blocks == want
    st = be.stats
    # every real prompt token and every decode step's token, 2 picks on
    # each of 8 sparse layers, all held
    assert st.expert_slots_routed == st.expert_slots_held
    assert st.expert_slots_routed >= real * 2 * 8
    assert np.asarray(st.expert_tokens).shape == (8, 8)
    assert int(np.asarray(st.expert_tokens).sum()) == st.expert_slots_held
    assert st.expert_decode_layer_steps % 8 == 0
    assert 0 < st.expert_decode_touched <= st.expert_decode_layer_steps * 4
    per_row = be.describe()["state_bytes_per_row"]
    assert set(per_row) >= {"k", "v", "ks", "vs", "conv"}
    assert per_row["conv"] == 8 * 2 * 64 * 4
    assert per_row["k"] == 2 * 2 * cfg.max_seq_len * 16


def test_generate_gives_the_same_rows_alone_and_in_a_batch(tiny):
    """A row's tokens do not hang on its neighbours or its pad: neither the
    tail nor an expert's rows of one row reach another's (greedy, kernels
    interpreted)."""
    both, alone = alone_and_in_a_batch(*tiny)
    assert both == alone
