"""The Brumby family (models/brumby.py, ops/power_retention.py) on the CPU
at a tiny size: three layers, 4 query heads on 2 KV heads of 16 (9 tiles of
phi), retention chunks of 8 — the system's STATE form against the
reference's ATTENTION form."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_setup_brumby as setup
from benchmarks import reference_brumby as reference
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
from vnsum_tpu.models import MODEL_REGISTRY, jitted_init, llama
from vnsum_tpu.models import brumby as bb
from vnsum_tpu.models.family import family_of

_sizes = functools.partial(sizes, setup)


@pytest.fixture(scope="module")
def tiny():
    """The tiny config and its weights, with the query and key products ten
    times the usual draw (under QK-norm their size is the norm's: what is
    sharpened is the gate, whose bias is drawn lower, U[-1, 3], so that a
    token's own decay — 0.27 to 0.95 — shows where U[2, 9] would hide a
    misplaced one behind gates of 0.999)."""
    cfg = bb.tiny_brumby()
    params = jitted_init(bb.init_params, cfg, 0)
    layers = dict(params["layers"])
    layers["b_gate_ret"] = jax.random.uniform(
        jax.random.key(9), layers["b_gate_ret"].shape, jnp.float32, -1.0, 3.0)
    return cfg, dict(params, layers=layers)


def _lay(rows):
    """The engine's ``rows`` record [positions, first | last, 1, KV, ...] as
    the reference lays a state: ([2, positions, KV, n, dv], [.., n])."""
    S, z = setup.as_the_reference_lays_it(
        np.asarray(rows["state"], np.float32)[:, :, 0],
        np.asarray(rows["normaliser"], np.float32)[:, :, 0])
    return S.swapaxes(0, 1), z.swapaxes(0, 1)


# -- the config and the parameters ---------------------------------------------


def test_published_config():
    cfg = bb.brumby_14b()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.intermediate, cfg.vocab_size, cfg.max_seq_len) == (
        5120, 40, 40, 8, 128, 17_408, 151_936, 32_768)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.retention_degree,
            cfg.retention_eps, cfg.retention_chunk_size) == (
        1e6, 1e-6, 2, 1e-6, 256)
    assert not cfg.tie_embeddings and cfg.act == "silu"
    assert cfg.q_per_kv == 5 and cfg.score_scale == 128 ** -0.5
    assert MODEL_REGISTRY["brumby-14b"]() == cfg
    assert MODEL_REGISTRY["tiny-brumby"]() == bb.tiny_brumby()


@pytest.mark.parametrize("kw, text", [
    (dict(n_kv_heads=3), "n_kv_heads must divide"),
    (dict(retention_degree=4), "degree 2"),
    (dict(head_dim=15), "even head_dim"),
])
def test_config_refuses_what_it_cannot_mean(kw, text):
    with pytest.raises(ValueError, match=text):
        bb.tiny_brumby(**kw)


def test_parameters_are_one_stack_with_a_gate_a_kv_head():
    cfg = bb.tiny_brumby()
    p = jax.eval_shape(lambda k: bb.init_params(k, cfg), jax.random.key(0))
    assert p["layers"]["wq"].shape == (3, 64, 4, 16)
    assert p["layers"]["wk"].shape == p["layers"]["wv"].shape == (3, 64, 2, 16)
    assert p["layers"]["wo"].shape == (3, 4, 16, 64)
    assert p["layers"]["w_gate_ret"].shape == (3, 64, 2)
    assert p["layers"]["b_gate_ret"].shape == (3, 2)
    assert p["layers"]["q_norm"].shape == p["layers"]["k_norm"].shape == (3, 16)
    assert p["layers"]["w_gate"].shape == (3, 64, 128)
    assert p["lm_head"].shape == (64, 384)           # untied
    for name in ("w_gate_ret", "b_gate_ret"):
        assert p["layers"][name].dtype == jnp.float32


def test_the_gate_is_drawn_from_a_fast_head_to_a_slow_one():
    leaves = bb.float_leaves(jax.random.key(3), bb.tiny_brumby(n_layers=40))
    b = np.asarray(leaves["layers"]["b_gate_ret"])
    assert 2.0 <= b.min() < 2.5 and 8.5 < b.max() <= 9.0
    # stratified: EVERY layer has a head in each half of [2, 9], its ends
    # within a stratum of 3.5 of the range's
    assert (b.min(1) < 5.5).all() and (b.max(1) > 5.5).all()
    assert not (np.sort(b, 1) == b).all()        # in a random order
    g = 1 / (1 + np.exp(-b))
    assert g.min() < 0.9 and g.max() > 0.9998
    w = np.asarray(leaves["layers"]["w_gate_ret"])
    assert 0.015 < w.std() < 0.025
    for name in ("q_norm", "k_norm"):
        n = np.asarray(leaves["layers"][name], np.float32)
        assert 0.5 <= n.min() < 0.7 and 1.3 < n.max() <= 1.5


def test_int8_keeps_the_gate_in_float32_and_draws_it_the_familys_way():
    from vnsum_tpu.models.quant import (
        dequantize_params,
        init_params_quantized,
        quantize_params,
    )

    cfg = bb.tiny_brumby()
    params = jitted_init(bb.init_params, cfg, 0)
    q = quantize_params(params)
    assert q["layers"]["wq"]["q"].dtype == jnp.int8
    assert q["layers"]["wk"]["s"].shape == (3, 2, 16)
    assert q["lm_head"]["s"].shape == (384,)
    for name in ("w_gate_ret", "b_gate_ret", "q_norm", "k_norm"):
        assert q["layers"][name] is params["layers"][name]
    back = dequantize_params(q)
    assert _rel(back["layers"]["wq"], params["layers"]["wq"]) < 0.01
    direct = init_params_quantized(jax.random.key(5), cfg)
    assert direct["layers"]["w_up"]["q"].shape == (3, 64, 128)
    b = np.asarray(direct["layers"]["b_gate_ret"])
    assert b.min() >= 2.0 and b.max() <= 9.0 and b.std() > 1.0
    assert (b.min(1) < 5.5).all() and (b.max(1) > 5.5).all()
    assert np.asarray(direct["layers"]["q_norm"], np.float32).std() > 0.1
    assert np.all(np.asarray(direct["layers"]["mlp_norm"], np.float32) == 1)


def test_the_state_is_a_matrix_a_kv_head_and_no_keys_and_values():
    cfg = bb.tiny_brumby()
    cache = jax.eval_shape(lambda: bb.init_cache(cfg, 3, 40))
    assert sorted(cache) == ["norm", "ret"]
    assert cache["ret"].shape == (3, 3, 2, 9, 16, 16)
    assert cache["norm"].shape == (3, 3, 2, 16, 16)
    assert cache["ret"].dtype == cache["norm"].dtype == jnp.float32
    # the cache's length sizes nothing
    longer = jax.eval_shape(lambda: bb.init_cache(cfg, 3, 4000))
    assert jax.tree.map(lambda a: a.shape, longer) \
        == jax.tree.map(lambda a: a.shape, cache)
    with pytest.raises(ValueError, match="no int8 form"):
        bb.init_cache(cfg, 1, 8, quantized=True)
    big = bb.brumby_14b()
    row = jax.eval_shape(lambda: bb.init_cache(big, 1, 8448))
    size = sum(a.size * a.dtype.itemsize for a in row.values()) // 40
    # a row's state of one layer is, to the byte, the bfloat16 keys and
    # values of 8,448 slots: this traffic sits at the crossover
    assert size == 34_603_008 == 2 * 8 * 128 * 8448 * 2


# -- the family against the reference --------------------------------------------


def test_cache_free_forward_equals_the_reference(tiny):
    cfg, params = tiny
    toks = _tokens(37)
    with jax.default_matmul_precision("highest"):
        got = bb.forward_dense(params, cfg, toks)
        want = jnp.stack([jitted(reference, _sizes(cfg))(params, t)["logits"]
                          for t in toks])
    assert got.shape == (2, 37, cfg.vocab_size)
    assert _rel(got, want) < 1e-5


def test_int8_weights_stay_within_their_rounding_of_the_reference(tiny):
    """The same tree quantized, through both: the reference multiplies the
    int8 leaves out, the program multiplies by them (W8A16 here)."""
    from vnsum_tpu.models.quant import quantize_params

    cfg, params = tiny
    q = jax.jit(quantize_params)(params)
    toks = _tokens(37)[0]
    with jax.default_matmul_precision("highest"):
        got = bb.forward_dense(q, cfg, toks[None])[0]
        want = jitted(reference, _sizes(cfg))(q, toks)["logits"]
        exact = jitted(reference, _sizes(cfg))(params, toks)["logits"]
    assert _rel(got, want) < 1e-4
    assert 1e-4 < _rel(want, exact) < 0.05      # int8 is a rounding


def _scale_shows(cfg, params):
    """A configuration under which the score's scale is visible: with
    ``retention_eps`` 0.5 the divisor no longer cancels it."""
    return dataclasses.replace(cfg, retention_eps=0.5), params


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_every_departure_of_the_reference_shows_in_the_logits(tiny, fault):
    cfg, params = tiny
    if fault in ("no_scale", "scale_on_both"):
        # under the normaliser s^2 cancels but for eps: raise eps to see it
        cfg, params = _scale_shows(cfg, params)
    toks = _tokens(37)[0]
    with jax.default_matmul_precision("highest"):
        mine = bb.forward_dense(params, cfg, toks[None])[0]
        want = jitted(reference, _sizes(cfg))(params, toks)["logits"]
        other = jitted(reference, _sizes(cfg), faults=(fault,))(
            params, toks)["logits"]
    assert _rel(mine, want) < 1e-5
    assert _rel(other, want) > 3e-3


def test_eps_zero_is_no_fault():
    """On the family's own draw of the gates (the slowest heads keep
    thousands of tokens) the divisor is a sum over every token seen and,
    a few tokens into a sequence, eps is lost in it. (A sequence's very
    first divisors are one or two tokens' squares, which can be anything,
    as a fast head's are under the fixture's sharpened gates: eps shows
    there, and a run scores no such position.)"""
    cfg = bb.tiny_brumby()
    params = jitted_init(bb.init_params, cfg, 0)
    toks = _tokens(60)[0]
    with jax.default_matmul_precision("highest"):
        want = jitted(reference, _sizes(cfg), last=20)(params, toks)["logits"]
        zero = jitted(reference, {**_sizes(cfg), "retention_eps": 0.0},
                      last=20)(params, toks)["logits"]
    # less than any fault moves them (3e-3 and more, above)
    assert _rel(zero, want) < 2e-3


def test_reference_refuses_an_unknown_fault(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="unknown faults"):
        reference.logits(params, _tokens(5)[0], _sizes(cfg), faults=("x",))


@pytest.mark.parametrize("flash", [True, False])
def test_engine_prefill_and_decode_agree_with_the_reference(tiny, flash):
    """The engine's chunked prefill — a left pad of 106 in a bucket of 256,
    two prefill chunks of 128, so the boundary between them falls inside
    the prompt and retention chunks of 8 inside and across it — and then
    teacher-forced decode steps through state and normaliser, against the
    reference's one forward over the whole sequence in the attention form:
    logits, the first and the last layer's state and normaliser after each
    scored position against the reference's sums over tokens. Both kernels
    interpreted, and the XLA forms."""
    _, params = tiny
    cfg = bb.tiny_brumby(max_seq_len=400)
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    kw = {} if flash else {"flash": False, "interpret": False}
    with jax.default_matmul_precision("highest"):
        be, got, state = _through_the_engine(cfg, params, ids, 150, 256, **kw)
        want = reference_of(reference, _sizes(cfg), params, ids, last=6)
        assert _rel(got, want["logits"]) < 1e-5
        assert got.shape == (6, cfg.vocab_size)
        S, z = _lay(state["rows"])
        for which in (0, 1):
            for row in range(6):
                assert _rel(S[which, row], want["state_rows"][which, row]) \
                    < 1e-5
                assert _rel(z[which, row],
                            want["normaliser_rows"][which, row]) < 1e-5
        # ... which are the states of shorter sequences
        short = reference_of(reference, _sizes(cfg), params, ids[:152])
        assert _rel(short["state_rows"][:, 0], want["state_rows"][:, 2]) < 1e-6
    assert sorted(state["cache"]) == ["norm", "ret"]
    if flash:
        assert be.stats.attention_paths["logits[B=1,S=256]"] == {
            "prefill": "kernel", "decode": "kernel"}


@pytest.fixture(scope="module")
def unpadded(tiny):
    """A 56-token prompt and 4 forced tokens through the engine with no
    pad at all, and the reference's forward over the 60."""
    cfg = bb.tiny_brumby(max_seq_len=400)
    _, params = tiny
    ids = np.asarray(_tokens(60, 1, seed=4))[0].tolist()
    with jax.default_matmul_precision("highest"):
        want = reference_of(reference, _sizes(cfg), params, ids, last=5)
        _, got, state = _through_the_engine(cfg, params, ids, 56, 56)
    return cfg, ids, want, got, state["cache"]


# the first real token on either side of a retention-chunk boundary (8) and
# of a prefill-chunk boundary (128), a chunk less one, more than a chunk
@pytest.mark.parametrize("pad", [0, 1, 7, 8, 9, 127, 128, 129, 170])
def test_pad_length_changes_neither_logits_nor_state(tiny, unpadded, pad):
    """The same prompt under a left pad: state and normaliser are exactly
    zero when the first real token arrives, so logits and final state are
    the unpadded run's (to float32's rounding: the pad moves the chunk
    boundaries) and the reference's."""
    cfg, ids, want, plain, plain_cache = unpadded
    _, params = tiny
    with jax.default_matmul_precision("highest"):
        _, got, state = _through_the_engine(cfg, params, ids, 56, 56 + pad)
    cache = state["cache"]
    assert _rel(got, plain) < 3e-6
    assert _rel(cache["ret"], plain_cache["ret"]) < 3e-6
    assert _rel(cache["norm"], plain_cache["norm"]) < 3e-6
    assert _rel(got, want["logits"]) < 1e-5
    S, z = _lay(state["rows"])
    assert _rel(S[:, -1], want["state_rows"][:, -1]) < 1e-5
    assert _rel(z[:, -1], want["normaliser_rows"][:, -1]) < 1e-5


@pytest.mark.parametrize("flash", [True, False])
def test_state_and_outputs_are_exactly_zero_under_a_pad(tiny, flash):
    """A mixer's output, state and normaliser at a position where nothing
    real has been seen: exact zeros, not small numbers."""
    cfg, params = tiny
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    B, S = 2, 24
    h = jax.random.normal(jax.random.key(0), (B, S, cfg.dim))
    pads = jnp.asarray([11, 24])
    valid = jnp.arange(S)[None, :] >= pads[:, None]
    h = jnp.where(valid[..., None], h, 0.0)
    ang = jnp.zeros((B, S, cfg.head_dim // 2))
    out, cache = bb._retention_mixer(
        h, lp, 0, (jnp.cos(ang), jnp.sin(ang)), valid,
        bb.init_cache(cfg, B, S), cfg, flash, flash)
    assert not np.asarray(out)[0, :11].any() and np.asarray(out)[0, 11:].any()
    assert not np.asarray(out)[1].any()
    assert not np.asarray(cache["ret"])[:, 1].any()
    assert not np.asarray(cache["norm"])[:, 1].any()
    assert np.asarray(cache["ret"])[0, 0].any()


def test_a_bf16_state_fails_the_states_tolerance(tiny):
    """The check is tight enough to see a precision cut: with state and
    normaliser held in bfloat16 (rounded after every chunk and step) the
    state misses 1e-4 by far where a float32 one meets 1e-5 (above)."""
    _, params = tiny
    cfg = bb.tiny_brumby(max_seq_len=400, state_dtype=jnp.bfloat16)
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    with jax.default_matmul_precision("highest"):
        _, got, state = _through_the_engine(cfg, params, ids, 150, 256)
        want = reference_of(reference, _sizes(cfg), params, ids, last=6)
    S, _ = _lay(state["rows"])
    err = _rel(S[0, -1], want["state_rows"][0, -1])
    assert err > 1e-4, err
    assert state["cache"]["ret"].dtype.name == "bfloat16"
    assert _rel(got, want["logits"]) > 1e-4


@pytest.mark.parametrize("piece_tokens", [128, 256])
def test_row_pieces_give_the_whole_batchs_rows(tiny, piece_tokens):
    """A prefill chunk run a piece of the rows at a time (one row of a
    128-token chunk; two) against the whole batch at once: the same greedy
    tokens, each piece's state written at its own rows."""
    from vnsum_tpu.core.config import GenerationConfig

    cfg, params = tiny
    gen = GenerationConfig(temperature=0.0)
    prompts = ["xin chào " * 30, "một hai ba", "bốn năm sáu bảy " * 9,
               "tám"]
    kw = dict(batch_size=4, max_new_tokens=6, generation=gen)
    whole = _engine(cfg, params, piece_tokens=10 ** 6, **kw)
    assert whole._prefill_piece_rows(4, 128) == 0
    pieces = _engine(cfg, params, piece_tokens=piece_tokens, **kw)
    assert pieces._prefill_piece_rows(4, 128) == piece_tokens // 128
    assert pieces.generate(prompts, max_new_tokens=6) \
        == whole.generate(prompts, max_new_tokens=6)


# -- the seam --------------------------------------------------------------------


def test_family_resolves_and_names_what_it_lacks():
    cfg = bb.tiny_brumby()
    fam = family_of(cfg)
    assert fam is bb.FAMILY and fam.name == "brumby"
    assert not fam.int8_cache and not fam.counts_prefill_blocks
    assert fam.attention_layers(cfg) == 0
    assert fam.attention_layers(bb.brumby_14b()) == 0
    assert fam.prefill_attention is None and fam.decode_attention is None
    assert fam.layer_windows(cfg) is None and fam.layer_groups(cfg) is None
    assert fam.counters is None and fam.row_record is bb.row_record
    assert fam.prefill_piece_tokens == 2048
    assert set(fam.missing) == {"slot loop", "prefix cache", "mesh",
                                "speculative decoding",
                                "long-context backend"}
    assert llama.FAMILY.attention_layers(llama.tiny_llama()) == 2


@pytest.mark.parametrize("entry", sorted(bb.FAMILY.missing))
def test_family_refuses_by_the_text_of_what_it_lacks(entry):
    with pytest.raises(NotImplementedError) as e:
        bb.FAMILY.refuse(entry)
    assert "brumby" in str(e.value) and entry in str(e.value)
    assert bb.FAMILY.missing[entry] in str(e.value)
    assert len(bb.FAMILY.missing[entry]) > 60   # says what, not just no
    assert "state" in bb.FAMILY.missing[entry]  # by mechanism
    assert ".py" in bb.FAMILY.missing[entry]    # and by module


@pytest.mark.parametrize("kw", [dict(cache_blocks=8), dict(mesh=object())])
def test_engine_refuses_the_entries_at_construction(tiny, kw):
    from vnsum_tpu.backend.engine import TpuBackend

    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="brumby"):
        TpuBackend(model_config=cfg, params=params, interpret=True, **kw)


def test_a_family_without_attention_layers_builds_no_kv_cache_and_no_prefix_cache(
        tiny):
    """Not by the family's name: an engine whose family says 0 attention
    layers asks it for no attention function in either phase (this family
    has none to give), carries the family's state alone, and refuses a
    prefix cache even where the family's ``missing`` does not."""
    from vnsum_tpu.backend.engine import TpuBackend

    cfg, params = tiny
    be = _engine(cfg, params, batch_size=2, max_new_tokens=4, fresh=True)
    assert be._attends is False and be.prefix_cache is None
    assert be.quantize_kv is False
    assert be._prefill_stacked(True, None, None) is None
    assert sorted(jax.eval_shape(lambda: be._init_cache(2, 64))) == [
        "norm", "ret"]
    assert sorted(be.describe()["state_bytes_per_row"]) == ["norm", "ret"]
    assert len(be.generate(["xin chào", "một hai ba"], max_new_tokens=4)) == 2
    # the engine's own refusal, where a family forgot to name the entry
    open_family = dataclasses.replace(
        bb.FAMILY, missing={k: v for k, v in bb.FAMILY.missing.items()
                            if k != "prefix cache"})
    import vnsum_tpu.backend.engine as engine_module

    real = engine_module.family_of
    engine_module.family_of = lambda cfg: open_family
    try:
        with pytest.raises(ValueError, match="no layer of this configuration"):
            TpuBackend(model_config=cfg, params=params, interpret=True,
                       cache_blocks=8)
    finally:
        engine_module.family_of = real
    # a family whose layers attend is asked as before
    dense = _engine(llama.tiny_llama(), None, batch_size=1, fresh=True)
    assert dense._attends is True


def test_forward_takes_no_attention_function(tiny):
    cfg, params = tiny
    toks = _tokens(8)
    with pytest.raises(ValueError, match="no layer of this family attends"):
        bb.forward(params, cfg, toks, jnp.zeros_like(toks),
                   bb.init_cache(cfg, 2, 8), 0, jnp.ones((2, 8, 1), bool),
                   stacked_attention_fn=lambda *a: None)


def test_prefill_counts_are_the_kernels_rule_from_the_pads():
    cfg = bb.tiny_brumby()
    # a bucket of 256 in two chunks of 128, retention chunks of 8: a row
    # with 3 pads skips nothing, one with 130 skips the first chunk and 0 of
    # the second's first retention chunk, a filler row (256 pads) everything
    got = bb.prefill_counts(cfg, [3, 130, 256], [(0, 128), (128, 256)])
    assert got == {"retention_tokens_real": (253 + 126) * 3,
                   "retention_tokens_computed": (256 + 128) * 3}
    got = bb.prefill_counts(cfg, [17], [(0, 64)])
    assert got == {"retention_tokens_real": 47 * 3,
                   "retention_tokens_computed": 48 * 3}


def test_engine_generates_and_counts_its_scan(tiny):
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
    real = int((S - np.asarray(pad_lens)).sum())
    want = bb.prefill_counts(
        cfg, pad_lens, [(lo, min(S, lo + 128)) for lo in range(0, S, 128)])
    assert be.stats.prefill_blocks == want
    assert want["retention_tokens_real"] == real * 3
    assert want["retention_tokens_real"] <= want["retention_tokens_computed"]
    per_row = be.describe()["state_bytes_per_row"]
    assert per_row == {"ret": 3 * 2 * 9 * 16 * 16 * 4,
                       "norm": 3 * 2 * 16 * 16 * 4}
    assert be.stats.decode_kv_blocks_total == 0


def test_generate_gives_the_same_rows_alone_and_in_a_batch(tiny):
    """A row's tokens do not hang on its neighbours or its pad: the state
    of one row never reaches another's (greedy, kernels interpreted)."""
    both, alone = alone_and_in_a_batch(*tiny)
    assert both == alone


@pytest.mark.parametrize("flash", [True, False])
def test_the_one_shot_program_names_the_familys_scopes(tiny, flash):
    """``ret_in``, ``ret_scan`` / ``ret_update`` and ``ret_out`` beside the
    feed-forward's and the head's: what ``scripts/trace_by_scope.py`` books
    this family by."""
    cfg, params = tiny
    kw = {} if flash else dict(flash=False, interpret=False)
    be = _engine(cfg, params, batch_size=2, max_new_tokens=4, fresh=True,
                 **kw)
    be._get_fn(2, 64, 4, be.gen_cfg)
    (m,) = be.scope_maps()
    got = {"/".join(p.split("/")[:2]) for p in m["scopes"].values()}
    assert {f"prefill/{c}" for c in ("ret_in", "ret_scan", "ret_out", "mlp",
                                     "lm_head", "embed")} <= got
    assert {f"decode/{c}" for c in ("ret_in", "ret_update", "ret_out", "mlp",
                                    "lm_head", "embed")} <= got
    assert "prefill/ret_update" not in got and "decode/ret_scan" not in got
    assert not {p for p in got if p.split("/")[-1] in (
        "qkv", "kv_write", "attn", "attn_out")}
