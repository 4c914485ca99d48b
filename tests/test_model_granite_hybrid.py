"""The Granite-4.0-H family (models/granite_hybrid.py, ops/ssd_scan.py) on
the CPU at a tiny size: two periods of [5 Mamba, attention, 4 Mamba], 4
query heads of 16 over 2 KV heads, 8 Mamba heads of 16, a state of 16, scan
chunks of 8."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_setup_granite_h as setup
from benchmarks import reference_granite_h as reference
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
from vnsum_tpu.models import granite_hybrid as gh
from vnsum_tpu.models.family import family_of
from vnsum_tpu.ops import ssd_scan


_sizes = functools.partial(sizes, setup)


@pytest.fixture(scope="module")
def tiny():
    """The tiny config and its weights, with the query and key products
    thirty times the usual draw: under ``attention_multiplier`` = 1/64 the
    scores of a 0.02-normal draw are flat to 1e-3 and no fault of the
    attention (a rotary, another scale) would show in the logits."""
    cfg = gh.tiny_granite_h()
    params = jitted_init(gh.init_params, cfg, 0)
    attn = dict(params["attn"], wq=params["attn"]["wq"] * 30.0,
                wk=params["attn"]["wk"] * 30.0)
    return cfg, dict(params, attn=attn)


# -- the config and the parameters ---------------------------------------------


def test_published_config_and_its_period():
    cfg = gh.granite_4_0_h_micro()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.intermediate, cfg.vocab_size, cfg.max_seq_len) == (
        2048, 40, 32, 8, 64, 8192, 100_352, 131_072)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_d_conv, cfg.mamba_chunk_size) == (
        64, 64, 128, 1, 4, 256)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attention_multiplier, cfg.norm_eps) == (
        12.0, 0.22, 8.0, 0.015625, 1e-5)
    assert cfg.tie_embeddings and cfg.act == "silu"
    assert cfg.d_inner == 4096 and cfg.conv_dim == 4352
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] \
        == [5, 15, 25, 35]
    assert (cfg.n_mamba, cfg.n_attention, len(cfg.period)) == (36, 4, 10)
    assert MODEL_REGISTRY["granite-4.0-h-micro"]() == cfg
    assert MODEL_REGISTRY["tiny-granite-h"]() == gh.tiny_granite_h()


@pytest.mark.parametrize("kw, text", [
    (dict(layer_types=("mamba", "attention")), "layer_types needs 20"),
    (dict(layer_types=("mamba",) * 19 + ("moe",)), "layer_types needs 20"),
    (dict(n_kv_heads=3), "n_kv_heads must divide"),
    (dict(mamba_n_groups=2), "one group of B and C"),
])
def test_config_refuses_what_it_cannot_mean(kw, text):
    with pytest.raises(ValueError, match=text):
        gh.tiny_granite_h(**kw)


def test_parameters_are_stacked_by_kind_with_one_feed_forward_a_layer():
    cfg = gh.tiny_granite_h()
    p = jax.eval_shape(lambda k: gh.init_params(k, cfg), jax.random.key(0))
    assert p["mamba"]["in_z"].shape == (18, 64, 128)
    assert p["mamba"]["in_xbc"].shape == (18, 64, 128 + 2 * 16)
    assert p["mamba"]["in_dt"].shape == (18, 64, 8)
    assert p["mamba"]["out_proj"].shape == (18, 128, 64)
    assert p["mamba"]["conv_w"].shape == (18, 160, 4)
    assert p["mamba"]["ssm_norm"].shape == (18, 128)
    assert p["attn"]["wq"].shape == (2, 64, 4, 16)
    assert p["layers"]["w_gate"].shape == (20, 64, 128)
    assert "lm_head" not in p            # the embedding is the head
    for name in gh.MAMBA_VECTORS:
        assert p["mamba"][name].dtype == jnp.float32


def test_the_scans_vectors_are_drawn_as_mamba2_publishes_them():
    v = gh.init_mamba_vectors(jax.random.key(3), gh.tiny_granite_h())
    A = np.exp(np.asarray(v["A_log"]))
    dt = np.asarray(jax.nn.softplus(v["dt_bias"]))
    assert 1.0 <= A.min() and A.max() <= 16.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert np.all(np.asarray(v["D"]) == 1.0)
    assert np.abs(np.asarray(v["conv_w"])).max() <= 0.5


def test_int8_keeps_the_scans_vectors_in_float32_and_ties_the_head():
    from vnsum_tpu.models.quant import (
        dequantize_params,
        init_params_quantized,
        quantize_params,
    )

    cfg = gh.tiny_granite_h()
    params = jitted_init(gh.init_params, cfg, 0)
    q = quantize_params(params)
    assert q["mamba"]["in_xbc"]["q"].dtype == jnp.int8
    assert q["mamba"]["in_xbc"]["s"].shape == (18, 160)
    assert q["mamba"]["in_dt"]["s"].shape == (18, 8)
    assert q["mamba"]["out_proj"]["s"].shape == (18, 64)
    assert q["embed"]["s"].shape == (384,) and "lm_head" not in q
    for name in gh.MAMBA_VECTORS:
        assert q["mamba"][name].dtype == jnp.float32
        assert q["mamba"][name] is params["mamba"][name]
    back = dequantize_params(q)
    assert _rel(back["mamba"]["in_z"], params["mamba"]["in_z"]) < 0.01
    # the direct int8 init draws the vectors the family's way, not ones
    direct = init_params_quantized(jax.random.key(5), cfg)
    assert direct["mamba"]["in_z"]["q"].shape == (18, 64, 128)
    A = np.exp(np.asarray(direct["mamba"]["A_log"]))
    assert A.min() >= 1.0 and A.max() <= 16.0 and A.std() > 1.0
    assert direct["attn"]["wq"]["q"].dtype == jnp.int8


def test_the_state_is_two_kinds_side_by_side():
    cfg = gh.tiny_granite_h()
    cache = jax.eval_shape(lambda: gh.init_cache(cfg, 3, 40, quantized=True))
    assert cache["k"].shape == (2, 3, 2, 40, 16) and cache["k"].dtype == jnp.int8
    assert cache["ks"].shape == (2, 3, 2, 40)
    assert cache["conv"].shape == (18, 3, 3, 160)
    assert cache["ssm"].shape == (18, 3, 16, 128)
    assert cache["ssm"].dtype == jnp.float32
    big = gh.granite_4_0_h_micro()
    row = jax.eval_shape(lambda: gh.init_cache(big, 1, 8448, quantized=True))
    size = {n: a.size * a.dtype.itemsize for n, a in row.items()}
    kv = sum(size[n] for n in ("k", "v", "ks", "vs"))
    assert kv == 4 * 8 * 8448 * (2 * 64 + 8)
    assert size["conv"] == 36 * 3 * 4352 * 2
    # the state that does not grow is twice the one that does, at 8,448
    assert size["ssm"] == 36 * 2_097_152 > 2 * kv


# -- the equations by hand -------------------------------------------------------


def test_the_convolution_at_a_rows_first_three_tokens():
    """Zeros stand before the row's first token: token 0 sees one tap,
    token 1 two, token 2 three, token 3 all four."""
    x = jnp.asarray([[[1.0], [2.0], [3.0], [4.0]]])          # [1, 4, 1]
    w = jnp.asarray([[0.5, -1.0, 2.0, 3.0]])                  # [1, K]
    b = jnp.asarray([0.25])
    out, tail = gh.causal_conv(x, jnp.zeros((1, 3, 1)), w, b)
    pre = np.asarray([0.25 + 3 * 1,
                      0.25 + 2 * 1 + 3 * 2,
                      0.25 - 1 * 1 + 2 * 2 + 3 * 3,
                      0.25 + 0.5 * 1 - 1 * 2 + 2 * 3 + 3 * 4])
    np.testing.assert_allclose(np.asarray(out)[0, :, 0],
                               pre / (1 + np.exp(-pre)), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail)[0, :, 0], [2.0, 3.0, 4.0])
    # and continued from that tail, the fifth token sees tokens 2-5
    out2, _ = gh.causal_conv(jnp.asarray([[[5.0]]]), tail, w, b)
    pre5 = 0.25 + 0.5 * 2 - 1 * 3 + 2 * 4 + 3 * 5
    np.testing.assert_allclose(float(out2[0, 0, 0]),
                               pre5 / (1 + np.exp(-pre5)), rtol=1e-6)


@pytest.mark.parametrize("form", ["chunked", "step", "kernel"])
def test_dt_a_and_d_on_a_two_token_row(form):
    """One head of one channel, a state of one: H1 = dt1 x1 b1,
    y1 = H1 c1 + D x1; H2 = exp(dt2 A) H1 + dt2 x2 b2, y2 = H2 c2 + D x2."""
    x1, x2, b1, b2, c1, c2 = 2.0, -1.0, 0.5, 3.0, 1.5, -2.0
    dt1, dt2, A, D = 0.1, 0.7, -4.0, 0.3
    h1 = dt1 * x1 * b1
    h2 = np.exp(dt2 * A) * h1 + dt2 * x2 * b2
    want = [h1 * c1 + D * x1, h2 * c2 + D * x2]
    x = jnp.asarray([x1, x2]).reshape(1, 2, 1, 1)
    dt = jnp.asarray([dt1, dt2]).reshape(1, 2, 1)
    Bm = jnp.asarray([b1, b2]).reshape(1, 2, 1)
    Cm = jnp.asarray([c1, c2]).reshape(1, 2, 1)
    Av, Dv, zero = jnp.asarray([A]), jnp.asarray([D]), jnp.zeros((1, 1, 1))
    if form == "chunked":
        y, h = ssd_scan.ssd_chunked_xla(x, dt, Av, Bm, Cm, Dv, zero, chunk=8)
        y = y[0, :, 0, 0]
    elif form == "kernel":
        y, h = ssd_scan.ssd_prefill_scan(
            x, dt, Av, Bm, Cm, Dv, zero[None], 0, jnp.zeros((1,), jnp.int32),
            chunk=8, interpret=True)
        y, h = y[0, :, 0, 0], h[0]
    else:
        ys, h = [], zero
        for t in range(2):
            yt, h = ssd_scan.ssm_step_xla(x[:, t], dt[:, t], Av, Bm[:, t],
                                          Cm[:, t], Dv, h)
            ys.append(yt[0, 0, 0])
        y = jnp.stack(ys)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-6)
    np.testing.assert_allclose(float(np.asarray(h).ravel()[0]), h2, rtol=1e-6)


# -- the scan's forms against each other -----------------------------------------


def _scan_case(seed=0, rows=3, S=20, H=8, P=16, N=16):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (rows, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, S, H)))
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(k[3], (rows, S, N))
    Cm = jax.random.normal(k[4], (rows, S, N))
    state = jax.random.normal(k[5], (2, rows, N, H * P))
    return x, dt, A, Bm, Cm, jnp.linspace(0.5, 1.5, H), state


def _token_by_token(x, dt, A, Bm, Cm, D, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssd_scan.ssm_step_xla(x[:, t], dt[:, t], A, Bm[:, t],
                                         Cm[:, t], D, state)
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("S", [8, 20, 24])
def test_chunked_forms_equal_the_recurrence_with_a_state_coming_in(S):
    """``ssd_chunked_xla`` and the interpreted ``ssd_prefill_scan`` against
    one token at a time, ``H_in`` non-zero, S a whole number of chunks and
    not; the other layer of the stacked state is not touched."""
    x, dt, A, Bm, Cm, D, state = _scan_case(S=S)
    with jax.default_matmul_precision("highest"):
        want_y, want_h = _token_by_token(x, dt, A, Bm, Cm, D, state[1])
        y, h = ssd_scan.ssd_chunked_xla(x, dt, A, Bm, Cm, D, state[1], 8)
        ky, kh = ssd_scan.ssd_prefill_scan(
            x, dt, A, Bm, Cm, D, state, 1, jnp.zeros((3,), jnp.int32),
            chunk=8, interpret=True)
    assert _rel(y, want_y) < 1e-5 and _rel(h, want_h) < 1e-5
    assert _rel(ky, want_y) < 1e-5 and _rel(kh[1], want_h) < 1e-5
    np.testing.assert_array_equal(np.asarray(kh[0]), np.asarray(state[0]))


def test_prefill_kernel_skips_whole_chunks_of_pads_and_keeps_zero():
    """Rows with 0, 9 and 17 pads of 20 tokens in chunks of 8: pad chunks
    read as zeros and leave a zero state zero; what follows equals the
    recurrence over the row's real tokens alone."""
    x, dt, A, Bm, Cm, D, state = _scan_case(S=20)
    pads = jnp.asarray([0, 9, 17])
    valid = jnp.arange(20)[None, :] >= pads[:, None]
    x = x * valid[..., None, None]
    zero = jnp.zeros_like(state)
    with jax.default_matmul_precision("highest"):
        want_y, want_h = _token_by_token(x, dt, A, Bm, Cm, D, zero[0])
        y, h = ssd_scan.ssd_prefill_scan(
            x, dt, A, Bm, Cm, D, zero, 0, pads, chunk=8, interpret=True)
    assert _rel(y, want_y) < 1e-5 and _rel(h[0], want_h) < 1e-5
    assert not np.asarray(y)[1, :8].any() and not np.asarray(y)[2, :16].any()
    assert ssd_scan.scan_tokens_computed(np.asarray(pads), 20, 8) \
        == 24 + 16 + 8
    # a filler row of whole chunks computes nothing
    assert ssd_scan.scan_tokens_computed([24], 24, 8) == 0


@pytest.mark.parametrize("rows", [[3, 0, 4, 1, 2], [4, 1], [2]],
                         ids=["permutation", "subset", "one-row"])
@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_a_row_piece_writes_its_rows_of_the_state_and_no_other(form, rows):
    """``rows`` names, for each row of the inputs, the state's batch row it
    continues (``ssd_prefill_scan``: of the stacked state's layer, in
    place; ``ssd_chunked_xla``: of one layer's state): those rows read what
    the same inputs give one row at a time, every other row — and the other
    layer of the stack — is bit-equal to what came in."""
    n = len(rows)
    x, dt, A, Bm, Cm, D, _ = _scan_case(seed=3, rows=n, S=20)
    state = jax.random.normal(jax.random.key(7), (2, 5, 16, 8 * 16))
    idx = jnp.asarray(rows, jnp.int32)
    # pads go by the piece's own rows, not the state's; a row behind a pad
    # starts from zeros, as the engine's rows do
    pads = jnp.asarray([0, 9, 17, 3, 8][:n], jnp.int32)
    x = x * (jnp.arange(20)[None, :] >= pads[:, None])[..., None, None]
    state = state.at[:, idx].multiply((pads == 0)[:, None, None])
    with jax.default_matmul_precision("highest"):
        want_y, want_h = _token_by_token(x, dt, A, Bm, Cm, D, state[1][idx])
        if form == "kernel":
            y, h = ssd_scan.ssd_prefill_scan(
                x, dt, A, Bm, Cm, D, state, 1, pads, idx, chunk=8,
                interpret=True)
            np.testing.assert_array_equal(np.asarray(h[0]),
                                          np.asarray(state[0]))
            h = h[1]
        else:
            y, h = ssd_scan.ssd_chunked_xla(x, dt, A, Bm, Cm, D, state[1], 8,
                                            idx)
    assert h.shape == state[1].shape
    # a row's whole chunks of pad read as zeros in the kernel and are
    # computed from zeroed inputs in the XLA form: the same numbers
    assert _rel(y, want_y) < 1e-5 and _rel(h[idx], want_h) < 1e-5
    others = [r for r in range(5) if r not in rows]
    np.testing.assert_array_equal(np.asarray(h)[others],
                                  np.asarray(state[1])[others])


def test_without_rows_the_scan_traces_to_the_form_it_had():
    """``rows=None`` is the call every program made before a piece existed
    (the text of its jaxpr hashed on PR 50's tree): the state operand 9
    aliased to output 1. With rows the state is operand 10, behind a third
    prefetched vector, and the kernel's body is the same."""
    import hashlib

    x, dt, A, Bm, Cm, D, state = _scan_case(S=16)
    pads = jnp.zeros((3,), jnp.int32)

    def text(*rows):
        return str(jax.make_jaxpr(lambda *a: ssd_scan.ssd_prefill_scan(
            *a, chunk=8, interpret=True))(
                x, dt, A, Bm, Cm, D, state, 1, pads, *rows))

    whole, piece = text(), text(jnp.arange(3, dtype=jnp.int32))
    assert hashlib.sha256(whole.encode()).hexdigest()[:16] \
        == "8d964679783ef578"
    assert whole == text(None)
    assert "input_output_aliases=((9, 1),)" in whole
    assert "input_output_aliases=((10, 1),)" in piece
    assert len(piece.split("\n")) == len(whole.split("\n"))
    # the tiny Granite and Nemotron-H one-shot programs, whose chunks hold
    # fewer tokens than a piece, are pinned whole in
    # tests/test_one_shot_programs_pinned.py


def test_decode_kernel_equals_its_xla_form_and_writes_one_layer():
    x, dt, A, Bm, Cm, D, state = _scan_case()
    args = (x[:, 5], dt[:, 5], A, Bm[:, 5], Cm[:, 5], D)
    want_y, want_h = ssd_scan.ssm_step_xla(*args, state[1])
    y, h = ssd_scan.ssm_decode_update(*args, state, 1, interpret=True)
    assert _rel(y, want_y) < 1e-6 and _rel(h[1], want_h) < 1e-6
    np.testing.assert_array_equal(np.asarray(h[0]), np.asarray(state[0]))


# -- the family against the reference --------------------------------------------


def test_cache_free_forward_equals_the_reference(tiny):
    cfg, params = tiny
    toks = _tokens(37)
    with jax.default_matmul_precision("highest"):
        got = gh.forward_dense(params, cfg, toks)
        want = jnp.stack([jitted(reference, _sizes(cfg))(params, t)["logits"]
                          for t in toks])
    assert got.shape == (2, 37, cfg.vocab_size)
    assert _rel(got, want) < 1e-5


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_every_departure_of_the_reference_shows_in_the_logits(tiny, fault):
    cfg, params = tiny
    toks = _tokens(37)[0]
    with jax.default_matmul_precision("highest"):
        want = jitted(reference, _sizes(cfg))(params, toks)["logits"]
        other = jitted(reference, _sizes(cfg), faults=(fault,))(
            params, toks)["logits"]
    assert _rel(other, want) > 1e-3


def test_reference_refuses_an_unknown_fault(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="unknown faults"):
        reference.logits(params, _tokens(5)[0], _sizes(cfg), faults=("x",))


@pytest.mark.parametrize("flash", [True, False])
def test_engine_prefill_and_decode_agree_with_the_reference(tiny, flash):
    """The engine's chunked prefill — a left pad of 106 in a bucket of 256,
    two prefill chunks of 128, so the boundary between them falls inside
    the prompt and scan chunks of 8 inside and across it — and then
    teacher-forced decode steps through the state, against the reference's
    one forward over the whole sequence: logits, the last Mamba layer's
    state after each scored position, the convolution tails. Both scan
    kernels and both attention kernels interpreted, and the XLA forms."""
    cfg, params = tiny
    cfg = gh.tiny_granite_h(max_seq_len=400)
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    kw = {} if flash else {"flash": False, "interpret": False}
    with jax.default_matmul_precision("highest"):
        be, got, state = _through_the_engine(cfg, params, ids, 150, 256, **kw)
        sizes = _sizes(cfg)
        want = reference_of(reference, sizes, params, ids, last=6)
        assert _rel(got, want["logits"]) < 1e-5
        assert got.shape == (6, cfg.vocab_size)
        # the first and the last Mamba layer's state after the prompt and
        # after each forced token: [rows, 2, 1, ...] against [2, rows, ...]
        lay = reference.state_as_the_program_lays_it
        for row in range(6):
            for which in (0, 1):
                assert _rel(state["rows"][row, which, 0],
                            lay(want["ssm_rows"][which, row])) < 1e-5
        # ... which are the states of shorter sequences
        short = reference_of(reference, sizes, params, ids[:152])
        assert _rel(short["ssm_rows"][:, 0], want["ssm_rows"][:, 2]) < 1e-6
    assert _rel(state["cache"]["ssm"][:, 0],
                reference.state_as_the_program_lays_it(want["ssm"])) < 1e-5
    assert _rel(state["cache"]["conv"][:, 0], want["conv"]) < 1e-5
    if flash:
        assert be.stats.attention_paths["logits[B=1,S=256]"] == {
            "prefill": "kernel", "decode": "kernel"}


@pytest.fixture(scope="module")
def unpadded(tiny):
    """A 56-token prompt and 4 forced tokens through the engine with no
    pad at all, and the reference's forward over the 60."""
    cfg = gh.tiny_granite_h(max_seq_len=400)
    _, params = tiny
    ids = np.asarray(_tokens(60, 1, seed=4))[0].tolist()
    with jax.default_matmul_precision("highest"):
        want = reference_of(reference, _sizes(cfg), params, ids, last=5)
        _, got, state = _through_the_engine(cfg, params, ids, 56, 56)
    return cfg, ids, want, got, state["cache"]


@pytest.mark.parametrize("pad", [0, 1, 127, 170])
def test_pad_length_changes_neither_logits_nor_state(tiny, unpadded, pad):
    """The same prompt under a left pad of 0, 1, a prefill chunk minus one
    and more than a prefill chunk: the state is zero when the first real
    token arrives, so logits and final state are the unpadded run's (to
    float32's rounding: the pad moves the scan's chunk boundaries) and the
    reference's."""
    cfg, ids, want, plain, plain_cache = unpadded
    _, params = tiny
    with jax.default_matmul_precision("highest"):
        _, got, state = _through_the_engine(cfg, params, ids, 56, 56 + pad)
    cache = state["cache"]
    assert _rel(got, plain) < 3e-6
    assert _rel(cache["ssm"], plain_cache["ssm"]) < 3e-6
    assert _rel(cache["conv"], plain_cache["conv"]) < 3e-6
    assert _rel(got, want["logits"]) < 1e-5
    assert _rel(cache["ssm"][:, 0],
                reference.state_as_the_program_lays_it(want["ssm"])) < 1e-5
    assert _rel(cache["conv"][:, 0], want["conv"]) < 1e-5


def test_a_bf16_state_fails_the_states_tolerance(tiny):
    """The check is tight enough to see a precision cut: with the recurrent
    state held in bfloat16 the final state misses 1e-4 by far, and a float32
    state meets 1e-5 (the tests above)."""
    cfg = gh.tiny_granite_h(max_seq_len=400, state_dtype=jnp.bfloat16)
    _, params = tiny
    ids = np.asarray(_tokens(155, 1, seed=8))[0].tolist()
    with jax.default_matmul_precision("highest"):
        _, got, state = _through_the_engine(cfg, params, ids, 150, 256)
        want = reference_of(reference, _sizes(cfg), params, ids, last=6)
    err = _rel(np.asarray(state["cache"]["ssm"][:, 0], np.float32),
               reference.state_as_the_program_lays_it(want["ssm"]))
    assert err > 1e-4, err
    assert state["cache"]["ssm"].dtype.name == "bfloat16"


# -- the seam --------------------------------------------------------------------


def test_family_resolves_and_names_what_it_lacks():
    cfg = gh.tiny_granite_h()
    fam = family_of(cfg)
    assert fam is gh.FAMILY and fam.name == "granite-hybrid"
    assert fam.int8_cache and fam.counts_prefill_blocks
    assert fam.attention_layers(cfg) == 2          # not 20
    assert fam.layer_windows(cfg) is None and fam.layer_groups(cfg) is None
    assert fam.counters is None and fam.row_record is gh.last_state
    assert set(fam.missing) == {"slot loop", "prefix cache", "mesh",
                                "speculative decoding",
                                "long-context backend"}
    # the families whose every layer attends say so by default
    assert llama.FAMILY.attention_layers(llama.tiny_llama()) == 2
    # and count nothing of their own for a plain stack (a looped one: PR 50)
    assert llama.FAMILY.prefill_counts(
        llama.tiny_llama(), [0], [(0, 8)], 16) == {}


@pytest.mark.parametrize("entry", sorted(gh.FAMILY.missing))
def test_family_refuses_by_the_text_of_what_it_lacks(entry):
    with pytest.raises(NotImplementedError) as e:
        gh.FAMILY.refuse(entry)
    assert "granite-hybrid" in str(e.value) and entry in str(e.value)
    assert gh.FAMILY.missing[entry] in str(e.value)
    assert len(gh.FAMILY.missing[entry]) > 60   # says what, not just no
    assert "state" in gh.FAMILY.missing[entry]  # by mechanism


@pytest.mark.parametrize("kw", [dict(cache_blocks=8), dict(mesh=object())])
def test_engine_refuses_the_entries_at_construction(tiny, kw):
    from vnsum_tpu.backend.engine import TpuBackend

    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="granite-hybrid"):
        TpuBackend(model_config=cfg, params=params, interpret=True, **kw)


def test_prefill_counts_are_the_kernels_rule_from_the_pads():
    cfg = gh.tiny_granite_h()
    # a bucket of 256 in two chunks of 128, scan chunks of 8: a row with 3
    # pads skips nothing, one with 130 skips the first chunk and 0 of 2's
    # first scan chunk, a filler row (256 pads) everything
    got = gh.prefill_counts(cfg, [3, 130, 256], [(0, 128), (128, 256)])
    assert got == {"scan_tokens_real": (253 + 126) * 18,
                   "scan_tokens_computed": (256 + 128) * 18}
    got = gh.prefill_counts(cfg, [17], [(0, 64)])
    assert got == {"scan_tokens_real": 47 * 18,
                   "scan_tokens_computed": 48 * 18}


def test_engine_generates_and_counts_its_scan_and_its_attention_cells(tiny):
    """``TpuBackend.generate`` with all four kernels interpreted: the
    prefill's attention cells counted over the 2 attention layers (not the
    20), the scan's tokens beside them in ``prefill_blocks``, the state's
    bytes a row in ``describe()``."""
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
                pad_lens, min(128, S - lo), C, lo, 0, 2, cfg.head_dim).items():
            want[name] += n * 2
    real = int((S - np.asarray(pad_lens)).sum())
    want.update(gh.prefill_counts(
        cfg, pad_lens, [(lo, min(S, lo + 128)) for lo in range(0, S, 128)]))
    assert be.stats.prefill_blocks == want
    assert want["scan_tokens_real"] == real * 18
    assert want["scan_tokens_real"] <= want["scan_tokens_computed"] \
        < want["scan_tokens_real"] + 18 * 8 * B * -(-S // 128)
    per_row = be.describe()["state_bytes_per_row"]
    assert per_row["ssm"] == 18 * 16 * 128 * 4
    assert per_row["conv"] == 18 * 3 * 160 * 4
    assert per_row["k"] == 2 * 2 * cfg.max_seq_len * 16


def test_generate_gives_the_same_rows_alone_and_in_a_batch(tiny):
    """A row's tokens do not hang on its neighbours or its pad: the state
    of one row never reaches another's (greedy, kernels interpreted)."""
    both, alone = alone_and_in_a_batch(*tiny)
    assert both == alone


def test_the_one_shot_program_names_the_familys_scopes(tiny):
    """``ssm_in``, ``conv``, ``ssd`` and ``ssm_out`` in both phases beside
    the attention layers' and the feed-forward's: what
    ``scripts/trace_by_scope.py`` books this family by."""
    cfg, params = tiny
    be = _engine(cfg, params, batch_size=2, max_new_tokens=4, flash=False,
                 interpret=False, fresh=True)
    be._get_fn(2, 64, 4, be.gen_cfg)
    (m,) = be.scope_maps()
    got = {"/".join(p.split("/")[:2]) for p in m["scopes"].values()}
    for phase in ("prefill", "decode"):
        assert {f"{phase}/{c}" for c in (
            "ssm_in", "conv", "ssd", "ssm_out", "qkv", "kv_write", "attn",
            "attn_out", "mlp", "lm_head", "embed")} <= got
