"""The Keye family (models/keye.py, ops/sparse_attention.py) on the CPU at a
tiny size: three layers, 4 query heads on 2 KV heads of 16, 8 experts top-2,
4 indexer heads of 8 and a top-k of 12 — shorter than the prompts, so that
selection really drops keys — against ``benchmarks/reference_keye.py``."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_setup_keye as setup
from benchmarks import reference_keye as reference
from family_harness import (
    alone_and_in_a_batch,
    engine as _engine,
    picks_agree,
    reference_of,
    rel as _rel,
    sizes,
    through_the_engine as _through_the_engine,
    tokens as _tokens,
)
from vnsum_tpu.models import MODEL_REGISTRY, jitted_init, llama
from vnsum_tpu.models import keye as ky
from vnsum_tpu.models.family import family_of

_sizes = functools.partial(sizes, setup)


@pytest.fixture(scope="module")
def tiny():
    cfg = ky.tiny_keye()
    return cfg, jitted_init(ky.init_params, cfg, 0)


# -- the config and the parameters ---------------------------------------------


def test_published_config():
    cfg = ky.keye_vl_2_0_30b_a3b()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.moe_intermediate, cfg.n_routed_experts,
            cfg.num_experts_per_tok, cfg.vocab_size, cfg.max_seq_len) == (
        2048, 48, 32, 4, 128, 768, 128, 8, 151_936, 262_144)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.mrope_section) == (
        1e7, 1e-6, (16, 24, 24))
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        16, 64, 2048)
    assert not cfg.tie_embeddings and cfg.act == "silu" and cfg.q_per_kv == 8
    assert cfg.n_held == 128 and cfg.expert_offset == 0
    assert MODEL_REGISTRY["keye-vl-2.0-30b-a3b"]() == cfg
    assert MODEL_REGISTRY["tiny-keye"]() == ky.tiny_keye()
    assert family_of(cfg).name == "keye"


@pytest.mark.parametrize("kw, text", [
    (dict(n_kv_heads=3), "n_kv_heads must divide"),
    (dict(mrope_section=(2, 3, 4)), "frequencies of a head"),
    (dict(index_head_dim=7), "must be even"),
])
def test_config_refuses_what_it_cannot_mean(kw, text):
    with pytest.raises(ValueError, match=text):
        ky.tiny_keye(**kw)


def test_parameters_are_one_stack_with_an_indexer_a_layer():
    cfg = ky.tiny_keye()
    p = jax.eval_shape(lambda k: ky.init_params(k, cfg), jax.random.key(0))
    lay = p["layers"]
    assert lay["wq"].shape == (3, 64, 4, 16)
    assert lay["wk"].shape == lay["wv"].shape == (3, 64, 2, 16)
    assert lay["wq_idx"].shape == (3, 64, 4, 8)
    assert lay["wk_idx"].shape == (3, 64, 8) and lay["w_idx"].shape == (3, 64, 4)
    assert lay["idx_norm_g"].shape == lay["idx_norm_b"].shape == (3, 8)
    assert lay["q_norm"].shape == lay["k_norm"].shape == (3, 16)
    assert lay["router"].shape == (3, 64, 8)
    assert lay["we_gate"].shape == (3, 8, 64, 32)
    assert lay["we_down"].shape == (3, 8, 32, 64)
    assert p["lm_head"].shape == (64, 384)            # untied
    for name in ("w_idx", "idx_norm_g", "idx_norm_b"):
        assert lay[name].dtype == jnp.float32
    assert "w_gate" not in lay and "ws_gate" not in lay   # no dense, no shared


def test_the_float_leaves_are_drawn_so_that_relu_and_the_weights_matter():
    leaves = ky.float_leaves(jax.random.key(3), ky.tiny_keye(n_layers=12))
    w = np.asarray(leaves["layers"]["w_idx"])
    assert 0.4 < (w > 0).mean() < 0.6                  # mixed signs
    assert 0.8 < w.std() * 64 ** 0.5 < 1.2             # N(0, 1 / sqrt D)
    for name in ("q_norm", "k_norm", "idx_norm_g"):
        n = np.asarray(leaves["layers"][name], np.float32)
        assert 0.5 <= n.min() < 0.7 and 1.3 < n.max() <= 1.5
    b = np.asarray(leaves["layers"]["idx_norm_b"])
    assert 0.05 < b.std() < 0.15


def test_int8_quantizes_the_indexers_projections_and_keeps_its_weights():
    from vnsum_tpu.models.quant import (
        dequantize_params,
        init_params_quantized,
        quantize_params,
    )

    cfg = ky.tiny_keye()
    q = jitted_init(init_params_quantized, cfg, 2)
    lay = q["layers"]
    for name in ("wq", "wk", "wv", "wo", "wq_idx", "wk_idx", "we_gate",
                 "we_up", "we_down"):
        assert lay[name]["q"].dtype == jnp.int8, name
    assert lay["wk_idx"]["s"].shape == (3, 8)
    assert lay["wq_idx"]["s"].shape == (3, 4, 8)
    for name in ("w_idx", "idx_norm_g", "idx_norm_b"):
        assert lay[name].dtype == jnp.float32
    # the family's own draws, not ones and not the router's 0.02
    assert 0.5 <= float(lay["q_norm"].min()) < float(lay["q_norm"].max()) <= 1.5
    assert float(jnp.abs(lay["idx_norm_b"]).max()) > 0
    back = dequantize_params(quantize_params(jitted_init(ky.init_params,
                                                         cfg, 0)))
    assert back["layers"]["wq_idx"].shape == (3, 64, 4, 8)


# -- rotary and routing ----------------------------------------------------------


def test_three_equal_components_are_plain_rotary():
    cfg = ky.tiny_keye()
    pos = jnp.arange(10)[None, :] + 3
    (cos, sin), (icos, isin) = ky.rope_tables(cfg, pos)
    plain = llama._rope_cos_sin(llama.tiny_llama(
        head_dim=16, rope_theta=cfg.rope_theta), pos)
    np.testing.assert_allclose(cos, plain[0], rtol=1e-6)
    np.testing.assert_allclose(sin, plain[1], rtol=1e-6)
    three = ky.rope_tables(cfg, jnp.broadcast_to(pos[..., None], (1, 10, 3)))
    assert (np.asarray(three[0][0]) == np.asarray(cos)).all()
    assert icos.shape == (1, 10, 4)


def test_distinct_components_turn_their_own_sections():
    cfg = ky.tiny_keye()            # sections (2, 3, 3)
    pos = jnp.stack([jnp.arange(6), jnp.arange(6) * 3, jnp.arange(6) * 7],
                    -1)[None]
    (cos, _), (icos, _) = ky.rope_tables(cfg, pos)
    inv = 1.0 / cfg.rope_theta ** (np.arange(8) / 8)
    want = np.concatenate([np.arange(6)[:, None] * inv[:2],
                           np.arange(6)[:, None] * 3 * inv[2:5],
                           np.arange(6)[:, None] * 7 * inv[5:]], -1)
    np.testing.assert_allclose(cos[0], np.cos(want), rtol=1e-5, atol=1e-6)
    # the indexer turns by the FIRST component alone
    iinv = 1.0 / cfg.rope_theta ** (np.arange(4) / 4)
    np.testing.assert_allclose(icos[0], np.cos(np.arange(6)[:, None] * iinv),
                               rtol=1e-5, atol=1e-6)


def test_routing_is_qwen3_moes_rule_on_a_hand_made_case():
    logits = jnp.log(jnp.asarray([[0.1, 0.4, 0.2, 0.3], [0.7, 0.1, 0.1, 0.1]]))
    ids, w = ky.route(logits, 2)
    assert ids.tolist() == [[1, 3], [0, 1]]
    np.testing.assert_allclose(w, [[4 / 7, 3 / 7], [7 / 8, 1 / 8]], rtol=1e-6)


# -- against the reference ---------------------------------------------------------


def test_cache_free_forward_is_the_reference(tiny):
    cfg, params = tiny
    toks = _tokens(40)
    got = ky.forward_dense(params, cfg, toks)
    for row in range(2):
        want = reference_of(reference, _sizes(cfg), params,
                            np.asarray(toks[row]).tolist())
        assert _rel(got[row], want["logits"]) < 1e-5


def test_cache_free_forward_at_distinct_position_components(tiny):
    cfg, params = tiny
    toks = _tokens(30, rows=1)
    pos = jnp.stack([jnp.arange(30), jnp.arange(30) // 2,
                     (jnp.arange(30) * 5) % 11], -1)
    got = ky.forward_dense(params, cfg, toks, pos[None])
    def plain(faults):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, t, at: reference.logits(
                p, t, _sizes(cfg), positions=at, faults=faults))(
                params, toks[0], pos)

    want, swapped = plain(()), plain(("components_swapped",))
    assert _rel(got[0], want) < 1e-5
    assert _rel(swapped, want) > 1e-2


def test_int8_weights_stay_within_their_rounding(tiny):
    from vnsum_tpu.models.quant import quantize_params

    cfg, params = tiny
    toks = _tokens(40, rows=1)
    qparams = quantize_params(params)
    got = ky.forward_dense(qparams, cfg, toks)
    # the reference multiplies the int8 leaves out: the same weights, so
    # what is left is the program's arithmetic
    same = reference_of(reference, _sizes(cfg), qparams,
                        np.asarray(toks[0]).tolist())
    assert _rel(got[0], same["logits"]) < 1e-4
    # against the float weights: their rounding, through three top-ks
    want = reference_of(reference, _sizes(cfg), params,
                        np.asarray(toks[0]).tolist())
    assert 1e-3 < _rel(got[0], want["logits"]) < 0.3


def test_no_more_visible_keys_than_the_top_k_is_dense_attention_bit_for_bit(
        tiny):
    cfg, params = tiny
    toks = _tokens(cfg.index_topk, rows=1)
    sparse = ky.forward_dense(params, cfg, toks)
    dense = ky.forward_dense(params, dataclasses.replace(
        cfg, index_topk=10_000), toks)
    assert (np.asarray(sparse) == np.asarray(dense)).all()
    longer = _tokens(cfg.index_topk + 8, rows=1)
    assert not (np.asarray(ky.forward_dense(params, cfg, longer))
                == np.asarray(ky.forward_dense(params, dataclasses.replace(
                    cfg, index_topk=10_000), longer))).all()


@pytest.mark.parametrize("n, bucket, kw", [
    (8, 64, {}),                                    # shorter than the top-k
    (12, 64, {}),                                   # exactly the top-k
    (64, 64, {}),                                   # pad 0, several top-ks
    (63, 64, {}),                                   # pad 1
    (129, 256, {}),                                 # pad a chunk less one
    (100, 256, {}),                                 # pad more than a chunk
    (200, 256, dict(piece_tokens=128)),             # row pieces, two chunks
    (100, 256, dict(flash=False, interpret=False)),   # the XLA forms
])
def test_chunked_prefill_and_decode_through_both_caches(tiny, n, bucket, kw):
    """Logits, every layer's selection position by position, the routers'
    picks and the caches' rows of the first layer against the reference's
    one full forward."""
    cfg, params = tiny
    steps = 4
    ids = np.asarray(_tokens(n + steps, rows=1, seed=n)[0]).tolist()
    be, got, state = _through_the_engine(cfg, params, ids, n, bucket, **kw)
    want = reference_of(reference, _sizes(cfg), params, ids, keep_sel=True)
    assert _rel(got, want["logits"][n - 1:]) < 1e-5
    at = slice(bucket - n, bucket + steps)
    sel = np.asarray(state["rows"]["sel"])[:, :, 0, at] != 0    # [pos, L, T]
    theirs = np.asarray(want["sel"])[:, n - 1:].swapaxes(0, 1)
    assert (sel == theirs).all()
    # nothing under the pad or past the position is ever kept
    whole = np.asarray(state["rows"]["sel"])[:, :, 0]
    assert not whole[:, :, :bucket - n].any()
    for i in range(steps + 1):
        assert not whole[i, :, bucket + i + 1 - (i == 0):].any() or i == 0
    assert picks_agree(state, want, steps + 1)
    cache = state["cache"]
    np.testing.assert_allclose(cache["ki"][0, 0, :, at].T, want["ki"][0],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        cache["k"][0, 0, :, at].swapaxes(0, 1), want["k"][0], rtol=1e-4,
        atol=1e-5)


def test_the_selection_drops_keys_and_is_one_set_for_all_heads(tiny):
    cfg, params = tiny
    ids = np.asarray(_tokens(60, rows=1, seed=5)[0]).tolist()
    want = reference_of(reference, _sizes(cfg), params, ids, keep_sel=True)
    sel = np.asarray(want["sel"])                    # [L, T, T]: no head dim
    kept = sel.sum(-1)
    assert (kept == np.minimum(np.arange(60) + 1, cfg.index_topk)).all()
    assert (kept[:, -1] == cfg.index_topk).all() and cfg.index_topk < 60
    # the layers do not share their sets
    assert (sel[0] != sel[1]).any() and (sel[1] != sel[2]).any()


@pytest.mark.parametrize("fault", [f for f in reference.FAULTS
                                   if f not in reference.SCORES_ONLY
                                   and f != "components_swapped"])
def test_a_fault_shows_in_the_logits(tiny, fault):
    cfg, params = tiny
    ids = np.asarray(_tokens(48, rows=1, seed=2)[0]).tolist()
    want = reference_of(reference, _sizes(cfg), params, ids)["logits"]
    wrong = reference_of(reference, _sizes(cfg), params, ids,
                         faults=(fault,))["logits"]
    assert _rel(wrong, want) > 5e-3, fault


def test_the_indexers_scale_shows_in_its_scores_alone(tiny):
    """A positive factor on every score moves no top-k: the logits cannot
    see it; the selection's recorded scores can."""
    cfg, params = tiny
    n, steps = 40, 2
    ids = np.asarray(_tokens(n + steps, rows=1, seed=3)[0]).tolist()
    _, _, state = _through_the_engine(cfg, params, ids, n, 64)
    mine = np.asarray(state["rows"]["sel_scores"])[:, 0, 0, 64 - n:64 + steps]
    sel = jnp.asarray(np.asarray(state["rows"]["sel"])[
        :, :, 0, 64 - n:64 + steps].swapaxes(0, 1) != 0)
    size = _sizes(cfg)
    outs = {}
    for faults in ((), ("no_index_scale",)):
        with jax.default_matmul_precision("highest"):
            outs[faults] = jax.jit(lambda p, t, theirs: reference.forward(
                p, t, size, their_sel=theirs, faults=faults))(
                params, jnp.asarray(ids), sel)
    clean, scaled = outs[()], outs[("no_index_scale",)]
    assert _rel(scaled["logits"], clean["logits"]) < 1e-6
    seen = np.isfinite(mine)
    assert _rel(mine[seen], np.asarray(clean["scores"][0])[seen]) < 1e-5
    # 1 - 1 / sqrt(4 x 8) of the unscaled scores' length
    assert _rel(mine[seen], np.asarray(scaled["scores"][0])[seen]) > 0.5


def test_softmax_over_the_picked_is_the_rule_itself(tiny):
    cfg, params = tiny
    ids = np.asarray(_tokens(30, rows=1, seed=4)[0]).tolist()
    want = reference_of(reference, _sizes(cfg), params, ids)["logits"]
    same = reference_of(reference, _sizes(cfg), params, ids,
                        faults=("softmax_over_picked",))["logits"]
    assert _rel(same, want) < 1e-5
    with pytest.raises(ValueError, match="unknown faults"):
        reference.forward(params, jnp.asarray(ids), _sizes(cfg),
                          faults=("no_such_fault",))


def test_the_reference_takes_a_rightful_set_and_no_other():
    scores = jnp.asarray([[9., 5., 5.01, 1., 8.]])
    visible = jnp.ones((1, 5), bool)
    own = reference.top_by_sort(scores, visible, 3)
    assert own.tolist() == [[True, False, True, False, True]]
    near = jnp.asarray([[True, True, False, False, True]])   # 5 for 5.01
    far = jnp.asarray([[True, False, False, True, True]])    # 1 for 5.01
    fewer = jnp.asarray([[True, False, False, False, True]])
    for theirs, band, ok in ((near, 0.01, True), (near, 0.0, False),
                             (far, 0.01, False), (fewer, 1.0, False),
                             (own, 0.0, True)):
        got = reference.selection_is_rightful(scores, visible, own, theirs,
                                              band)
        assert bool(got[0]) is ok, (theirs, band)


@pytest.mark.parametrize("theirs, band, want", [
    # 5 for 5.01, a near-tie: theirs inside the band; at band 0 the cut's
    # own slot alone is theirs to drop, and 5 stays out
    ([1, 1, 0, 0, 1], 0.01, [1, 1, 0, 0, 1]),
    ([1, 1, 0, 0, 1], 0.0, [1, 0, 0, 0, 1]),
    # 1 for 5.01: the far slot stays out, the near one is theirs to drop
    ([1, 0, 0, 1, 1], 0.01, [1, 0, 0, 0, 1]),
    # 1 for 9: both far from the cut, nothing of theirs is taken
    ([0, 0, 1, 1, 1], 0.01, [1, 0, 1, 0, 1]),
    # fewer slots than the reference keeps: not taken at all
    ([1, 0, 0, 0, 1], 5.0, [1, 0, 1, 0, 1]),
])
def test_the_reference_takes_their_slots_near_the_cut_alone(theirs, band,
                                                            want):
    scores = jnp.asarray([[9., 5., 5.01, 1., 8.]])
    visible = jnp.ones((1, 5), bool)
    own = reference.top_by_sort(scores, visible, 3)
    got = reference.their_slots_near_the_cut(
        scores, visible, own, jnp.asarray([theirs], bool), band)
    assert got[0].astype(int).tolist() == want
    # a slot that is not visible is never taken
    hidden = visible.at[0, 1].set(False)
    own = reference.top_by_sort(scores, hidden, 3)
    got = reference.their_slots_near_the_cut(
        scores, hidden, own, jnp.asarray([[1, 1, 0, 0, 1]], bool), 5.0)
    assert got[0].tolist() == own[0].tolist()


# -- the engine's seam -------------------------------------------------------------


def test_generate_gives_the_same_rows_alone_and_in_a_batch(tiny):
    both, alone = alone_and_in_a_batch(*tiny)
    assert both == alone


def test_generate_at_the_longest_bucket_with_kernels_on_and_off(tiny):
    """A prompt in the bucket the sequence limit leaves (the 16,384 bucket's
    tiny equivalent: 250 of 256 slots), through the kernels and through the
    XLA forms: the same greedy tokens."""
    from vnsum_tpu.core.config import GenerationConfig

    cfg, params = tiny
    gen = GenerationConfig(temperature=0.0)
    prompt = "tóm tắt văn bản " * 11          # ~230 byte tokens
    outs = [
        _engine(cfg, params, generation=gen, **kw).generate(
            [prompt], max_new_tokens=6)[0]
        for kw in ({}, dict(flash=False, interpret=False))]
    assert outs[0] == outs[1] and outs[0]
    be = _engine(cfg, params, generation=gen)
    assert any(S == 250 for (_, S) in be.stats.by_bucket)
    blocks = be.stats.prefill_blocks
    assert 0 < blocks["dsa_index_scores_needed"] \
        <= blocks["dsa_index_scores_computed"]
    assert 0 < blocks["dsa_attention_scores_selected"] \
        < blocks["dsa_keys_visible"] * cfg.n_heads
    assert be.stats.expert_slots_routed > 0


def test_the_program_carries_indexer_keys_beside_keys_and_values(tiny):
    cfg, params = tiny
    cache = jax.eval_shape(lambda: ky.init_cache(cfg, 2, 40, quantized=True))
    assert cache["ki"].shape == (3, 2, 8, 40) and cache["ki"].dtype == cfg.dtype
    assert cache["k"].shape == (3, 2, 2, 40, 16) and cache["k"].dtype == jnp.int8
    assert cache["sel"].shape == (3, 2, 40)
    assert {"picks", "expert_tokens", "decode_touched"} <= set(cache)
    leaves = _engine(cfg, params).describe()["state_bytes_per_row"]
    assert leaves["ki"] == 3 * 8 * cfg.max_seq_len * 4


@pytest.mark.parametrize("entry, how", [
    ("slot loop", lambda be: be.start_slot_loop(2, 64)),
    ("speculative decoding", None),
    ("prefix cache", None), ("mesh", None), ("long-context backend", None),
    ("image inputs", None),
])
def test_entries_the_family_cannot_run_are_refused_by_mechanism(tiny, entry,
                                                                how):
    fam = family_of(tiny[0])
    with pytest.raises(NotImplementedError, match="keye family cannot run"):
        fam.refuse(entry)
    text = fam.missing[entry]
    assert any(word in text for word in ("indexer", "tower", "selection",
                                         "top-k"))
    if entry == "prefix cache":
        with pytest.raises(NotImplementedError, match="prefix cache"):
            _engine(*tiny, fresh=True, cache_blocks=4)
