"""CPU tests of the benchmark's own parts for the Laguna family: the plain
reference against the program, the run-time parity check and the faults it
has to catch, the rooflines against hand-worked numbers, the readers, the
cell's rehearsal, and the configuration file's arithmetic.

Nothing here touches the TPU library at import.
"""
import copy
import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import cells, engine_setup  # noqa: E402
from benchmarks import engine_setup_laguna as family_setup  # noqa: E402
from benchmarks import roofline_laguna as roof  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
CONFIG = cells.load_config(BENCH, "laguna-s-2.1-l5-int8")
CELL = "laguna-s-2.1-l5-int8.offline-mapreduce-8k-moe256"
FULL, SLIDING = "full_attention", "sliding_attention"
# poolside/Laguna-S-2.1 config.json, as published
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
               "original_max_position_embeddings": 8192, "beta_slow": 1,
               "beta_fast": 32, "attention_factor": 1.4852030263919618,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}},
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING] * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0,
}


def _tiny(**kw):
    from vnsum_tpu.models.laguna import tiny_laguna

    return tiny_laguna(**kw)


# -- the reference against the program ---------------------------------------


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("n_layers", [9, 7])   # whole periods; a part of one
def test_plain_reference_agrees_with_the_cache_free_forward(int8, n_layers):
    import jax
    import jax.numpy as jnp

    from benchmarks import reference_laguna as reference
    from vnsum_tpu.models import laguna as lg
    from vnsum_tpu.models.quant import quantize_params

    cfg = _tiny(n_layers=n_layers)
    params = lg.init_params(jax.random.key(5), cfg)
    if int8:
        params = quantize_params(params)
    toks = jax.random.randint(jax.random.key(6), (60,), 0, cfg.vocab_size)
    want = reference.logits(params, toks, family_setup.sizes_from(cfg))
    got = lg.forward_dense(params, cfg, toks[None])[0]
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_engine_prefill_and_decode_agree_with_the_reference(kv):
    """The engine's chunked prefill (two chunks, kernels interpreted at both
    groups, the per-layer window) and then decode steps through the cache,
    against the reference's ONE forward, with a prompt longer than the
    window: float weights, so what is left is the cache's own rounding (and
    the router's near-ties it turns, which the reference takes inside a
    band as the run-time check does)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_laguna as reference
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import laguna as lg

    cfg = _tiny(max_seq_len=400)
    params = lg.init_params(jax.random.key(7), cfg)
    be = TpuBackend(model_config=cfg, tokenizer="byte", params=params,
                    batch_size=1, max_new_tokens=8, interpret=True,
                    quantize_kv=(kv == "int8"), prefill_chunk_tokens=128)
    ids = np.asarray(jax.random.randint(
        jax.random.key(8), (155,), 0, cfg.vocab_size)).tolist()
    assert 150 > 4 * cfg.sliding_window     # far past the window
    got, state = be.prefill_then_decode_logits(
        ids[:150], ids[150:], bucket=256, return_state=True)
    picks = jnp.asarray(state["rows"][:, :, 0].swapaxes(0, 1))
    want = np.asarray(reference.forward(
        params, jnp.asarray(ids), family_setup.sizes_from(cfg), last=6,
        theirs=picks, tie_band=0.0 if kv == "bf16" else 0.05)["logits"])
    assert got.shape == want.shape == (6, cfg.vocab_size)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert err.max() < (1e-5 if kv == "bf16" else 0.02), err
    assert be.stats.attention_paths["logits[B=1,S=256]"] == {
        "prefill": "kernel", "decode": "kernel"}


# -- the run-time parity check -------------------------------------------------


def _int4_kv(x):
    """``models.llama._quantize_kv`` with 4 bits a value: the nearest
    precision below the configured int8 cache."""
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32), -1, keepdims=True), 1e-8) / 7.0
    return (jnp.clip(jnp.round(x32 / scale), -7, 7).astype(jnp.int8),
            scale[..., 0])


@pytest.fixture(scope="module")
def rehearsal_backend():
    """One tiny engine with interpreted kernels for every parity reading:
    the faults are the reference's, the engine is the same."""
    from vnsum_tpu.backend.engine import TpuBackend

    config = copy.deepcopy(CONFIG)
    cfg = family_setup.model_config(config, rehearsal=True)
    params = family_setup.start_weights(config, cfg, 14)
    return TpuBackend(
        model_config=cfg, tokenizer="byte", batch_size=2, max_new_tokens=8,
        params=params, **engine_setup.backend_kwargs(config, rehearsal=True))


def _parity(backend, faults=(), config=None, seed=14):
    return family_setup.parity_with_reference(
        backend, config or copy.deepcopy(CONFIG), seed, rehearsal=True,
        faults=faults)


@pytest.mark.parametrize("fault", [
    None, "no_gate", "gate_a_token", "sliding_heads_as_full", "full_rotary",
    "no_attention_factor", "no_window", "theta_swapped", "no_scaling",
    "no_renorm", "no_shared", "top_6"])
def test_parity_check_passes_the_program_and_catches_each_fault(
        fault, rehearsal_backend):
    """``parity_with_reference`` on a tiny engine with interpreted kernels
    and a prompt past the window: it passes the program as it is (prefill
    and decode steps, every row within the one tolerance, the leading
    layer's cache rows within theirs), and fails when the two stop being
    the same mathematics — each of ``reference.FAULTS``."""
    from benchmarks import reference_laguna as reference

    assert set(reference.FAULTS) == {
        "no_gate", "gate_a_token", "sliding_heads_as_full", "full_rotary",
        "no_attention_factor", "no_window", "theta_swapped", "no_scaling",
        "no_renorm", "no_shared", "top_6"}
    got = _parity(rehearsal_backend, (fault,) if fault else ())
    assert got["ok"] is (fault is None), got
    assert got["kernel"] is True and got["prompt_tokens"] == 150
    assert got["prompt_tokens"] > got["window"] == 48
    assert got["decode_steps"] == 4 and len(got["errors"]) == 5
    assert got["error"] == max(got["errors"])
    assert len(got["took"]) == 5 and max(got["took"]) <= 8   # sparse layers
    assert got["slots_held"] == got["slots_routed"] == (150 + 4) * 4 * 8
    if fault is None:
        assert got["took"] == [8] * 5
        assert got["error"] < 0.7 * got["tolerance"]
        assert got["kv_error"] < 0.7 * got["kv_tolerance"]
    else:
        assert got["error"] > 1.3 * got["tolerance"]
    # the leading layer is a full-attention one and reads the embedding
    # alone: of these only its own rotary reaches its keys
    assert (got["kv_error"] <= got["kv_tolerance"]) is (
        fault not in ("full_rotary", "no_attention_factor", "theta_swapped"))


def test_a_cache_of_four_bits_fails_the_check_of_the_caches_rows(monkeypatch):
    """A cache that rounds keys and values to 4 bits is the nearest
    precision below the configured int8: the leading layer's rows read many
    times their clean distance from the reference's, over
    ``kv_tolerance``."""
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import llama

    config = copy.deepcopy(CONFIG)
    cfg = family_setup.model_config(config, rehearsal=True)
    params = family_setup.start_weights(config, cfg, 14)
    monkeypatch.setattr(llama, "_quantize_kv", _int4_kv)
    backend = TpuBackend(
        model_config=cfg, tokenizer="byte", batch_size=2, max_new_tokens=8,
        params=params, **engine_setup.backend_kwargs(config, rehearsal=True))
    got = _parity(backend)
    assert got["ok"] is False
    assert got["kv_error"] > 1.3 * got["kv_tolerance"]


def test_one_broken_row_fails_the_check(monkeypatch, rehearsal_backend):
    """Every row is held, not a quantile of them."""
    import numpy as np

    from vnsum_tpu.backend.engine import TpuBackend

    real = TpuBackend.prefill_then_decode_logits

    def one_row_wrong(self, *a, **kw):
        logits, state = real(self, *a, **kw)
        logits = np.array(logits)
        logits[3] = logits[3][::-1]
        return logits, state

    monkeypatch.setattr(TpuBackend, "prefill_then_decode_logits",
                        one_row_wrong)
    got = _parity(rehearsal_backend)
    assert sorted(got["errors"])[-2] <= got["tolerance"]
    assert got["error"] > got["tolerance"] and got["ok"] is False


def test_a_prompt_inside_the_window_is_refused(rehearsal_backend):
    """A parity prompt that never leaves the window would pass a program
    that ignores it."""
    config = copy.deepcopy(CONFIG)
    config["rehearsal"]["parity"]["prompt_tokens"] = 40
    with pytest.raises(ValueError, match="never leaves the window"):
        _parity(rehearsal_backend, config=config)


def test_reference_refuses_an_unknown_fault():
    import jax
    import jax.numpy as jnp

    from benchmarks import reference_laguna as reference
    from vnsum_tpu.models import laguna as lg

    cfg = _tiny(n_layers=5)
    params = lg.init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="unknown faults"):
        reference.logits(params, jnp.arange(8), family_setup.sizes_from(cfg),
                         faults=("no_such_fault",))


def test_the_references_routing_rule_by_hand():
    """softmax over all, the best k, renormalised, times 2.5 — and each of
    its three faults."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_laguna as reference

    sizes = {"num_experts_per_tok": 2, "moe_routed_scaling_factor": 2.5}
    row = jnp.log(jnp.asarray([[0.4, 0.1, 0.3, 0.2]]))
    ids, w = reference.route(row, sizes)
    assert sorted(np.asarray(ids[0])) == [0, 2]
    np.testing.assert_allclose(np.sort(np.asarray(w[0])),
                               [2.5 * 3 / 7, 2.5 * 4 / 7], rtol=1e-6)
    _, w = reference.route(row, sizes, ("no_scaling",))
    np.testing.assert_allclose(np.sort(np.asarray(w[0])), [3 / 7, 4 / 7],
                               rtol=1e-6)
    _, w = reference.route(row, sizes, ("no_renorm",))
    np.testing.assert_allclose(np.sort(np.asarray(w[0])), [0.75, 1.0],
                               rtol=1e-6)
    ten = {"num_experts_per_tok": 10, "moe_routed_scaling_factor": 2.5}
    _, w = reference.route(jnp.arange(12.0)[None], ten, ("top_6",))
    assert int((np.asarray(w[0]) > 0).sum()) == 6
    np.testing.assert_allclose(float(w.sum()), 2.5, rtol=1e-6)
    # picks taken another's way: of those experts alone
    among = jnp.asarray([[True, True, False, False]])
    ids, w = reference.route(row, sizes, among=among)
    assert sorted(np.asarray(ids[0])) == [0, 1]
    np.testing.assert_allclose(np.sort(np.asarray(w[0])), [0.5, 2.0],
                               rtol=1e-6)
    assert bool(reference.ties_broken_their_way(
        row, jnp.asarray([[0, 2]]), 0.0)[0])
    assert not bool(reference.ties_broken_their_way(
        row, jnp.asarray([[0, 3]]), 0.1)[0])


# -- the configuration file -----------------------------------------------------


def test_model_config_builds_the_files_cut_of_the_published_model():
    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_sparse_layers,
            cfg.n_routed_experts, cfg.n_held, cfg.expert_offset) == (
        5, 1, 4, 256, 256, 0)
    assert (cfg.dim, cfg.n_heads, cfg.n_heads_sliding, cfg.n_kv_heads,
            cfg.head_dim, cfg.intermediate, cfg.moe_intermediate,
            cfg.shared_intermediate, cfg.num_experts_per_tok, cfg.vocab_size,
            cfg.sliding_window) == (
        3072, 48, 72, 8, 128, 12288, 1024, 1024, 10, 100352, 512)
    assert cfg.sliding_layout == (0, 1, 1, 1, 0)
    assert cfg.heads_per_layer == (48, 72, 72, 72, 48)
    assert (cfg.rope_theta, cfg.rope_factor, cfg.rope_original_max_len,
            cfg.rope_beta_fast, cfg.rope_beta_slow, cfg.rope_attention_factor,
            cfg.partial_rotary_factor, cfg.rope_local_theta) == (
        500000, 128, 8192, 32, 1, 1.4852030263919618, 0.5, 10000)
    assert cfg.routed_scaling_factor == 2.5 and cfg.max_seq_len == 8448
    kw = engine_setup.backend_kwargs(CONFIG, rehearsal=False)
    assert kw["quantize"] and kw["quantize_act"] and kw["quantize_kv"] is True
    assert family_setup.sizes_from(cfg) == family_setup.sizes_of(CONFIG, False)
    # the rehearsal's stand-in is the family's tiny preset but for its size
    tiny = family_setup.model_config(CONFIG, rehearsal=True)
    assert (tiny.n_layers, tiny.heads_per_layer[:2], tiny.n_routed_experts,
            tiny.num_experts_per_tok) == (9, (4, 6), 16, 4)


@pytest.mark.parametrize("edit, text", [
    (lambda s: s["num_attention_heads_per_layer"].__setitem__(2, 64),
     "not one number a layer kind"),
    (lambda s: s["mlp_layer_types"].__setitem__(2, "dense"),
     "dense layers do not lead"),
])
def test_lists_the_family_cannot_stack_are_refused(edit, text):
    sizes = copy.deepcopy(family_setup.sizes_of(CONFIG, False))
    edit(sizes)
    with pytest.raises(ValueError, match=text):
        family_setup.config_kwargs(sizes)


def test_config_file_keeps_every_published_key_and_states_its_cut():
    c = CONFIG
    entry = next(e for e in BENCH["configs"] if e["name"] == c["name"])
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 48}
    assert c["num_hidden_layers"] == 5      # the dense layer + a whole period
    for key, value in PUBLISHED.items():
        if key not in c["reduced"]:
            assert c[key] == value, key
    assert entry["source"] == c["source"] and "Laguna-S-2.1" in c["source"]
    for key in ("assumed", "deployment", "bytes", "engine_notes", "engine",
                "reference", "setup_module", "checkpoint_notes"):
        assert c[key], key
    for key in ("gate", "heads_per_layer", "qk_norm", "rope", "router",
                "shared_expert", "act", "rope_theta"):
        assert key in c["assumed"], key
    assert "nine times" in c["deployment"]
    assert c["checkpoint_seed"] == 41
    assert c["engine"]["batch"] in (24, 12, 8)
    assert c["engine"]["prefill_chunk_tokens"] in (2048, 1024)
    assert c["engine"]["max_seq_len"] == 8448
    parity = c["reference"]["parity"]
    assert parity["prompt_tokens"] >= 10 * 512 - 200 and parity["bucket"] == 8192
    assert parity["decode_steps"] == 8


def test_config_files_byte_arithmetic_is_the_models():
    import jax

    from vnsum_tpu.models.laguna import init_cache
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    tree = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                          jax.random.key(0))
    size = lambda t: sum(a.size * a.dtype.itemsize  # noqa: E731
                         for a in jax.tree.leaves(t))
    b, ffn = CONFIG["bytes"], tree["layers"]
    experts = sum(size(ffn[n]) for n in ("we_gate", "we_up", "we_down"))
    assert b["experts_a_layer"] == experts // 4 == 256 * b["one_expert"]
    assert b["one_expert"] == 3 * 3072 * 1024 + 4 * (2 * 1024 + 3072)
    assert b["router_a_layer"] == size(ffn["router"]) // 4 == 3072 * 256 * 2
    assert b["sparse_ffn_a_layer"] == size(ffn) // 4
    assert b["attention_full_layer"] == size(tree["full"])
    assert b["attention_sliding_layer"] == size(tree["sliding"]) // 3
    assert b["dense_layer"] == size(tree["dense"])
    assert b["full_sparse_layer"] == b["attention_full_layer"] + size(ffn) // 4
    assert b["layers_5"] == b["dense_layer"] + b["full_sparse_layer"] \
        + 3 * b["sliding_sparse_layer"]
    assert b["embedding_and_head"] == size(tree["embed"]) + size(tree["lm_head"])
    assert b["weights"] == size(tree) == b["layers_5"] + b["embedding_and_head"] \
        + size(tree["final_norm"])
    # the issue's reckoning: 10.74 GB
    assert 10.73e9 < b["weights"] < 10.75e9
    s = family_setup.sizes_of(CONFIG, False)
    assert roof.attention_params(s, 72) == 3072 * 128 * (72 + 16 + 72) + 3072 * 72
    assert roof.attention_params(s, 48) == 3072 * 128 * (48 + 16 + 48) + 3072 * 48
    assert roof.expert_params(s) == roof.shared_params(s) == 9_437_184
    assert roof.router_params(s) == 786_432
    cache = jax.eval_shape(lambda: init_cache(cfg, 1, 8448, quantized=True))
    kv = sum(size(cache[n]) for n in ("k", "v", "ks", "vs"))
    assert kv == b["kv_cache_a_row"] == 5 * 8 * 8448 * (2 * 128 + 8)


# -- the rooflines ----------------------------------------------------------------

SIZES = family_setup.sizes_of(CONFIG, False)
PEAKS = {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_per_s": 819e9}
PRECISION = {"weights": 1, "kv": 1, "prefill_matmul": "int8"}
EXPERTS = {"slots_routed": 1000, "slots_held": 1000, "decode_touched": 163840,
           "decode_layer_steps": 1024}      # 160 experts a step and layer
ATTN = {48: 3072 * 128 * 112 + 3072 * 48, 72: 3072 * 128 * 160 + 3072 * 72}
EXPERT = 9_437_184


def test_layers_and_contexts_by_hand():
    assert roof.layers(SIZES) == [
        (48, False, False), (72, True, True), (72, True, True),
        (72, True, True), (48, False, True)]
    assert roof.sparse_layers(SIZES) == 4
    assert roof.causal_pairs(4) == 10 and roof.causal_pairs(4, 2) == 7
    assert roof.causal_pairs(8000, 512) == 512 * 513 // 2 + 7488 * 512
    # a row of 3 tokens, 4 steps: 4 + 5 + 6 + 7 slots; in a window of 5:
    # 4 + 5 + 5 + 5
    assert roof.context(3, 4) == 22 and roof.context(3, 4, 5) == 19
    assert roof.context(9, 2, 5) == 10


def test_kernel_rooflines_against_hand_worked_numbers():
    lens, steps = [8000, 5000], 256
    k = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, EXPERTS, lens, steps)
    # a sliding layer at 72 heads, a full one at 48: each its own pairs
    full = lambda n: n * (n + 1) // 2  # noqa: E731
    win = lambda n: 512 * 513 // 2 + (n - 512) * 512  # noqa: E731
    ops = 4 * 128 * sum(2 * 48 * full(n) + 3 * 72 * win(n) for n in lens)
    assert roof.prefill_attention_ops(SIZES, lens) == ops
    assert k["flash_prefill_attention"] == {
        "seconds": pytest.approx(ops / 197e12), "bound": "compute"}
    # decode: both rows are past the window from the first step
    ctx_full = sum(steps * (n + 1) + steps * (steps - 1) // 2 for n in lens)
    ctx_win = 2 * steps * 512
    dec = roof.decode_attention(SIZES, lens, steps, 1)
    assert dec["ops"] == 4 * 128 * (2 * 48 * ctx_full + 3 * 72 * ctx_win)
    assert dec["bytes"] == 8 * (2 * 128 + 8) * (2 * ctx_full + 3 * ctx_win)
    assert k["flash_decode_attention"] == {
        "seconds": pytest.approx(dec["bytes"] / 819e9), "bound": "memory"}
    # experts: ten picks a token and sparse layer in prefill; a decode step
    # reads the 160 experts a layer it touched, each 9.4 MB
    ex = roof.expert_matmul(SIZES, EXPERTS, 13000, 2, steps, 1)
    assert ex["prefill_ops"] == 2 * EXPERT * 10 * 4 * 13000
    assert ex["decode_ops"] == 2 * EXPERT * 10 * 4 * 2 * steps
    assert ex["decode_bytes"] == pytest.approx(EXPERT * 160 * 4 * steps)
    assert k["expert_grouped_matmul"]["seconds"] == pytest.approx(
        ex["prefill_ops"] / 393e12 + ex["decode_bytes"] / 819e9)
    assert k["expert_grouped_matmul"]["bound"] == "compute, then memory"
    assert roof.decode_attention(SIZES, lens, steps, 2)["bytes"] == \
        8 * 2 * 128 * 2 * (2 * ctx_full + 3 * ctx_win)
    assert roof.touched(SIZES, {"decode_layer_steps": 0}, steps) == 0.0


def test_dispatch_roofline_adds_up_by_hand():
    lens, steps = [8000, 5000], 256
    d = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, lens, steps)
    sparse = 786_432 + EXPERT + 10 * EXPERT     # router, shared, ten picks
    token = (2 * ATTN[48] + 3 * ATTN[72] + 3 * 3072 * 12288 + 4 * sparse)
    assert roof.params_a_token(SIZES, 1.0) == token
    head = 3072 * 100_352
    assert d["prefill_matmul_ops"] == pytest.approx(
        2 * token * 13000 + 2 * head * 2)
    k = d["kernels"]
    assert d["prefill_s"] == pytest.approx(
        d["prefill_matmul_ops"] / 393e12
        + k["flash_prefill_attention"]["seconds"])
    fixed = (2 * ATTN[48] + 3 * ATTN[72] + 3 * 3072 * 12288
             + 4 * (786_432 + EXPERT))
    assert roof.fixed_params(SIZES) == fixed
    dec = roof.decode_attention(SIZES, lens, steps, 1)
    assert d["decode_bytes"] == pytest.approx(
        (fixed + head) * steps + EXPERT * 160 * 4 * steps + dec["bytes"])
    assert d["decode_s"] == pytest.approx(d["decode_bytes"] / 819e9)
    assert d["total_s"] == pytest.approx(d["prefill_s"] + d["decode_s"])
    # half the picks held here: half the routed experts' operations a token
    half = dict(EXPERTS, slots_held=500)
    assert roof.params_a_token(SIZES, roof.held_share(half)) == \
        token - 4 * 5 * EXPERT


# -- the readers --------------------------------------------------------------------


def _raw():
    return {
        "device": {"kind": "TPU v5 lite"}, "sizes": SIZES,
        "precision": PRECISION,
        "counts": {"experts": {**EXPERTS, "decode_reads_possible": 1024 * 256,
                               "tokens": [[25] * 255 + [50]] * 4},
                   "prefill_blocks": {"interior": 10, "edge": 4,
                                      "window_scores_computed": 900,
                                      "window_scores_needed": 300}},
        "trace": {"busy_s": 10.0, "modules": {"jit_generate": 9.0},
                  "module_calls": {"jit_generate": 1},
                  "device_ops": [["flash_prefill_attention", 2.5],
                                 ["expert_grouped_matmul", 0.5],
                                 ["fusion.7", 0.3]]},
        "traced": {"dispatches": [
            {"prompt_lens": [8000, 5000], "steps": 256, "experts": EXPERTS},
            {"prompt_lens": [2000], "steps": 256, "experts": EXPERTS}]},
    }


def _read(name, raw):
    spec = cells.load_layer_metric(name)
    return cells.load_module("readers", spec["reader"]).read(spec, raw)


def test_new_metrics_on_a_known_record():
    raw = _raw()
    least = roof.kernel_least_seconds(
        SIZES, PRECISION, PEAKS, EXPERTS, [8000, 5000], 256)
    assert _read("laguna_prefill_attention_roofline", raw) == pytest.approx(
        100 * least["flash_prefill_attention"]["seconds"] / 2.5)
    assert _read("laguna_expert_matmul_roofline", raw) == pytest.approx(
        100 * least["expert_grouped_matmul"]["seconds"] / 0.5)
    assert _read("laguna_decode_attention_roofline", raw) is None
    assert _read("laguna_attention_busy_share", raw) is None
    raw["trace"]["device_ops"].append(["flash_decode_attention", 1.5])
    assert _read("laguna_attention_busy_share", raw) == pytest.approx(40.0)
    assert _read("laguna_decode_attention_roofline", raw) == pytest.approx(
        100 * least["flash_decode_attention"]["seconds"] / 1.5)
    whole = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, [8000, 5000], 256)
    assert _read("generate_roofline_share_laguna", raw) == pytest.approx(
        100 * whole["total_s"] / 9.0)
    assert _read("laguna_window_scores_computed_over_needed", raw) == \
        pytest.approx(3.0)
    # the metrics it shares with the other expert cells
    assert _read("expert_distinct_per_step", raw) == pytest.approx(
        100 * 160 / 256)
    assert _read("expert_load_max_over_mean", raw) == pytest.approx(
        50 / ((255 * 25 + 50) / 256))
    assert _read("expert_ffn_busy_share", raw) == pytest.approx(5.0)


def test_readers_with_nothing_to_read_leave_their_metric_out():
    """As on the parent commit, whose program has no such family or
    counter: None, never an exception."""
    bare = {"device": {"kind": "TPU v5 lite"}, "counts": {}, "trace": None,
            "traced": None}
    for m in cells.metrics_for(BENCH, "per_layer", CELL):
        if m["name"] not in ("host_share.offline",):
            assert _read(m["name"], bare) is None, m["name"]
    # a program that counts its cells by class alone (the parent commit's)
    raw = _raw()
    raw["counts"]["prefill_blocks"] = {"interior": 10, "edge": 4}
    assert _read("laguna_window_scores_computed_over_needed", raw) is None
    raw["traced"]["dispatches"][0]["experts"] = None
    for name in ("laguna_prefill_attention_roofline",
                 "generate_roofline_share_laguna"):
        assert _read(name, raw) is None, name


def test_the_cell_lists_its_own_metrics_and_those_it_shares():
    """By membership: where in ``per_layer`` an entry stands is the
    driver's to check, not this file's."""
    mine = {m["name"] for m in cells.metrics_for(BENCH, "per_layer", CELL)}
    own = {"generate_roofline_share_laguna",
           "laguna_prefill_attention_roofline",
           "laguna_decode_attention_roofline", "laguna_expert_matmul_roofline",
           "laguna_attention_busy_share",
           "laguna_window_scores_computed_over_needed"}
    shared = {"host_share.offline", "generate_device_s_per_dispatch",
              "device_idle.offline", "expert_ffn_busy_share",
              "expert_load_max_over_mean", "expert_distinct_per_step",
              "idle_in_engine_host.offline", "idle_in_pipeline_host.offline",
              "idle_unexplained.offline"}
    assert mine == own | shared
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in own:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "docs_per_min"
        assert m["layer"] == "model and kernels"
        spec = cells.load_layer_metric(name)
        assert spec["drivers"] == ["offline_pipeline_family"]
        if "roofline" in spec:
            assert spec["roofline"] == "roofline_laguna"
    assert {m["name"] for m in cells.metrics_for(BENCH, "end_to_end", CELL)
            } == {"docs_per_min", "setup_s"}
    assert cells.validate(BENCH, ROOT) == []
    cell = cells.find_cell(BENCH, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "5 of 48 layers" in cell["why"]
    traffic = cells.load_traffic("offline-mapreduce-8k-moe256")
    base = cells.load_traffic("offline-mapreduce-8k")
    for key in ("doc_tokens", "chunks_per_doc", "chunk_size", "chunk_overlap",
                "token_max", "max_new_tokens", "bpe_vocab", "bpe_train_words",
                "warmup_reduce_summaries", "approach", "rehearsal"):
        assert traffic[key] == base[key], key
    assert traffic["driver"] == "offline_pipeline_family"
    assert CONFIG["setup_module"] == "engine_setup_laguna"


def test_the_driver_finds_this_familys_setup_module():
    import importlib

    mod = importlib.import_module(f"benchmarks.{CONFIG['setup_module']}")
    for fn in ("model_config", "start_weights", "sizes_of",
               "parity_with_reference"):
        assert callable(getattr(mod, fn)), fn


# -- the cell, rehearsed ----------------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    """The whole cell at a tiny size on the CPU, kernels interpreted: the
    driver, the family's set-up, parity, warm-up, a window, the readers."""
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 77), "--seconds", "2", "--trace",
         str(trace), "--rehearsal"],
        capture_output=True, text=True, cwd=ROOT, timeout=900,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu" and line["correct"] is False
    assert "failed checks: ['platform_is_tpu']" in p.stderr, p.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    group = "per_layer" if trace else "end_to_end"
    assert set(line["metrics"]) == {
        m["name"] for m in cells.metrics_for(BENCH, group, CELL)}
    if trace:
        counted = {n: m["value"] for n, m in line["metrics"].items()
                   if m["value"] != "not measured"}
        assert set(counted) == {
            "expert_load_max_over_mean", "expert_distinct_per_step",
            "laguna_window_scores_computed_over_needed"}
        assert counted["laguna_window_scores_computed_over_needed"] > 1.0
        assert 25.0 <= counted["expert_distinct_per_step"] <= 100.0
