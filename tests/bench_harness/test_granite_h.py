"""CPU tests of the benchmark's own parts for the Granite-4.0-H family: the
plain reference against the program, the run-time parity check and what it
has to catch (a fault of the equations, a state kept a precision below),
the rooflines against hand-worked numbers, the two readers that serve a
family without expert counters, the cell's rehearsal, and the configuration
file's keys and arithmetic.

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
from benchmarks import engine_setup_granite_h as family_setup  # noqa: E402
from benchmarks import roofline_granite_h as roof  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
CONFIG = cells.load_config(BENCH, "granite-4.0-h-micro-int8")
CELL = "granite-4.0-h-micro-int8.offline-mapreduce-8k-ssm"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# ibm-granite/granite-4.0-h-micro config.json, as the catalog row has it
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}


def _tiny(**kw):
    from vnsum_tpu.models.granite_hybrid import tiny_granite_h

    return tiny_granite_h(**kw)


# -- the reference against the program ---------------------------------------


@pytest.mark.parametrize("int8", [False, True])
def test_plain_reference_agrees_with_the_cache_free_forward(int8):
    import jax
    import jax.numpy as jnp

    from benchmarks import reference_granite_h as reference
    from vnsum_tpu.models import granite_hybrid as gh
    from vnsum_tpu.models.quant import quantize_params

    cfg = _tiny()
    params = gh.init_params(jax.random.key(5), cfg)
    if int8:
        params = quantize_params(params)
    toks = jax.random.randint(jax.random.key(6), (60,), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = reference.logits(params, toks, family_setup.sizes_from(cfg))
        got = gh.forward_dense(params, cfg, toks[None])[0]
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_reference_is_plain_float32_with_the_recurrence_token_by_token():
    src = (ROOT / "benchmarks" / "reference_granite_h.py").read_text()
    assert 'jax.default_matmul_precision("highest")' in src
    assert "jax.lax.scan(token" in src            # one token a step
    code = src.split('"""', 2)[2]
    imports = [ln for ln in code.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp"]
    for word in ("pallas", "chunk_size", "bfloat16", "cumsum"):
        assert word not in code, word


# -- the run-time parity check -------------------------------------------------


@pytest.fixture(scope="module")
def rehearsal_backend():
    import jax

    from vnsum_tpu.backend.engine import TpuBackend

    config = copy.deepcopy(CONFIG)
    cfg = family_setup.model_config(config, rehearsal=True)
    params = family_setup.start_weights(config, cfg, 11)
    return TpuBackend(
        model_config=cfg, tokenizer="byte", batch_size=2, max_new_tokens=8,
        params=jax.block_until_ready(params),
        **engine_setup.backend_kwargs(config, rehearsal=True))


def _parity(backend, faults=(), config=None, seed=3):
    return family_setup.parity_with_reference(
        backend, config or copy.deepcopy(CONFIG), seed, rehearsal=True,
        faults=faults)


def test_parity_holds_on_the_timed_programs_own_paths(rehearsal_backend):
    got = _parity(rehearsal_backend)
    assert got["ok"] and got["kernel"] and got["state_dtype"] == "float32"
    assert len(got["errors"]) == len(got["state_errors"]) == 5
    assert got["pad"] == 106 and got["bucket"] == 256
    assert 0 < got["error"] <= got["tolerance"]
    assert 0 < got["state_error"] <= got["state_tolerance"]
    assert 0 < got["state_step_error"] <= got["state_step_tolerance"]
    # the limits have room on both sides of what a clean run reads
    assert got["error"] * 1.3 < got["tolerance"]
    assert got["state_error"] * 1.3 < got["state_tolerance"]
    assert got["state_step_error"] * 1.3 < got["state_step_tolerance"]


@pytest.mark.parametrize("fault", ["norm_before_gate",
                                   "no_residual_multiplier", "no_conv_bias",
                                   "no_D", "dt_no_bias"])
def test_parity_catches_a_departure_from_the_equations(fault,
                                                       rehearsal_backend):
    """Every fault of the mixer, the scalars and the residual path. The two
    faults of the attention (``rope``, ``sqrt_scale``) are not here: under
    ``attention_multiplier`` = 1/64 a random draw's scores are flat and
    the logits do not show them (PERF.md section 7);
    tests/test_model_granite_hybrid.py shows them with sharper weights."""
    got = _parity(rehearsal_backend, (fault,))
    assert not got["ok"], got
    assert got["faults"] == [fault]


def test_parity_catches_a_state_kept_a_precision_below(rehearsal_backend):
    """bfloat16 is the nearest precision below the configured float32
    state: the same weights and prompt fail, and by the state's limits —
    the logits hardly show it."""
    import dataclasses

    import jax.numpy as jnp

    from vnsum_tpu.backend.engine import TpuBackend

    clean = _parity(rehearsal_backend)
    config = copy.deepcopy(CONFIG)
    cfg = dataclasses.replace(family_setup.model_config(config, True),
                              state_dtype=jnp.bfloat16)
    below = TpuBackend(
        model_config=cfg, tokenizer="byte", batch_size=2, max_new_tokens=8,
        params=rehearsal_backend.params,
        **engine_setup.backend_kwargs(config, rehearsal=True))
    got = _parity(below)
    assert got["state_dtype"] == "bfloat16" and not got["ok"]
    assert got["state_step_error"] > got["state_step_tolerance"]
    assert got["state_step_error"] > 2 * clean["state_step_error"]
    assert got["error"] <= got["tolerance"]        # not by the logits


def test_one_broken_row_fails_the_check(monkeypatch, rehearsal_backend):
    import numpy as np

    real = rehearsal_backend.prefill_then_decode_logits

    def broken(*a, **kw):
        logits, state = real(*a, **kw)
        logits = np.array(logits)
        logits[2] = logits[2][::-1]
        return logits, state

    monkeypatch.setattr(rehearsal_backend, "prefill_then_decode_logits",
                        broken)
    got = _parity(rehearsal_backend)
    assert not got["ok"] and got["error"] > 1.0
    assert sum(e > got["tolerance"] for e in got["errors"]) == 1


def test_a_prompt_that_fills_its_bucket_is_refused(rehearsal_backend):
    config = copy.deepcopy(CONFIG)
    config["rehearsal"]["parity"]["prompt_tokens"] = 256
    with pytest.raises(ValueError, match="behind a pad"):
        _parity(rehearsal_backend, config=config)


# -- the configuration file -----------------------------------------------------


def test_model_config_builds_the_published_model_uncut():
    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    assert (cfg.n_layers, cfg.n_mamba, cfg.n_attention) == (40, 36, 4)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.intermediate, cfg.vocab_size) == (
        2048, 32, 8, 64, 8192, 100352)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_d_conv, cfg.mamba_chunk_size) == (64, 64, 128, 4, 256)
    assert cfg.layer_types == tuple(PERIOD * 4) and cfg.tie_embeddings
    assert cfg.max_seq_len == 8448
    kw = engine_setup.backend_kwargs(CONFIG, rehearsal=False)
    assert kw["quantize"] and kw["quantize_act"] and kw["quantize_kv"] is True
    assert kw["prefill_chunk_tokens"] == 2048
    assert family_setup.sizes_from(cfg) == family_setup.sizes_of(CONFIG, False)
    tiny = family_setup.model_config(CONFIG, rehearsal=True)
    assert (tiny.n_layers, tiny.n_attention, tiny.mamba_chunk_size) == (
        20, 2, 8)
    assert tiny == _tiny(vocab_size=640, max_seq_len=640)


@pytest.mark.parametrize("key, value, text", [
    ("position_embedding_type", "rope", "this family builds 'nope'"),
    ("num_local_experts", 8, "this family builds 0"),
    ("mamba_proj_bias", True, "this family builds False"),
    ("mamba_expand", 4, "stated two ways"),
    ("shared_intermediate_size", 4096, "stated two ways"),
])
def test_a_mechanism_the_family_does_not_build_is_refused(key, value, text):
    config = copy.deepcopy(CONFIG)
    config[key] = value
    with pytest.raises(ValueError, match=text):
        family_setup.sizes_of(config, False)


def test_config_file_keeps_every_published_key_and_reduces_nothing():
    c = CONFIG
    entry = next(e for e in BENCH["configs"] if e["name"] == c["name"])
    assert entry["reduced"] == c["reduced"] == [] and c["published"] == {}
    for key, value in PUBLISHED.items():
        assert c[key] == value, key
    assert c["head_dim"] == 64 == c["hidden_size"] // c["num_attention_heads"]
    assert entry["source"] == c["source"]
    assert c["source"].endswith("granite-4.0-h-micro/blob/main/config.json")
    for key in ("assumed", "deployment", "bytes", "engine_notes", "engine",
                "reference", "setup_module", "checkpoint_notes"):
        assert c[key], key
    for key in ("head_dim", "gated_norm", "dt_limits", "state_precision",
                "random_weights", "attention", "feed_forward", "embedding",
                "chunked_scan"):
        assert key in c["assumed"], key
    assert "whole model on one accelerator" in c["deployment"]
    assert c["checkpoint_seed"] == 43
    assert c["setup_module"] == "engine_setup_granite_h"
    assert c["engine"] == {
        "weights": "int8", "activations": "int8", "kv": "int8",
        "state": "float32", "prefill_chunk_tokens": 2048, "batch": 24,
        "max_seq_len": 8448}
    parity = c["reference"]["parity"]
    assert parity["bucket"] == 8192 and parity["decode_steps"] == 8
    # behind a left pad, and past three prefill chunks: the state crosses
    # all four
    assert 3 * 2048 < parity["prompt_tokens"] < 8192
    for limit in ("tolerance", "state_tolerance", "state_step_tolerance"):
        assert 0 < parity[limit] < 1 and limit in parity["what"], limit
        assert 0 < c["rehearsal"]["parity"][limit] < 1


def test_config_files_keys_are_the_catalog_rows():
    """Every number of the catalog entry's config under the same key."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "granite-4.0-h-micro")
    assert CONFIG["source"] == row["source_url"]
    assert row["config"] == PUBLISHED
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key


def test_config_files_byte_arithmetic_is_the_models():
    import jax

    from vnsum_tpu.models.granite_hybrid import init_cache
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    tree = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                          jax.random.key(0))
    size = lambda t: sum(a.size * a.dtype.itemsize  # noqa: E731
                         for a in jax.tree.leaves(t))
    b, m = CONFIG["bytes"], tree["mamba"]
    assert b["mamba_in_proj"] == sum(
        size(m[part]) for part in ("in_z", "in_xbc", "in_dt")) // 36 \
        == 2048 * 8512 + 4 * 8512
    assert b["mamba_out_proj"] == size(m["out_proj"]) // 36 \
        == 4096 * 2048 + 4 * 2048
    assert b["mamba_conv"] == (size(m["conv_w"]) + size(m["conv_b"])) // 36 \
        == 4352 * 5 * 4
    assert b["mamba_mixer"] == size(m) // 36 == (
        b["mamba_in_proj"] + b["mamba_out_proj"] + b["mamba_conv"]
        + b["mamba_vectors_and_norms"])
    assert b["attention_mixer"] == size(tree["attn"]) // 4
    assert b["feed_forward_a_layer"] == size(tree["layers"]) // 40
    assert b["mamba_layer"] == b["mamba_mixer"] + b["feed_forward_a_layer"]
    assert b["attention_layer"] == (b["attention_mixer"]
                                    + b["feed_forward_a_layer"])
    assert b["layers_40"] == 36 * b["mamba_layer"] + 4 * b["attention_layer"]
    assert b["embedding_and_head"] == size(tree["embed"]) \
        == 100352 * 2048 + 4 * 100352
    assert "lm_head" not in tree
    assert b["weights"] == size(tree) == (
        b["layers_40"] + b["embedding_and_head"] + size(tree["final_norm"]))
    assert 3.19e9 < b["weights"] < 3.21e9     # the issue's reckoning: 3.19 GB
    row = jax.eval_shape(lambda: init_cache(cfg, 1, 8448, quantized=True))
    assert sum(size(row[n]) for n in ("k", "v", "ks", "vs")) \
        == b["kv_cache_a_row"]
    assert size(row["conv"]) == b["conv_tail_a_row"]
    assert size(row["ssm"]) == b["recurrent_state_a_row"]
    assert b["recurrent_state_a_row"] == 36 * 64 * 64 * 128 * 4
    assert b["kv_cache_a_row"] == 4 * 8 * 8448 * (2 * 64 + 8)
    s = family_setup.sizes_of(CONFIG, False)
    assert roof.mamba_params(s) == 2048 * 8512 + 4096 * 2048
    assert roof.attention_params(s) == 2048 * 64 * (32 + 16) + 32 * 64 * 2048
    assert roof.ffn_params(s) == 3 * 2048 * 8192
    assert roof.state_bytes_a_row(s) == b["recurrent_state_a_row"]


# -- the rooflines ----------------------------------------------------------------

SIZES = family_setup.sizes_of(CONFIG, False)
PEAKS = {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_per_s": 819e9}
PRECISION = {"weights": 1, "kv": 1, "prefill_matmul": "int8"}
MAMBA = 2048 * 8512 + 4096 * 2048
ATTN = 2048 * 64 * 48 + 2048 * 2048
FFN = 3 * 2048 * 8192
TOKEN = 36 * MAMBA + 4 * ATTN + 40 * FFN
STATE = 36 * 4096 * 128            # elements of one row's recurrent state


def test_scan_and_contexts_by_hand():
    assert roof.layers_of(SIZES, "mamba") == 36
    assert roof.layers_of(SIZES, "attention") == 4
    assert roof.token_params(SIZES) == TOKEN
    scan = roof.scan_a_token(SIZES)
    # chunk 256, state 128, inner 4096: the masked product, the readout and
    # the update, C B^T
    assert scan["ops"] == 2 * 256 * 4096 + 4 * 128 * 4096 + 2 * 256 * 128 \
        == 4_259_840
    assert scan["bytes"] == (2 * 4096 + 2 * 128) * 2 + 3 * 4 * 64 == 17_664
    # a row of 3 tokens, 4 steps: 4 + 5 + 6 + 7 slots
    assert roof.decode_context([3], 4) == 22
    assert roof.decode_context([3, 9], 2) == (4 + 5) + (10 + 11)


def test_kernel_rooflines_against_hand_worked_numbers():
    lens, steps = [7800, 5000], 256
    k = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, None, lens, steps)
    scanned = 12800 * 36
    assert k["ssd_prefill_scan"] == {
        "seconds": pytest.approx(4_259_840 * scanned / 197e12),
        "bound": "compute"}
    assert 17_664 * scanned / 819e9 < k["ssd_prefill_scan"]["seconds"]
    # every row's state read and written once a step
    assert k["ssm_decode_update"] == {
        "seconds": pytest.approx(2 * 4 * STATE * 2 * steps / 819e9),
        "bound": "memory"}
    pairs = sum(n * (n + 1) // 2 for n in lens)
    assert roof.prefill_attention_ops(SIZES, lens) == 4 * 32 * 64 * 4 * pairs
    assert k["flash_prefill_attention"] == {
        "seconds": pytest.approx(4 * 32 * 64 * 4 * pairs / 197e12),
        "bound": "compute"}
    ctx = 4 * sum(steps * (n + 1) + steps * (steps - 1) // 2 for n in lens)
    dec = roof.decode_attention(SIZES, lens, steps, 1)
    assert dec == {"ops": 4 * 32 * 64 * ctx, "bytes": 8 * (2 * 64 + 8) * ctx}
    assert k["flash_decode_attention"] == {
        "seconds": pytest.approx(dec["bytes"] / 819e9), "bound": "memory"}
    assert roof.decode_attention(SIZES, lens, steps, 2)["bytes"] == \
        8 * 2 * 64 * 2 * ctx


def test_dispatch_roofline_adds_up_by_hand():
    lens, steps = [7800, 5000], 256
    d = roof.dispatch(SIZES, PRECISION, PEAKS, None, lens, steps)
    head = 2048 * 100_352
    assert d["prefill_matmul_ops"] == 2 * TOKEN * 12800 + 2 * head * 2
    k = d["kernels"]
    assert d["prefill_s"] == pytest.approx(
        d["prefill_matmul_ops"] / 393e12 + k["ssd_prefill_scan"]["seconds"]
        + k["flash_prefill_attention"]["seconds"])
    dec = roof.decode_attention(SIZES, lens, steps, 1)
    assert d["decode_state_bytes"] == 2 * 4 * STATE * 2 * steps
    assert d["decode_bytes"] == (
        (TOKEN + head) * steps + d["decode_state_bytes"] + dec["bytes"])
    assert d["decode_s"] == pytest.approx(d["decode_bytes"] / 819e9)
    assert d["total_s"] == pytest.approx(d["prefill_s"] + d["decode_s"])
    # at the cell's 24 rows the state is 48% of a step's bytes
    full = roof.dispatch(SIZES, PRECISION, PEAKS, None, [7800] * 24, steps)
    assert 0.46 < full["decode_state_bytes"] / full["decode_bytes"] < 0.50
    # the issue's reckoning of a map dispatch: 1.12e15 operations, 2.84 s
    assert full["prefill_matmul_ops"] == pytest.approx(1.12e15, rel=0.01)
    assert full["decode_s"] == pytest.approx(2.39, rel=0.01)


# -- the readers --------------------------------------------------------------------


def _raw():
    return {
        "device": {"kind": "TPU v5 lite"}, "sizes": SIZES,
        "precision": PRECISION,
        "counts": {"experts": None,
                   "prefill_blocks": {"interior": 10, "edge": 4,
                                      "scan_tokens_real": 36 * 7800,
                                      "scan_tokens_computed": 36 * 7936}},
        "trace": {"busy_s": 10.0, "modules": {"jit_generate": 9.0},
                  "module_calls": {"jit_generate": 1},
                  "device_ops": [["ssd_prefill_scan", 0.5],
                                 ["flash_prefill_attention", 0.25],
                                 ["fusion.7", 0.3]]},
        "traced": {"dispatches": [
            {"prompt_lens": [7800, 5000], "steps": 256, "experts": None},
            {"prompt_lens": [2000], "steps": 256, "experts": None}]},
    }


def _read(name, raw):
    spec = cells.load_layer_metric(name)
    return cells.load_module("readers", spec["reader"]).read(spec, raw)


def test_new_metrics_on_a_known_record():
    raw = _raw()
    least = roof.kernel_least_seconds(
        SIZES, PRECISION, PEAKS, None, [7800, 5000], 256)
    assert _read("ssd_prefill_scan_roofline", raw) == pytest.approx(
        100 * least["ssd_prefill_scan"]["seconds"] / 0.5)
    assert _read("hd64_prefill_attention_roofline", raw) == pytest.approx(
        100 * least["flash_prefill_attention"]["seconds"] / 0.25)
    assert _read("ssm_decode_update_roofline", raw) is None
    assert _read("hd64_decode_attention_roofline", raw) is None
    assert _read("ssm_busy_share", raw) is None
    raw["trace"]["device_ops"] += [["ssm_decode_update", 1.5],
                                   ["flash_decode_attention", 0.4],
                                   ["while", 0.1]]
    assert _read("ssm_busy_share", raw) == pytest.approx(20.0)
    # what the profiler lost inside a loop is counted against the kernel
    assert _read("ssm_decode_update_roofline", raw) == pytest.approx(
        100 * least["ssm_decode_update"]["seconds"] / 1.6)
    assert _read("hd64_decode_attention_roofline", raw) == pytest.approx(
        100 * least["flash_decode_attention"]["seconds"] / 0.5)
    whole = roof.dispatch(SIZES, PRECISION, PEAKS, None, [7800, 5000], 256)
    assert _read("generate_roofline_share_ssm", raw) == pytest.approx(
        100 * whole["total_s"] / 9.0)
    assert _read("ssm_scan_tokens_computed_over_real", raw) == \
        pytest.approx(7936 / 7800)
    # two whole executions: both dispatches counted
    raw["trace"]["module_calls"]["jit_generate"] = 2
    both = whole["total_s"] + roof.dispatch(
        SIZES, PRECISION, PEAKS, None, [2000], 256)["total_s"]
    assert _read("generate_roofline_share_ssm", raw) == pytest.approx(
        100 * both / 9.0)


def test_the_state_readers_serve_dispatches_with_counters_too():
    """The same arithmetic as the ``family_*`` readers where every dispatch
    has expert counters: the Laguna cell's record through both pairs."""
    from benchmarks import engine_setup_laguna

    laguna = cells.load_config(BENCH, "laguna-s-2.1-l5-int8")
    experts = {"slots_routed": 1000, "slots_held": 1000,
               "decode_touched": 163840, "decode_layer_steps": 1024}
    raw = _raw()
    raw["sizes"] = engine_setup_laguna.sizes_of(laguna, False)
    for d in raw["traced"]["dispatches"]:
        d["experts"] = experts
    spec = dict(cells.load_layer_metric("laguna_prefill_attention_roofline"))
    old = cells.load_module("readers", "family_kernel_roofline").read(spec, raw)
    new = cells.load_module("readers", "state_kernel_roofline").read(spec, raw)
    assert old == new and old is not None
    spec = dict(cells.load_layer_metric("generate_roofline_share_laguna"))
    old = cells.load_module("readers", "family_dispatch_roofline").read(
        spec, raw)
    new = cells.load_module("readers", "state_dispatch_roofline").read(
        spec, raw)
    assert old == new and old is not None


def test_readers_with_nothing_to_read_leave_their_metric_out():
    """As on the parent commit, whose program has no such family, kernel or
    counter: None, never an exception."""
    bare = {"device": {"kind": "TPU v5 lite"}, "counts": {}, "trace": None,
            "traced": None}
    for m in cells.metrics_for(BENCH, "per_layer", CELL):
        if m["name"] not in ("host_share.offline",):
            assert _read(m["name"], bare) is None, m["name"]
    # a program that counts its attention cells alone (the parent commit's)
    raw = _raw()
    raw["counts"]["prefill_blocks"] = {"interior": 10, "edge": 4}
    assert _read("ssm_scan_tokens_computed_over_real", raw) is None
    # a checkout without the family's roofline module
    spec = dict(cells.load_layer_metric("ssd_prefill_scan_roofline"),
                roofline="roofline_of_no_such_family")
    reader = cells.load_module("readers", "state_kernel_roofline")
    assert reader.read(spec, raw) is None
    whole = cells.load_module("readers", "state_dispatch_roofline")
    assert whole.read(dict(spec, modules=["jit_generate"]), raw) is None
    # no whole execution in the stretch
    raw["trace"]["module_calls"] = {}
    assert _read("ssd_prefill_scan_roofline", raw) is None
    assert _read("generate_roofline_share_ssm", raw) is None


def test_the_cell_lists_its_own_metrics_and_those_it_shares():
    """By membership: where in ``per_layer`` an entry stands is the
    driver's to check, not this file's."""
    mine = {m["name"] for m in cells.metrics_for(BENCH, "per_layer", CELL)}
    own = {"generate_roofline_share_ssm", "ssd_prefill_scan_roofline",
           "ssm_decode_update_roofline", "hd64_prefill_attention_roofline",
           "hd64_decode_attention_roofline", "ssm_busy_share",
           "ssm_scan_tokens_computed_over_real"}
    shared = {"host_share.offline", "generate_device_s_per_dispatch",
              "device_idle.offline", "idle_in_engine_host.offline",
              "idle_in_pipeline_host.offline", "idle_unexplained.offline"}
    assert mine == own | shared
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in own:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "docs_per_min"
        assert m["layer"] == "model and kernels"
        spec = cells.load_layer_metric(name)
        assert spec["drivers"] == ["offline_pipeline_family"]
        if "roofline" in spec:
            assert spec["roofline"] == "roofline_granite_h"
            assert spec["reader"].startswith("state_")
        if name.endswith("_roofline"):
            assert m["unit"] == "%" and m["better"] == "higher"
    assert {m["name"] for m in cells.metrics_for(BENCH, "end_to_end", CELL)
            } == {"docs_per_min", "setup_s"}
    assert cells.validate(BENCH, ROOT) == []
    cell = cells.find_cell(BENCH, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell == BENCH["workloads"][-1]
    assert BENCH["configs"][-1]["name"] == cell["config"]
    assert len(BENCH["configs"]) == 6 and len(BENCH["workloads"]) == 7
    traffic = cells.load_traffic("offline-mapreduce-8k-ssm")
    base = cells.load_traffic("offline-mapreduce-8k")
    for key in ("doc_tokens", "chunks_per_doc", "chunk_size", "chunk_overlap",
                "token_max", "max_new_tokens", "bpe_vocab", "bpe_train_words",
                "warmup_reduce_summaries", "approach", "rehearsal"):
        assert traffic[key] == base[key], key
    assert traffic["driver"] == "offline_pipeline_family"
    assert traffic["min_group_seconds"] > 0 and traffic["trace_seconds"] > 0


def test_the_driver_finds_this_familys_setup_module():
    import importlib

    mod = importlib.import_module(f"benchmarks.{CONFIG['setup_module']}")
    for fn in ("model_config", "start_weights", "sizes_of",
               "parity_with_reference"):
        assert callable(getattr(mod, fn)), fn


# -- the cell, rehearsed ----------------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    """The whole cell at a tiny size on the CPU, all four kernels
    interpreted: the driver, the family's set-up, parity, warm-up, a
    window, the readers."""
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 43), "--seconds", "2", "--trace",
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
        assert set(counted) == {"ssm_scan_tokens_computed_over_real"}
        assert 1.0 <= counted["ssm_scan_tokens_computed_over_real"] < 1.05
