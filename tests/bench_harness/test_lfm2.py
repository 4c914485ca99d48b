"""CPU tests of the benchmark's own parts for the LFM2-MoE family: the
plain reference against the program, the run-time parity check and what it
has to catch (a fault of the equations), the rooflines against hand-worked
numbers, the readers on a known record, the cell's rehearsal, and the
configuration file's keys and arithmetic.

The cell, its configuration and its metrics are found by MEMBERSHIP: where
an entry stands in a list, and how many entries a list has, is the driver's
to check and the next cell's to change.

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
from benchmarks import engine_setup_lfm2 as family_setup  # noqa: E402
from benchmarks import roofline_lfm2 as roof  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
NAME = "lfm2-8b-a1b-int8"
CONFIG = cells.load_config(BENCH, NAME)
TRAFFIC = "offline-mapreduce-8k-conv-moe"
CELL = f"{NAME}.{TRAFFIC}"
ATTENTION_AT = (2, 6, 10, 14, 18, 21)
OWN = {"generate_roofline_share_lfm2", "lfm2_expert_matmul_roofline",
       "lfm2_prefill_attention_roofline", "lfm2_decode_attention_roofline",
       "shortconv_tokens_computed_over_real"}
SHARED = {"host_share.offline", "generate_device_s_per_dispatch",
          "device_idle.offline", "idle_in_engine_host.offline",
          "idle_in_pipeline_host.offline", "idle_unexplained.offline",
          "expert_ffn_busy_share", "expert_load_max_over_mean",
          "expert_distinct_per_step"}


def _tiny(**kw):
    from vnsum_tpu.models.lfm2 import tiny_lfm2

    return tiny_lfm2(**kw)


# -- the reference against the program ---------------------------------------


@pytest.mark.parametrize("int8", [False, True])
def test_plain_reference_agrees_with_the_cache_free_forward(int8):
    import jax
    import jax.numpy as jnp

    from benchmarks import reference_lfm2 as reference
    from vnsum_tpu.models import lfm2
    from vnsum_tpu.models.quant import quantize_params

    cfg = _tiny()
    params = lfm2.init_params(jax.random.key(5), cfg)
    params["layers"]["router"] = params["layers"]["router"] * 10.0
    if int8:
        params = quantize_params(params)
    toks = jax.random.randint(jax.random.key(6), (60,), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = reference.logits(params, toks, family_setup.sizes_from(cfg))
        got = lfm2.forward_dense(params, cfg, toks[None])[0]
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_reference_is_plain_float32_and_reads_nothing_of_the_program():
    src = (ROOT / "benchmarks" / "reference_lfm2.py").read_text()
    code = src.split('"""', 2)[2]
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in code
    assert "for j in range(K)" in code          # the taps, an explicit sum
    assert "fori_loop(0, held, one_expert" in code   # ONE expert at a time
    for word in ("pallas", "bfloat16", "import vnsum", "from vnsum",
                 "lax.conv"):
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
    assert 0 < got["last_row_error"] <= got["decode_tolerance"]
    assert got["last_row_error"] == got["errors"][-1]
    assert 0 < got["state_error"] <= got["state_tolerance"]
    assert got["first_layer_picks_ok"]
    # every real token on 8 sparse layers x 2 picks, all held
    assert got["slots_routed"] == got["slots_held"] == 154 * 8 * 2
    # the limits have room on both sides of what a clean run reads
    assert got["error"] * 1.3 < got["tolerance"]
    assert got["last_row_error"] * 1.3 < got["decode_tolerance"]
    assert got["state_error"] * 1.3 < got["state_tolerance"]


@pytest.mark.parametrize("fault", [
    "conv_silu", "conv_bias", "conv_of_x", "gate_order", "softmax_router",
    "no_bias", "no_renorm", "rope_before_norm", "capacity"])
def test_parity_catches_a_departure_from_the_equations(fault,
                                                       rehearsal_backend):
    """The faults of the operators, the router and the experts, on the int8
    engine the cell times. Not here: ``bias_in_weight`` (a tiny router's
    picked scores renormalise to nearly what they were: 0.072 / 0.051 for
    0.048 / 0.038 clean); tests/test_model_lfm2.py shows all ten in the
    logits with sharper weights."""
    got = _parity(rehearsal_backend, (fault,))
    assert not got["ok"], got
    assert got["faults"] == [fault]


def test_a_tail_that_holds_something_else_fails_by_the_tails_limit(
        rehearsal_backend):
    """``conv_of_x``: the reference's tail holds ``x`` and not ``b * x``;
    the first convolution layer's tail reads far past its limit."""
    got = _parity(rehearsal_backend, ("conv_of_x",))
    assert got["state_error"] > 10 * got["state_tolerance"]


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


def test_picks_outside_the_band_fail_the_check(monkeypatch,
                                               rehearsal_backend):
    """A router that picks by another rule: the first sparse layer's picks
    of one scored row replaced by other experts are no rightful top-k
    within the band, and the check says so whatever the logits."""
    import numpy as np

    real = rehearsal_backend.prefill_then_decode_logits

    def other_picks(*a, **kw):
        logits, state = real(*a, **kw)
        picks = np.array(state["rows"]["picks"])
        picks[1, 0, 0] = (picks[1, 0, 0] + 3) % 8
        return logits, {**state, "rows": {**state["rows"], "picks": picks}}

    monkeypatch.setattr(rehearsal_backend, "prefill_then_decode_logits",
                        other_picks)
    got = _parity(rehearsal_backend)
    assert not got["first_layer_picks_ok"] and not got["ok"]
    assert got["first_layer_rows_differing"] >= 1


def test_a_prompt_that_fills_its_bucket_is_refused(rehearsal_backend):
    config = copy.deepcopy(CONFIG)
    config["rehearsal"]["parity"]["prompt_tokens"] = 256
    with pytest.raises(ValueError, match="behind a pad"):
        _parity(rehearsal_backend, config=config)


# -- the configuration file -----------------------------------------------------


def test_model_config_builds_the_published_widths_at_24_layers():
    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    assert (cfg.n_layers, cfg.n_conv, cfg.n_attention, cfg.n_sparse,
            cfg.num_dense_layers) == (24, 18, 6, 22, 2)
    assert tuple(i for i, k in enumerate(cfg.layer_types)
                 if k == "full_attention") == ATTENTION_AT
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.vocab_size) == (2048, 32, 8, 64, 65536)
    assert (cfg.intermediate, cfg.moe_intermediate, cfg.n_routed_experts,
            cfg.n_held, cfg.num_experts_per_tok, cfg.routed_scaling_factor,
            cfg.conv_L_cache) == (7168, 1792, 32, 32, 4, 1, 3)
    assert cfg.tie_embeddings and cfg.max_seq_len == 8448
    assert cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-5
    kw = engine_setup.backend_kwargs(CONFIG, rehearsal=False)
    assert kw["quantize"] and kw["quantize_act"] and kw["quantize_kv"] is True
    assert kw["prefill_chunk_tokens"] == CONFIG["engine"][
        "prefill_chunk_tokens"]
    sizes = family_setup.sizes_of(CONFIG, False)
    assert family_setup.sizes_from(cfg) == {**sizes, "expert_offset": 0}
    tiny = family_setup.model_config(CONFIG, rehearsal=True)
    assert tiny == _tiny(vocab_size=640, max_seq_len=640)


@pytest.mark.parametrize("key, value, text", [
    ("conv_bias", True, "conv_bias true"),
    ("use_expert_bias", False, "use_expert_bias"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("model_type", "lfm2", "this family builds 'lfm2_moe'"),
    ("norm_eps", 1e-6, "stated two ways"),
    ("num_dense_layers", 25, "past the depth"),
])
def test_a_mechanism_the_family_does_not_build_is_refused(key, value, text):
    config = copy.deepcopy(CONFIG)
    config[key] = value
    with pytest.raises(ValueError, match=text):
        family_setup.model_config(config, False)


def _catalog_row():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    return next(r for r in map(json.loads, catalog.read_text().splitlines())
                if r["name"] == "LFM2-8B-A1B")


def test_config_files_keys_are_the_catalog_rows():
    """Every key of the catalog entry's config under the same name at the
    same value: nothing is reduced."""
    row = _catalog_row()
    assert CONFIG["source"] == row["source_url"]
    assert len(row["config"]) == 20
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key


def test_config_file_keeps_every_width_and_reduces_nothing():
    c = CONFIG
    entry = next(e for e in BENCH["configs"] if e["name"] == NAME)
    assert entry["reduced"] == c["reduced"] == []
    assert entry["source"] == c["source"]
    assert c["source"].endswith("LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert len(entry["why"]) <= 200
    for key, value in {
            "hidden_size": 2048, "num_hidden_layers": 24,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "intermediate_size": 7168, "moe_intermediate_size": 1792,
            "num_experts": 32, "num_experts_per_tok": 4,
            "num_dense_layers": 2, "conv_L_cache": 3, "conv_bias": False,
            "routed_scaling_factor": 1, "use_expert_bias": True,
            "norm_topk_prob": True, "vocab_size": 65536,
            "rope_theta": 1000000, "norm_eps": 1e-5,
            "max_position_embeddings": 128000,
            "model_type": "lfm2_moe"}.items():
        assert c[key] == value, key
    assert len(c["layer_types"]) == 24
    assert tuple(i for i, k in enumerate(c["layer_types"])
                 if k == "full_attention") == ATTENTION_AT
    for key in ("assumed", "deployment", "bytes", "engine_notes", "engine",
                "reference", "setup_module", "checkpoint_notes"):
        assert c[key], key
    # sizes no key of config.json states, under the harness's names
    assert (c["head_dim"], c["tie_word_embeddings"], c["rms_norm_eps"]) == (
        64, True, 1e-5)
    for key in ("head_dim", "tie_word_embeddings", "rms_norm_eps",
                "conv_operator", "norms", "attention", "router",
                "tail_precision", "random_weights"):
        assert key in c["assumed"], key
    assert "WHOLE on ONE accelerator" in c["deployment"]
    assert "32 of 32 experts" in c["deployment"]
    assert c["checkpoint_seed"] == 54 and c["chips"] == 1
    assert c["mesh"] is None
    assert c["setup_module"] == "engine_setup_lfm2"
    assert c["registry_name"] == "lfm2-8b-a1b"
    engine = c["engine"]
    assert {k: engine[k] for k in ("weights", "activations", "kv", "state",
                                   "max_seq_len")} == {
        "weights": "int8", "activations": "int8", "kv": "int8",
        "state": "bfloat16", "max_seq_len": 8448}
    # a group's 24 map prompts are whole dispatches of the batch
    assert 24 % engine["batch"] == 0 and engine["batch"] >= 8
    assert engine["prefill_chunk_tokens"] in (1024, 2048)
    parity = c["reference"]["parity"]
    assert parity["bucket"] == 8192 and parity["decode_steps"] == 8
    # behind a left pad, and past all but the last prefill chunk
    assert 8192 - engine["prefill_chunk_tokens"] < parity["prompt_tokens"] \
        < 8192
    for limit in ("tolerance", "decode_tolerance", "state_tolerance",
                  "tie_band"):
        assert 0 < parity[limit] <= 1 and limit in parity["what"], limit
        assert 0 < c["rehearsal"]["parity"][limit] < 1


def test_config_files_byte_arithmetic_is_the_models():
    import jax

    from vnsum_tpu.models.lfm2 import init_cache
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    tree = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                          jax.random.key(0))
    size = lambda t: sum(a.size * a.dtype.itemsize  # noqa: E731
                         for a in jax.tree.leaves(t))
    b, conv, e = CONFIG["bytes"], tree["conv"], tree["layers"]
    assert b["conv_in_proj"] == sum(
        size(conv[part]) for part in ("in_b", "in_c", "in_x")) // 18 \
        == 3 * (2048 * 2048 + 4 * 2048)
    assert b["conv_out_proj"] == size(conv["out_proj"]) // 18 \
        == 2048 * 2048 + 4 * 2048
    assert b["conv_taps"] == size(conv["conv_w"]) // 18 == 2048 * 3 * 4
    assert b["conv_layer"] == size(conv) // 18
    assert b["attention_layer"] == size(tree["attn"]) // 6
    assert b["dense_ffn"] == size(tree["dense"]) // 2
    # an expert: three matrices, a float32 scale a column
    assert b["routed_expert"] == sum(
        size(e[n]) for n in ("we_gate", "we_up", "we_down")) // 22 // 32 \
        == 3 * 2048 * 1792 + 4 * (2 * 1792 + 2048)
    assert b["routed_experts_a_layer"] == 32 * b["routed_expert"]
    assert b["sparse_layer"] == size(e) // 22 \
        == b["routed_experts_a_layer"] + b["router_bias_and_norm"]
    assert b["layers_24"] == (18 * b["conv_layer"] + 6 * b["attention_layer"]
                              + 2 * b["dense_ffn"] + 22 * b["sparse_layer"])
    assert b["embedding_and_final_norm"] == (
        size(tree["embed"]) + size(tree["final_norm"]))
    assert "lm_head" not in tree
    assert b["weights"] == size(tree) == (b["layers_24"]
                                          + b["embedding_and_final_norm"])
    # the issue's reckoning: ~8.36 GB
    assert 8.35e9 < b["weights"] < 8.37e9
    row = jax.eval_shape(lambda: init_cache(cfg, 1, 8448, quantized=True))
    assert sum(size(row[n]) for n in ("k", "v", "ks", "vs")) \
        == b["kv_cache_a_row"] == 6 * 8 * 8448 * (2 * 64 + 8)
    assert size(row["conv"]) == b["conv_tail_a_row"] == 18 * 2 * 2048 * 2
    s = family_setup.sizes_of(CONFIG, False)
    assert roof.tail_bytes_a_row(s) == b["conv_tail_a_row"]
    assert roof.expert_params(s) == 3 * 2048 * 1792
    assert roof.conv_params(s) == 4 * 2048 * 2048


# -- the rooflines ----------------------------------------------------------------

SIZES = family_setup.sizes_of(CONFIG, False)
PEAKS = {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_per_s": 819e9}
PRECISION = {"weights": 1, "kv": 1, "prefill_matmul": "int8"}
CONV = 4 * 2048 * 2048
ATTN = 2048 * 64 * (32 + 16) + 32 * 64 * 2048
DENSE = 3 * 2048 * 7168
EXPERT = 3 * 2048 * 1792
ROUTER = 2048 * 32
FIXED = 18 * CONV + 6 * ATTN + 2 * DENSE + 22 * ROUTER
HEAD = 2048 * 65536
TAILS = 18 * 2 * 2048 * 2           # bytes of one row's tails
EXPERTS = {"slots_routed": 1000, "slots_held": 1000,
           "decode_touched": 22 * 256 * 30, "decode_layer_steps": 22 * 256}


def test_params_and_the_element_wise_pass_by_hand():
    assert roof.layers_of(SIZES, "conv") == 18
    assert roof.layers_of(SIZES, "full_attention") == 6
    assert roof.sparse_layers(SIZES) == 22
    assert roof.conv_params(SIZES) == CONV
    assert roof.attention_params(SIZES) == ATTN
    assert roof.dense_ffn_params(SIZES) == DENSE
    assert roof.expert_params(SIZES) == EXPERT
    assert roof.fixed_params(SIZES) == FIXED
    assert roof.params_a_token(SIZES, 1.0) == FIXED + 22 * 4 * EXPERT
    assert roof.params_a_token(SIZES, 0.25) == FIXED + 22 * EXPERT
    # the issue's reckoning: 1.42 G multiply-adds a real token
    assert 1.41e9 < roof.params_a_token(SIZES, 1.0) < 1.43e9
    # b, c, x read and the gated result written, bf16, 18 layers
    assert roof.shortconv_bytes_a_token(SIZES) == 4 * 2048 * 2 * 18
    assert roof.tail_bytes_a_row(SIZES) == TAILS
    assert roof.decode_context([10, 20], 3) == (11 + 12 + 13) + (21 + 22 + 23)


def test_kernel_rooflines_against_hand_worked_numbers():
    lens, steps = [7800, 5000], 256
    got = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, EXPERTS, lens,
                                    steps)
    assert set(got) == {"flash_prefill_attention", "flash_decode_attention",
                        "expert_grouped_matmul"}
    tokens = sum(lens)
    pairs = sum(n * (n + 1) // 2 for n in lens)
    assert got["flash_prefill_attention"]["seconds"] == pytest.approx(
        4 * 32 * 64 * 6 * pairs / 197e12)
    ctx = roof.decode_context(lens, steps) * 6
    assert got["flash_decode_attention"]["seconds"] == pytest.approx(max(
        4 * 32 * 64 * ctx / 197e12, 8 * (2 * 64 + 8) * ctx / 819e9))
    assert got["flash_decode_attention"]["bound"] == "memory"
    # the experts: four a token and sparse layer at the int8 peak, then each
    # step the 30 experts a layer it touched, read once
    prefill = 2 * EXPERT * 4 * 22 * tokens / 393e12
    decode = max(2 * EXPERT * 4 * 22 * 2 * steps / 393e12,
                 EXPERT * 30 * 22 * steps / 819e9)
    assert got["expert_grouped_matmul"]["seconds"] == pytest.approx(
        prefill + decode)
    assert got["expert_grouped_matmul"]["bound"] == "compute, then memory"


def test_dispatch_roofline_adds_up_by_hand():
    lens, steps = [7800, 5000], 256
    got = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, lens, steps)
    kernels = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, EXPERTS,
                                        lens, steps)
    tokens, token = sum(lens), FIXED + 22 * 4 * EXPERT
    assert got["prefill_matmul_ops"] == 2 * token * tokens + 2 * HEAD * 2
    assert got["shortconv_bytes"] == 4 * 2048 * 2 * 18 * tokens
    assert got["prefill_s"] == pytest.approx(
        got["prefill_matmul_ops"] / 393e12
        + got["shortconv_bytes"] / 819e9
        + kernels["flash_prefill_attention"]["seconds"])
    ctx = roof.decode_context(lens, steps) * 6
    assert got["decode_tail_bytes"] == 2 * TAILS * 2 * steps
    assert got["decode_expert_bytes"] == EXPERT * 30 * 22 * steps
    assert got["decode_bytes"] == pytest.approx(
        (FIXED + HEAD) * steps + EXPERT * 30 * 22 * steps
        + 2 * TAILS * 2 * steps + 8 * (2 * 64 + 8) * ctx)
    assert got["decode_s"] == pytest.approx(max(
        got["decode_bytes"] / 819e9, got["decode_ops"] / 197e12))
    assert got["total_s"] == got["prefill_s"] + got["decode_s"]
    # a share of the experts held elsewhere takes its operations along
    half = roof.dispatch(SIZES, PRECISION, PEAKS,
                         {**EXPERTS, "slots_held": 500}, lens, steps)
    assert half["prefill_matmul_ops"] == pytest.approx(
        2 * (FIXED + 22 * 2 * EXPERT) * tokens + 2 * HEAD * 2)


# -- the readers --------------------------------------------------------------------


def _raw():
    dispatch = {"prompt_lens": [7800, 5000], "steps": 256, "experts": EXPERTS}
    return {
        "device": {"kind": "TPU v5 lite"}, "sizes": SIZES,
        "precision": PRECISION,
        "counts": {"experts": {**EXPERTS, "tokens": [[3, 1], [2, 2]],
                               "decode_reads_possible": 22 * 256 * 32},
                   "prefill_blocks": {"interior": 10, "edge": 4,
                                      "conv_tokens_real": 18 * 7800,
                                      "conv_tokens_computed": 18 * 8192}},
        "trace": {"busy_s": 10.0, "modules": {"jit_generate": 9.0},
                  "module_calls": {"jit_generate": 1},
                  "device_ops": [["flash_prefill_attention", 0.25],
                                 ["expert_grouped_matmul", 2.0],
                                 ["fusion.7", 0.3]]},
        "traced": {"dispatches": [dispatch,
                                  {**dispatch, "prompt_lens": [2000]}]},
    }


def _read(name, raw):
    spec = cells.load_layer_metric(name)
    return cells.load_module("readers", spec["reader"]).read(spec, raw)


def test_new_metrics_on_a_known_record():
    raw = _raw()
    least = roof.kernel_least_seconds(
        SIZES, PRECISION, PEAKS, EXPERTS, [7800, 5000], 256)
    assert _read("lfm2_prefill_attention_roofline", raw) == \
        pytest.approx(100 * least["flash_prefill_attention"]["seconds"] / 0.25)
    assert _read("lfm2_expert_matmul_roofline", raw) == pytest.approx(
        100 * least["expert_grouped_matmul"]["seconds"] / 2.0)
    assert _read("lfm2_decode_attention_roofline", raw) is None
    raw["trace"]["device_ops"] += [["flash_decode_attention", 0.4],
                                   ["while", 0.1]]
    # what the profiler lost inside a loop is counted against the kernel
    assert _read("lfm2_decode_attention_roofline", raw) == \
        pytest.approx(100 * least["flash_decode_attention"]["seconds"] / 0.5)
    assert _read("lfm2_expert_matmul_roofline", raw) == pytest.approx(
        100 * least["expert_grouped_matmul"]["seconds"] / 2.1)
    whole = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, [7800, 5000], 256)
    assert _read("generate_roofline_share_lfm2", raw) == pytest.approx(
        100 * whole["total_s"] / 9.0)
    assert _read("shortconv_tokens_computed_over_real", raw) == \
        pytest.approx(8192 / 7800)
    # the shared metrics' files hold for this cell's record as written
    assert _read("expert_ffn_busy_share", raw) == pytest.approx(20.0)
    assert _read("expert_load_max_over_mean", raw) == pytest.approx(5 / 4)
    assert _read("expert_distinct_per_step", raw) == pytest.approx(
        100 * 30 / 32)
    # two whole executions: both dispatches counted
    raw["trace"]["module_calls"]["jit_generate"] = 2
    both = whole["total_s"] + roof.dispatch(
        SIZES, PRECISION, PEAKS, EXPERTS, [2000], 256)["total_s"]
    assert _read("generate_roofline_share_lfm2", raw) == pytest.approx(
        100 * both / 9.0)


def test_readers_with_nothing_to_read_leave_their_metric_out():
    """As on the parent commit, whose program has no such family, kernel or
    counter: None, never an exception."""
    bare = {"device": {"kind": "TPU v5 lite"}, "counts": {}, "trace": None,
            "traced": None}
    for m in cells.metrics_for(BENCH, "per_layer", CELL):
        if m["name"] not in ("host_share.offline",):
            assert _read(m["name"], bare) is None, m["name"]
    # a checkout without the family's roofline module
    raw = _raw()
    spec = dict(cells.load_layer_metric("lfm2_expert_matmul_roofline"),
                roofline="roofline_of_no_such_family")
    reader = cells.load_module("readers", "state_kernel_roofline")
    assert reader.read(spec, raw) is None
    whole = cells.load_module("readers", "state_dispatch_roofline")
    assert whole.read(dict(spec, modules=["jit_generate"]), raw) is None
    # a program that counts no convolution tokens
    del raw["counts"]["prefill_blocks"]["conv_tokens_real"]
    assert _read("shortconv_tokens_computed_over_real", raw) is None
    # no whole execution in the stretch
    raw["trace"]["module_calls"] = {}
    assert _read("lfm2_expert_matmul_roofline", raw) is None
    assert _read("generate_roofline_share_lfm2", raw) is None


@pytest.mark.parametrize("name", sorted(OWN))
def test_an_own_metric_is_listed_for_this_cell_alone(name):
    m = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert m["workloads"] == [CELL] and m["moves"] == "docs_per_min"
    assert m["layer"] == "model and kernels"
    spec = cells.load_layer_metric(name)
    assert spec["drivers"] == ["offline_pipeline_family"]
    for key in ("layer", "unit", "moves", "better", "source"):
        assert spec[key] == m[key], key
    if "roofline" in spec:
        assert spec["roofline"] == "roofline_lfm2"
        assert spec["reader"].startswith("state_")
        assert (m["unit"], m["better"]) == ("%", "higher")
        assert "roofline" in name


@pytest.mark.parametrize("name", sorted(SHARED))
def test_a_shared_metric_lists_this_cell_among_its_cells(name):
    m = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert CELL in m["workloads"] and len(m["workloads"]) > 1
    assert m["moves"] == "docs_per_min"


def test_the_cell_is_in_the_benchmark_by_membership():
    mine = {m["name"] for m in cells.metrics_for(BENCH, "per_layer", CELL)}
    assert mine == OWN | SHARED
    assert {m["name"] for m in cells.metrics_for(BENCH, "end_to_end", CELL)
            } == {"docs_per_min", "setup_s"}
    assert cells.validate(BENCH, ROOT) == []
    cell = cells.find_cell(BENCH, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["config"] == NAME and cell["traffic"] == TRAFFIC
    assert NAME in [c["name"] for c in BENCH["configs"]]
    # one cell of this configuration, and no other
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == NAME] == [CELL]
    traffic = cells.load_traffic(TRAFFIC)
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
    for fn in ("model_config", "start_weights", "sizes_of", "sizes_from",
               "parity_with_reference"):
        assert callable(getattr(mod, fn)), fn


# -- the cell, rehearsed ----------------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    """The whole cell at a tiny size on the CPU, every kernel interpreted:
    the driver, the family's set-up, parity, warm-up, a window, the
    readers."""
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 54), "--seconds", "2", "--trace",
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
        assert set(counted) == {"shortconv_tokens_computed_over_real",
                                "expert_load_max_over_mean",
                                "expert_distinct_per_step"}
        assert 1.0 <= counted["shortconv_tokens_computed_over_real"] < 2.0
        assert counted["expert_load_max_over_mean"] >= 1.0
        assert 0 < counted["expert_distinct_per_step"] <= 100
