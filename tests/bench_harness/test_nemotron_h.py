"""CPU tests of the benchmark's own parts for the Nemotron-H family: the
plain reference against the program, the run-time parity check and what it
has to catch (a fault of the equations, a state kept a precision below),
the rooflines against hand-worked numbers, the readers on a known record,
the cell's rehearsal, and the configuration file's keys and arithmetic.

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
from benchmarks import engine_setup_nemotron_h as family_setup  # noqa: E402
from benchmarks import roofline_nemotron_h as roof  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
NAME = "nemotron-3-nano-l16-int8"
CONFIG = cells.load_config(BENCH, NAME)
CELL = f"{NAME}.offline-mapreduce-8k-ssm-moe"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
OWN = {"generate_roofline_share_nemotron_h",
       "nemotron_ssd_prefill_scan_roofline",
       "nemotron_ssm_decode_update_roofline",
       "nemotron_prefill_attention_roofline",
       "nemotron_decode_attention_roofline",
       "nemotron_expert_matmul_roofline",
       "nemotron_scan_tokens_computed_over_real"}
SHARED = {"host_share.offline", "generate_device_s_per_dispatch",
          "device_idle.offline", "idle_in_engine_host.offline",
          "idle_in_pipeline_host.offline", "idle_unexplained.offline",
          "expert_ffn_busy_share", "expert_load_max_over_mean",
          "expert_distinct_per_step", "ssm_busy_share"}


def _tiny(**kw):
    from vnsum_tpu.models.nemotron_h import tiny_nemotron_h

    return tiny_nemotron_h(**kw)


# -- the reference against the program ---------------------------------------


@pytest.mark.parametrize("int8", [False, True])
def test_plain_reference_agrees_with_the_cache_free_forward(int8):
    import jax
    import jax.numpy as jnp

    from benchmarks import reference_nemotron_h as reference
    from vnsum_tpu.models import nemotron_h as nh
    from vnsum_tpu.models.quant import quantize_params

    cfg = _tiny()
    params = nh.init_params(jax.random.key(5), cfg)
    params["layers"]["router"] = params["layers"]["router"] * 10.0
    if int8:
        params = quantize_params(params)
    toks = jax.random.randint(jax.random.key(6), (60,), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = reference.logits(params, toks, family_setup.sizes_from(cfg))
        got = nh.forward_dense(params, cfg, toks[None])[0]
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_reference_is_plain_float32_and_reads_nothing_of_the_program():
    src = (ROOT / "benchmarks" / "reference_nemotron_h.py").read_text()
    code = src.split('"""', 2)[2]
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in code
    assert "jax.lax.scan(token" in code          # the recurrence, by token
    assert "fori_loop(0, held, one_expert" in code   # ONE expert at a time
    for word in ("pallas", "chunk_size", "bfloat16", "cumsum", "import vnsum",
                 "from vnsum"):
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
    assert got["last_row_error"] == got["errors"][-1] < got["error"]
    assert 0 < got["state_error"] <= got["state_tolerance"]
    assert 0 < got["state_step_error"] <= got["state_step_tolerance"]
    assert got["first_layer_picks_ok"]
    # every real token on 4 sparse layers x 4 picks, all held
    assert got["slots_routed"] == got["slots_held"] == 154 * 4 * 4
    # the limits have room on both sides of what a clean run reads
    assert got["error"] * 1.3 < got["tolerance"]
    assert got["last_row_error"] * 1.3 < got["decode_tolerance"]
    assert got["state_error"] * 1.3 < got["state_tolerance"]
    assert got["state_step_error"] * 1.3 < got["state_step_tolerance"]


@pytest.mark.parametrize("fault", [
    "relu", "gated", "group0_bc", "norm_whole", "norm_before_gate",
    "no_renorm", "no_scaling", "no_shared", "no_conv_bias", "no_D",
    "residual_multiplier"])
def test_parity_catches_a_departure_from_the_equations(fault,
                                                       rehearsal_backend):
    """The faults of the mixers, the expert form and the residual path, on
    the int8 engine the cell times. Not here: the router's four
    (``softmax_router``, ``no_bias``, ``bias_in_weight`` — a seeded tiny
    router's picks sit too far apart for the tiny bias to move them) and
    ``rope`` (a 0.02-normal draw's scores are flat);
    tests/test_model_nemotron_h.py shows all fifteen with sharper
    weights."""
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


def test_picks_outside_the_band_fail_the_check(monkeypatch,
                                               rehearsal_backend):
    """A router that picks by another rule: the first sparse layer's picks
    of one scored row replaced by the LEAST ranked experts are no rightful
    top-k within any band, and the check says so whatever the logits."""
    import numpy as np

    real = rehearsal_backend.prefill_then_decode_logits

    def other_picks(*a, **kw):
        logits, state = real(*a, **kw)
        picks = np.array(state["rows"]["picks"])
        picks[1, 0, 0] = (picks[1, 0, 0] + 7) % 16
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


def test_model_config_builds_the_published_widths_at_sixteen_layers():
    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    assert cfg.layer_pattern == "MEMEM*EMEMEM*EME" == PATTERN[:16]
    assert (cfg.n_layers, cfg.n_mamba, cfg.n_sparse, cfg.n_attention) == (
        16, 7, 7, 2)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.vocab_size) == (2688, 32, 2, 128, 131072)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_d_conv) == (64, 64, 128, 8, 4)
    assert (cfg.moe_intermediate, cfg.moe_stored, cfg.shared_intermediate,
            cfg.n_routed_experts, cfg.n_held, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor) == (1856, 1920, 3712, 128, 128, 6, 2.5)
    assert not cfg.tie_embeddings and cfg.max_seq_len == 8448
    assert cfg.mamba_chunk_size == CONFIG["engine"]["scan_chunk"]
    kw = engine_setup.backend_kwargs(CONFIG, rehearsal=False)
    assert kw["quantize"] and kw["quantize_act"] and kw["quantize_kv"] is True
    assert kw["prefill_chunk_tokens"] == CONFIG["engine"][
        "prefill_chunk_tokens"]
    sizes = family_setup.sizes_of(CONFIG, False)
    assert {**family_setup.sizes_from(cfg), "chunk_size": 128} == {
        **sizes, "expert_offset": 0}
    assert sizes["hybrid_override_pattern"] == "MEMEM*EMEMEM*EME"
    tiny = family_setup.model_config(CONFIG, rehearsal=True)
    assert (tiny.layer_pattern, tiny.mamba_n_groups, tiny.q_per_kv,
            tiny.mamba_chunk_size) == ("MEM*EMEME", 2, 4, 8)
    assert tiny == _tiny(vocab_size=640, max_seq_len=640, intermediate=24)


@pytest.mark.parametrize("key, value, text", [
    ("mlp_hidden_act", "silu", "this family builds 'relu2'"),
    ("n_group", 8, "this family builds 1"),
    ("norm_topk_prob", False, "this family builds True"),
    ("use_conv_bias", False, "this family builds True"),
    ("n_shared_experts", 2, "this family builds 1"),
    ("layer_norm_epsilon", 1e-6, "stated two ways"),
])
def test_a_mechanism_the_family_does_not_build_is_refused(key, value, text):
    config = copy.deepcopy(CONFIG)
    config[key] = value
    with pytest.raises(ValueError, match=text):
        family_setup.sizes_of(config, False)


def _catalog_row():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    return next(r for r in map(json.loads, catalog.read_text().splitlines())
                if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


def test_config_files_keys_are_the_catalog_rows():
    """Every number of the catalog entry's config under the same key, but
    the one reduced; the pattern cut in the file and whole under
    ``published``."""
    row = _catalog_row()
    assert CONFIG["source"] == row["source_url"]
    assert row["config"]["hybrid_override_pattern"] == PATTERN
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (CONFIG[key], CONFIG["published"][key]) == (16, value)
        elif key == "hybrid_override_pattern":
            assert CONFIG[key] == value[:16]
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key


def test_config_file_keeps_every_width_and_reduces_depth_alone():
    c = CONFIG
    entry = next(e for e in BENCH["configs"] if e["name"] == NAME)
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == c["source"]
    assert c["source"].endswith(
        "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    assert len(entry["why"]) <= 200
    for key, value in {
            "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
            "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
            "num_attention_heads": 32, "num_key_value_heads": 2,
            "head_dim": 128, "n_routed_experts": 128,
            "moe_intermediate_size": 1856, "num_experts_per_tok": 6,
            "moe_shared_expert_intermediate_size": 3712,
            "routed_scaling_factor": 2.5, "vocab_size": 131072}.items():
        assert c[key] == value, key
    for key in ("assumed", "deployment", "bytes", "engine_notes", "engine",
                "reference", "setup_module", "checkpoint_notes"):
        assert c[key], key
    for key in ("attention", "gated_norm", "groups", "expand", "dt_limits",
                "state_precision", "router", "experts", "expert_storage",
                "random_weights", "chunked_scan", "embedding"):
        assert key in c["assumed"], key
    assert "FIRST stage" in c["deployment"]
    assert "ALL 128 routed experts" in c["deployment"]
    assert c["checkpoint_seed"] == 47
    assert c["setup_module"] == "engine_setup_nemotron_h"
    engine = c["engine"]
    assert {k: engine[k] for k in ("weights", "activations", "kv", "state",
                                   "max_seq_len")} == {
        "weights": "int8", "activations": "int8", "kv": "int8",
        "state": "float32", "max_seq_len": 8448}
    # a group's 24 map prompts are whole dispatches of the batch
    assert 24 % engine["batch"] == 0
    assert engine["prefill_chunk_tokens"] in (1024, 2048)
    parity = c["reference"]["parity"]
    assert parity["bucket"] == 8192 and parity["decode_steps"] == 8
    # behind a left pad, and past all but the last prefill chunk
    assert 8192 - engine["prefill_chunk_tokens"] < parity["prompt_tokens"] \
        < 8192
    for limit in ("tolerance", "decode_tolerance", "state_tolerance",
                  "state_step_tolerance", "tie_band"):
        assert 0 < parity[limit] < 1 and limit in parity["what"], limit
        assert 0 < c["rehearsal"]["parity"][limit] < 1


def test_config_files_byte_arithmetic_is_the_models():
    import jax

    from vnsum_tpu.models.nemotron_h import init_cache
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    tree = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                          jax.random.key(0))
    size = lambda t: sum(a.size * a.dtype.itemsize  # noqa: E731
                         for a in jax.tree.leaves(t))
    b, m, e = CONFIG["bytes"], tree["mamba"], tree["layers"]
    assert b["mamba_in_proj"] == sum(
        size(m[part]) for part in ("in_z", "in_xbc", "in_dt")) // 7 \
        == 2688 * 10304 + 4 * 10304
    assert b["mamba_out_proj"] == size(m["out_proj"]) // 7 \
        == 4096 * 2688 + 4 * 2688
    assert b["mamba_conv"] == (size(m["conv_w"]) + size(m["conv_b"])) // 7 \
        == 6144 * 5 * 4
    assert b["mamba_layer"] == size(m) // 7
    assert b["attention_layer"] == size(tree["attn"]) // 2
    # an expert as STORED: 1,920 wide, a float32 scale a column
    assert b["routed_expert_as_stored"] == (
        size(e["we_up"]) + size(e["we_down"])) // 7 // 128 \
        == 2 * 2688 * 1920 + 4 * (1920 + 2688)
    assert b["routed_expert_published"] == 2 * 2688 * 1856 \
        == roof.expert_params(family_setup.sizes_of(CONFIG, False))
    assert b["shared_expert"] == (size(e["ws_up"]) + size(e["ws_down"])) // 7
    assert b["sparse_layer"] == size(e) // 7
    assert b["layers_16"] == (7 * b["mamba_layer"] + 7 * b["sparse_layer"]
                              + 2 * b["attention_layer"])
    assert b["embedding_and_head"] == (
        size(tree["embed"]) + size(tree["lm_head"])
        + size(tree["final_norm"]))
    assert b["weights"] == size(tree) == (b["layers_16"]
                                          + b["embedding_and_head"])
    # the issue's reckoning at 1,856 and no scales: 10.105 GB; stored 1,920
    assert 10.4e9 < b["weights"] < 10.5e9
    row = jax.eval_shape(lambda: init_cache(cfg, 1, 8448, quantized=True))
    assert sum(size(row[n]) for n in ("k", "v", "ks", "vs")) \
        == b["kv_cache_a_row"] == 2 * 2 * 8448 * (2 * 128 + 8)
    assert size(row["conv"]) == b["conv_tail_a_row"]
    assert size(row["ssm"]) == b["recurrent_state_a_row"] \
        == 7 * 64 * 64 * 128 * 4
    s = family_setup.sizes_of(CONFIG, False)
    assert roof.state_bytes_a_row(s) == b["recurrent_state_a_row"]
    assert roof.mamba_params(s) == 2688 * 10304 + 4096 * 2688
    assert roof.attention_params(s) == 2688 * 128 * (32 + 4) + 32 * 128 * 2688


# -- the rooflines ----------------------------------------------------------------

SIZES = family_setup.sizes_of(CONFIG, False)
PEAKS = {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_per_s": 819e9}
PRECISION = {"weights": 1, "kv": 1, "prefill_matmul": "int8"}
MAMBA = 2688 * 10304 + 4096 * 2688
ATTN = 2688 * 128 * 36 + 4096 * 2688
EXPERT = 2 * 2688 * 1856
SHARED_EXPERT = 2 * 2688 * 3712
ROUTER = 2688 * 128
FIXED = 7 * MAMBA + 2 * ATTN + 7 * (ROUTER + SHARED_EXPERT)
HEAD = 2688 * 131072
STATE = 7 * 4096 * 128             # elements of one row's recurrent state
EXPERTS = {"slots_routed": 1000, "slots_held": 1000,
           "decode_touched": 7 * 256 * 50, "decode_layer_steps": 7 * 256}


def test_params_and_scan_by_hand():
    assert [roof.layers_of(SIZES, k) for k in "ME*"] == [7, 7, 2]
    assert roof.inner(SIZES) == 4096
    assert roof.mamba_params(SIZES) == MAMBA
    assert roof.attention_params(SIZES) == ATTN
    assert roof.expert_params(SIZES) == EXPERT        # at 1,856, not 1,920
    assert roof.shared_params(SIZES) == SHARED_EXPERT
    assert roof.fixed_params(SIZES) == FIXED
    assert roof.params_a_token(SIZES, 1.0) == FIXED + 7 * 6 * EXPERT
    assert roof.params_a_token(SIZES, 0.5) == FIXED + 7 * 3 * EXPERT
    scan = roof.scan_a_token(SIZES)
    # the published chunk of 128, state 128, inner 4096, C B^T once a GROUP
    assert scan["ops"] == 2 * 128 * 4096 + 4 * 128 * 4096 + 2 * 128 * 128 * 8
    assert scan["bytes"] == (2 * 4096 + 2 * 8 * 128) * 2 + 12 * 64
    assert roof.state_bytes_a_row(SIZES) == STATE * 4
    assert roof.decode_context([10, 20], 3) == (11 + 12 + 13) + (21 + 22 + 23)


def test_kernel_rooflines_against_hand_worked_numbers():
    lens, steps = [7800, 5000], 256
    got = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, EXPERTS, lens,
                                    steps)
    tokens = sum(lens)
    scan = roof.scan_a_token(SIZES)
    assert got["ssd_prefill_scan"]["seconds"] == pytest.approx(max(
        scan["ops"] * tokens * 7 / 197e12, scan["bytes"] * tokens * 7 / 819e9))
    assert got["ssm_decode_update"] == {
        "seconds": pytest.approx(8 * STATE * 2 * steps / 819e9),
        "bound": "memory"}
    pairs = sum(n * (n + 1) // 2 for n in lens)
    assert got["flash_prefill_attention"]["seconds"] == pytest.approx(
        4 * 32 * 128 * 2 * pairs / 197e12)
    ctx = roof.decode_context(lens, steps) * 2
    assert got["flash_decode_attention"]["seconds"] == pytest.approx(max(
        4 * 32 * 128 * ctx / 197e12, 2 * (2 * 128 + 8) * ctx / 819e9))
    assert got["flash_decode_attention"]["bound"] == "memory"
    # the experts: six a token and sparse layer at the int8 peak, then each
    # step the 50 experts a layer it touched, read once
    prefill = 2 * EXPERT * 6 * 7 * tokens / 393e12
    decode = max(2 * EXPERT * 6 * 7 * 2 * steps / 393e12,
                 EXPERT * 50 * 7 * steps / 819e9)
    assert got["expert_grouped_matmul"]["seconds"] == pytest.approx(
        prefill + decode)
    assert got["expert_grouped_matmul"]["bound"] == "compute, then memory"


def test_dispatch_roofline_adds_up_by_hand():
    lens, steps = [7800, 5000], 256
    got = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, lens, steps)
    kernels = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, EXPERTS,
                                        lens, steps)
    tokens, token = sum(lens), FIXED + 7 * 6 * EXPERT
    assert got["prefill_matmul_ops"] == 2 * token * tokens + 2 * HEAD * 2
    assert got["prefill_s"] == pytest.approx(
        got["prefill_matmul_ops"] / 393e12
        + kernels["ssd_prefill_scan"]["seconds"]
        + kernels["flash_prefill_attention"]["seconds"])
    ctx = roof.decode_context(lens, steps) * 2
    assert got["decode_state_bytes"] == 8 * STATE * 2 * steps
    assert got["decode_expert_bytes"] == EXPERT * 50 * 7 * steps
    assert got["decode_bytes"] == pytest.approx(
        (FIXED + HEAD) * steps + EXPERT * 50 * 7 * steps
        + 8 * STATE * 2 * steps + 2 * (2 * 128 + 8) * ctx)
    assert got["decode_s"] == pytest.approx(max(
        got["decode_bytes"] / 819e9, got["decode_ops"] / 197e12))
    assert got["total_s"] == got["prefill_s"] + got["decode_s"]
    # a share of the experts held elsewhere takes its operations along
    half = roof.dispatch(SIZES, PRECISION, PEAKS,
                         {**EXPERTS, "slots_held": 500}, lens, steps)
    assert half["prefill_matmul_ops"] == pytest.approx(
        2 * (FIXED + 7 * 3 * EXPERT) * tokens + 2 * HEAD * 2)


# -- the readers --------------------------------------------------------------------


def _raw():
    dispatch = {"prompt_lens": [7800, 5000], "steps": 256, "experts": EXPERTS}
    return {
        "device": {"kind": "TPU v5 lite"}, "sizes": SIZES,
        "precision": PRECISION,
        "counts": {"experts": {**EXPERTS, "tokens": [[3, 1], [2, 2]],
                               "decode_reads_possible": 7 * 256 * 128},
                   "prefill_blocks": {"interior": 10, "edge": 4,
                                      "scan_tokens_real": 7 * 7800,
                                      "scan_tokens_computed": 7 * 7936}},
        "trace": {"busy_s": 10.0, "modules": {"jit_generate": 9.0},
                  "module_calls": {"jit_generate": 1},
                  "device_ops": [["ssd_prefill_scan", 0.5],
                                 ["flash_prefill_attention", 0.25],
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
    assert _read("nemotron_ssd_prefill_scan_roofline", raw) == pytest.approx(
        100 * least["ssd_prefill_scan"]["seconds"] / 0.5)
    assert _read("nemotron_prefill_attention_roofline", raw) == \
        pytest.approx(100 * least["flash_prefill_attention"]["seconds"] / 0.25)
    assert _read("nemotron_expert_matmul_roofline", raw) == pytest.approx(
        100 * least["expert_grouped_matmul"]["seconds"] / 2.0)
    assert _read("nemotron_ssm_decode_update_roofline", raw) is None
    assert _read("nemotron_decode_attention_roofline", raw) is None
    raw["trace"]["device_ops"] += [["ssm_decode_update", 1.5],
                                   ["flash_decode_attention", 0.4],
                                   ["while", 0.1]]
    # what the profiler lost inside a loop is counted against the kernel
    assert _read("nemotron_ssm_decode_update_roofline", raw) == \
        pytest.approx(100 * least["ssm_decode_update"]["seconds"] / 1.6)
    assert _read("nemotron_decode_attention_roofline", raw) == \
        pytest.approx(100 * least["flash_decode_attention"]["seconds"] / 0.5)
    assert _read("nemotron_expert_matmul_roofline", raw) == pytest.approx(
        100 * least["expert_grouped_matmul"]["seconds"] / 2.1)
    whole = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, [7800, 5000], 256)
    assert _read("generate_roofline_share_nemotron_h", raw) == pytest.approx(
        100 * whole["total_s"] / 9.0)
    assert _read("nemotron_scan_tokens_computed_over_real", raw) == \
        pytest.approx(7936 / 7800)
    # the shared metrics' files hold for this cell's record as written
    assert _read("ssm_busy_share", raw) == pytest.approx(20.0)
    assert _read("expert_ffn_busy_share", raw) == pytest.approx(20.0)
    assert _read("expert_load_max_over_mean", raw) == pytest.approx(5 / 4)
    assert _read("expert_distinct_per_step", raw) == pytest.approx(
        100 * 50 / 128)
    # two whole executions: both dispatches counted
    raw["trace"]["module_calls"]["jit_generate"] = 2
    both = whole["total_s"] + roof.dispatch(
        SIZES, PRECISION, PEAKS, EXPERTS, [2000], 256)["total_s"]
    assert _read("generate_roofline_share_nemotron_h", raw) == pytest.approx(
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
    spec = dict(cells.load_layer_metric("nemotron_ssd_prefill_scan_roofline"),
                roofline="roofline_of_no_such_family")
    reader = cells.load_module("readers", "state_kernel_roofline")
    assert reader.read(spec, raw) is None
    whole = cells.load_module("readers", "state_dispatch_roofline")
    assert whole.read(dict(spec, modules=["jit_generate"]), raw) is None
    # no whole execution in the stretch
    raw["trace"]["module_calls"] = {}
    assert _read("nemotron_expert_matmul_roofline", raw) is None
    assert _read("generate_roofline_share_nemotron_h", raw) is None


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
        assert spec["roofline"] == "roofline_nemotron_h"
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
    assert cell["config"] == NAME
    assert cell["traffic"] == "offline-mapreduce-8k-ssm-moe"
    assert NAME in [c["name"] for c in BENCH["configs"]]
    traffic = cells.load_traffic("offline-mapreduce-8k-ssm-moe")
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
         CELL, "--seed", str(2**31 + 47), "--seconds", "2", "--trace",
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
        assert set(counted) == {"nemotron_scan_tokens_computed_over_real",
                                "expert_load_max_over_mean",
                                "expert_distinct_per_step"}
        assert 1.0 <= counted["nemotron_scan_tokens_computed_over_real"] < 1.05
        assert counted["expert_load_max_over_mean"] >= 1.0
        assert 0 < counted["expert_distinct_per_step"] <= 100
