"""CPU tests of the benchmark's own parts for the dense stack LOOPED over its
weights (Ouro-2.6B): the configuration file against the catalog row and the
program's own tree and cache, the plain reference against the program, the
run-time parity check and what it has to catch (every fault of the
equations, a cache written or read a pass off), the rooflines against
hand-worked numbers — the layers' weights ``T`` times a decode step among
them —, the readers on a known record, and the cell's rehearsal.

It tests MEMBERSHIP — its cell, configuration and metrics are in the lists —
never a position or a count of cells. Nothing here touches the TPU library
at import.
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
from benchmarks import engine_setup_ouro as family_setup  # noqa: E402
from benchmarks import reference_ouro as reference  # noqa: E402
from benchmarks import roofline_ouro as roof  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
CONFIG = cells.load_config(BENCH, "ouro-2.6b-l12-int8")
CELL = "ouro-2.6b-l12-int8.offline-mapreduce-8k-loop"
# ByteDance/Ouro-2.6B config.json, as the catalog row has it
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}


# -- the configuration file -----------------------------------------------------


def test_config_file_keeps_every_published_width_and_cuts_depth_alone():
    c = CONFIG
    entry = next(e for e in BENCH["configs"] if e["name"] == c["name"])
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == c["source"]
    assert c["source"].endswith("ByteDance/Ouro-2.6B/blob/main/config.json")
    assert len(entry["why"]) <= 200
    for key, value in PUBLISHED.items():
        if key not in ("num_hidden_layers", "layer_types"):
            assert c[key] == value, key
    assert c["num_hidden_layers"] == 12
    assert c["layer_types"] == ["full_attention"] * 12
    assert c["published"] == {"num_hidden_layers": 48,
                              "layer_types": PUBLISHED["layer_types"]}
    # the mechanism is never cut
    assert c["total_ut_steps"] == 4 and c["early_exit_threshold"] == 1
    for key in ("assumed", "deployment", "bytes", "engine_notes", "engine",
                "reference", "checkpoint_notes"):
        assert c[key], key
    assert c["setup_module"] == "engine_setup_ouro"
    assert c["registry_name"] == "ouro-2.6b" and c["checkpoint_seed"] == 50
    for key in ("sandwich_norms", "norm_between_passes",
                "cache_per_pass_and_layer", "positions", "attention_bias",
                "exit_gate"):
        assert key in c["assumed"] and len(c["assumed"][key]) > 40, key
    assert "RING" in c["deployment"] and "12 + 12 + 12 + 12" in c["deployment"]
    e = c["engine"]
    assert (e["weights"], e["activations"], e["prefill_chunk_tokens"],
            e["max_seq_len"]) == ("int8", "int8", 2048, 8448)
    assert e["batch"] in (4, 6, 8) and str(e["batch"]) in c["engine_notes"]
    parity = c["reference"]["parity"]
    assert parity["bucket"] == 8192 and parity["decode_steps"] == 8
    # behind a left pad, and past three prefill chunks
    assert 3 * 2048 < parity["prompt_tokens"] < 8192
    for limit in ("tolerance", "kv_tolerance", "kv_last_pass_tolerance"):
        assert 0 < parity[limit] < 1 and limit in parity["what"], limit
        assert 0 < c["rehearsal"]["parity"][limit] < 1


def test_config_files_keys_are_the_catalog_rows():
    """Every number of the catalog entry's config under the same key, the
    depth and its list of layer kinds excepted (``reduced``)."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "Ouro-2.6B")
    assert CONFIG["source"] == row["source_url"]
    assert row["config"] == PUBLISHED
    for key, value in row["config"].items():
        if key not in ("num_hidden_layers", "layer_types"):
            assert CONFIG[key] == value, key
        else:
            assert CONFIG["published"][key] == value, key


def test_model_config_builds_the_published_model_at_the_files_depth():
    from vnsum_tpu.models import llama

    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    assert cfg == llama.ouro_2p6b(n_layers=12, max_seq_len=8448)
    assert llama.cache_layers(cfg) == 48 and cfg.q_per_kv == 1
    assert family_setup.sizes_from(cfg) == family_setup.sizes_of(CONFIG, False)
    tiny = family_setup.model_config(CONFIG, rehearsal=True)
    assert (tiny.n_layers, tiny.loop_passes, tiny.n_kv_heads) == (2, 3, 4)
    assert tiny.sandwich_norms and not tiny.tie_embeddings


@pytest.mark.parametrize("key, value, text", [
    ("hidden_act", "gelu", "hidden_act"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("model_type", "qwen3", "model_type"),
])
def test_a_mechanism_the_program_does_not_build_is_refused(key, value, text):
    config = copy.deepcopy(CONFIG)
    config[key] = value
    with pytest.raises(ValueError, match=text):
        family_setup.sizes_of(config, False)


def test_a_threshold_under_one_is_refused_by_name():
    config = copy.deepcopy(CONFIG)
    config["early_exit_threshold"] = 0.95
    with pytest.raises(NotImplementedError, match="adaptive exit"):
        family_setup.model_config(config, rehearsal=False)


def test_config_files_byte_arithmetic_is_the_programs():
    import jax

    from vnsum_tpu.models import llama
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    tree = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                          jax.random.key(0))
    size = lambda t: sum(a.size * a.dtype.itemsize  # noqa: E731
                         for a in jax.tree.leaves(t))
    b = CONFIG["bytes"]
    assert b["layers_12"] == size(tree["layers"]) == 12 * b["layer"]
    params = 4 * 2048 * 2048 + 3 * 2048 * 5632          # 51.38 M a layer
    assert params == roof.layer_params(family_setup.sizes_of(CONFIG, False))
    # a byte a parameter, a float32 scale an output channel, four bf16 norms
    assert b["layer"] == params + 4 * (3 * 2048 + 2048 + 2 * 5632 + 2048) \
        + b["norms_a_layer"]
    assert b["norms_a_layer"] == 4 * 2048 * 2
    assert b["embedding_and_head"] == size(tree["embed"]) + size(
        tree["lm_head"]) == 2 * (49152 * 2048 + 4 * 49152)
    assert b["exit_gate"] == size(tree["exit_gate"]) == 4 * 2048 + 4
    assert b["weights"] == size(tree) == (
        b["layers_12"] + b["embedding_and_head"] + b["final_norm"]
        + b["exit_gate"])
    assert 0.81e9 < b["weights"] < 0.83e9   # the issue's reckoning: 817.9 MB
    row = jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, 1, 8448, quantized=True))
    assert row["k"].shape == (48, 1, 16, 8448, 128)
    assert b["cache_layers"] == 48 == row["k"].shape[0]
    assert b["kv_a_token_and_cache_layer"] == 16 * (2 * 128 + 8) == 4224
    assert b["kv_cache_a_row"] == size(row) == 48 * 4224 * 8448
    assert b["kv_cache_8_rows"] == 8 * b["kv_cache_a_row"]
    # the published depth: what one chip would have to hold
    full = llama.ouro_2p6b(max_seq_len=8448)
    whole = jax.eval_shape(lambda k: init_params_quantized(k, full),
                           jax.random.key(0))
    deep = b["published_depth"]
    assert deep["weights"] == size(whole)
    assert deep["cache_layers"] == 192 == llama.cache_layers(full)
    assert deep["kv_cache_a_row"] == 192 * 4224 * 8448


# -- the reference against the program ------------------------------------------


def _tiny(**kw):
    from vnsum_tpu.models import llama

    return llama.tiny_ouro(**kw)


@pytest.mark.parametrize("int8", [False, True])
def test_plain_reference_agrees_with_the_cache_free_forward(int8):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vnsum_tpu.models import llama
    from vnsum_tpu.models.quant import quantize_params

    cfg = _tiny()
    params = llama.init_params(jax.random.key(2), cfg)
    if int8:
        params = quantize_params(params)
    toks = jax.random.randint(jax.random.key(3), (1, 48), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = llama.forward_train(params, cfg, toks, remat=False)[0]
    want = reference.logits(params, toks[0], family_setup.sizes_from(cfg))
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 1e-5, err
    assert np.isfinite(np.asarray(want)).all()


def test_reference_is_plain_float32_and_reads_nothing_of_the_program():
    import ast

    src = (ROOT / "benchmarks" / "reference_ouro.py").read_text()
    imported = {n.module or "" for n in ast.walk(ast.parse(src))
                if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(ast.parse(src))
        if isinstance(n, ast.Import) for a in n.names}
    assert not any(name.startswith(("vnsum_tpu", "benchmarks"))
                   for name in imported), imported
    assert 'default_matmul_precision("highest")' in src
    assert "pallas" not in src and "int8" not in src.split('"""')[2]
    assert set(reference.FAULTS) >= {
        "three_passes", "one_pass", "norm_at_end_alone", "no_output_norms",
        "output_norm_after_add", "norm_plus_one", "qk_norm", "no_rotary",
        "rotary_rebased"}
    with pytest.raises(ValueError, match="unknown faults"):
        reference.forward({}, None, {"early_exit_threshold": 1},
                          faults=("no_such",))


# -- the run-time parity check ----------------------------------------------------


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
    assert got["ok"] and got["kernel"] and got["passes"] == 3
    assert len(got["errors"]) == 5 and got["pad"] == 56
    assert got["cache_layers_seen"] == [0, 4]   # (T - 1) * L of T * L = 6
    assert 0 < got["error"] <= got["tolerance"]
    assert 0 < got["kv_error"] <= got["kv_tolerance"]
    assert 0 < got["kv_last_pass_error"] <= got["kv_last_pass_tolerance"]
    # the limits have room on both sides of what a clean run reads
    assert got["error"] * 1.3 < got["tolerance"]
    assert got["kv_error"] * 1.3 < got["kv_tolerance"]
    assert got["kv_last_pass_error"] * 1.3 < got["kv_last_pass_tolerance"]
    assert max(got["kv_decode_errors"]) < got["kv_last_pass_tolerance"]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_parity_catches_a_departure_from_the_equations(fault,
                                                       rehearsal_backend):
    """Every fault fails at least one limit. ``rotary_rebased`` moves no
    logit (a common shift of a pass's positions cancels in q . k): the last
    pass's cache rows are what sees it. ``no_rotary`` hides in the logits
    of random weights (PERF.md section 7) and shows in both caches."""
    got = _parity(rehearsal_backend, (fault,))
    assert not got["ok"], got
    assert got["faults"] == [fault]
    if fault == "rotary_rebased":
        assert got["error"] <= got["tolerance"]
        assert got["kv_error"] <= got["kv_tolerance"]
        assert got["kv_last_pass_error"] > 5 * got["kv_last_pass_tolerance"]
    if fault == "no_rotary":
        assert got["kv_error"] > 5 * got["kv_tolerance"]


def test_parity_catches_a_pass_that_writes_another_passes_layer(
        monkeypatch, rehearsal_backend):
    """A cache indexed by the layer alone in the DECODE steps: every pass
    of a decode step writes and reads the first pass's cache layers. The
    prefill's row of logits stands; the decode rows and the last pass's
    decode-written cache rows fail."""
    from vnsum_tpu.backend.engine import TpuBackend
    from vnsum_tpu.models import llama

    write, attend = llama._write_kv, llama._cache_attention

    def by_layer_alone(layer_idx, decode):
        return layer_idx % 2 if decode else layer_idx   # L = 2

    monkeypatch.setattr(
        llama, "_write_kv", lambda cache, k, v, layer_idx, *a, **kw: write(
            cache, k, v, by_layer_alone(layer_idx, k.shape[1] == 1), *a, **kw))
    monkeypatch.setattr(
        llama, "_cache_attention", lambda q, cache, layer_idx, *a, **kw:
        attend(q, cache, by_layer_alone(layer_idx, q.shape[1] == 1),
               *a, **kw))
    config = copy.deepcopy(CONFIG)
    broken = TpuBackend(
        model_config=rehearsal_backend.cfg, tokenizer="byte", batch_size=2,
        max_new_tokens=8, params=rehearsal_backend.params,
        **engine_setup.backend_kwargs(config, rehearsal=True))
    got = _parity(broken)
    assert not got["ok"]
    assert got["errors"][0] <= got["tolerance"] < min(got["errors"][1:])
    assert got["kv_decode_errors"][1] > got["kv_last_pass_tolerance"]


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


# -- the rooflines ----------------------------------------------------------------

SIZES = family_setup.sizes_of(CONFIG, False)
PEAKS = {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_per_s": 819e9}
PRECISION = {"weights": 1, "kv": 1, "prefill_matmul": "int8"}
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632
HEAD = 2048 * 49152


def test_counts_by_hand():
    assert roof.cache_layers(SIZES) == 48
    assert roof.layer_params(SIZES) == LAYER == 51_380_224
    assert roof.stack_params(SIZES) == 12 * LAYER
    assert roof.token_params(SIZES) == 4 * 12 * LAYER   # T times a token
    assert roof.decode_context([10], 3) == 11 + 12 + 13
    assert roof.prefill_attention_ops(SIZES, [3, 2]) == (
        4 * 16 * 128 * 48 * (6 + 3))
    got = roof.decode_attention(SIZES, [10], 3, 1)
    assert got["bytes"] == 4224 * 48 * 36
    assert got["ops"] == 4 * 16 * 128 * 48 * 36
    assert roof.decode_attention(SIZES, [10], 3, 2)["bytes"] == (
        16 * 2 * 128 * 2 * 48 * 36)


def test_kernel_rooflines_against_hand_worked_numbers():
    lens = [7800] * 8
    k = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, None, lens, 256)
    pairs = 8 * 7800 * 7801 // 2
    assert k["flash_prefill_attention"]["seconds"] == pytest.approx(
        4 * 16 * 128 * 48 * pairs / 197e12)
    assert 0.45 < k["flash_prefill_attention"]["seconds"] < 0.52
    ctx = 8 * (256 * 7801 + 256 * 255 // 2) * 48
    assert k["flash_decode_attention"]["bound"] == "memory"
    assert k["flash_decode_attention"]["seconds"] == pytest.approx(
        4224 * ctx / 819e9)
    # ~12.9 GB a step: 15.8 ms, 256 steps ~4.0 s
    assert 3.9 < k["flash_decode_attention"]["seconds"] < 4.2


def test_dispatch_roofline_reads_the_layers_weights_t_times_a_step():
    lens = [7800] * 8
    d = roof.dispatch(SIZES, PRECISION, PEAKS, None, lens, 256)
    assert d["prefill_matmul_ops"] == (
        2 * 48 * LAYER * 62400 + 2 * HEAD * 8)
    assert d["decode_weight_bytes"] == (4 * 12 * LAYER + HEAD) * 256
    once = (12 * LAYER + HEAD) * 256
    assert d["decode_weight_bytes"] - once == 3 * 12 * LAYER * 256
    assert d["decode_bytes"] == d["decode_weight_bytes"] + d["decode_kv_bytes"]
    assert d["decode_s"] == pytest.approx(d["decode_bytes"] / 819e9)
    assert d["prefill_s"] == pytest.approx(
        d["prefill_matmul_ops"] / 393e12
        + d["kernels"]["flash_prefill_attention"]["seconds"])
    assert d["total_s"] == pytest.approx(d["prefill_s"] + d["decode_s"])
    # the issue's sizing: ~0.78 + ~0.49 s of prefill, ~4.8 s of decode
    assert 1.2 < d["prefill_s"] < 1.35 and 4.7 < d["decode_s"] < 5.0
    assert d["decode_kv_bytes"] / d["decode_bytes"] > 0.8
    # bf16 weights and cache double both streams
    wide = roof.dispatch(SIZES, {"weights": 2, "kv": 2,
                                 "prefill_matmul": "bf16"}, PEAKS, None,
                         lens, 256)
    assert wide["decode_weight_bytes"] == 2 * d["decode_weight_bytes"]
    assert wide["prefill_s"] > d["prefill_s"]


# -- the readers --------------------------------------------------------------------


def _raw():
    return {
        "device": {"kind": "TPU v5 lite"}, "sizes": SIZES,
        "precision": PRECISION,
        "counts": {"experts": None,
                   "prefill_blocks": {"interior": 10, "edge": 4,
                                      "scores_computed": 3_000_000,
                                      "scores_needed": 2_000_000}},
        "trace": {"busy_s": 10.0, "modules": {"jit_generate": 9.0},
                  "module_calls": {"jit_generate": 1},
                  "device_ops": [["flash_prefill_attention", 1.25],
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
    assert _read("ouro_prefill_attention_roofline", raw) == pytest.approx(
        100 * least["flash_prefill_attention"]["seconds"] / 1.25)
    assert _read("ouro_decode_attention_roofline", raw) is None
    assert _read("ouro_attention_busy_share", raw) is None
    raw["trace"]["device_ops"] += [["flash_decode_attention", 2.75],
                                   ["while", 0.25]]
    assert _read("ouro_attention_busy_share", raw) == pytest.approx(40.0)
    # what the profiler lost inside a loop is counted against the kernel
    assert _read("ouro_decode_attention_roofline", raw) == pytest.approx(
        100 * least["flash_decode_attention"]["seconds"] / 3.0)
    whole = roof.dispatch(SIZES, PRECISION, PEAKS, None, [7800, 5000], 256)
    assert _read("generate_roofline_share_ouro", raw) == pytest.approx(
        100 * whole["total_s"] / 9.0)
    assert _read("ouro_prefill_scores_computed_over_needed", raw) == \
        pytest.approx(1.5)
    # two whole executions: both dispatches counted
    raw["trace"]["module_calls"]["jit_generate"] = 2
    both = whole["total_s"] + roof.dispatch(
        SIZES, PRECISION, PEAKS, None, [2000], 256)["total_s"]
    assert _read("generate_roofline_share_ouro", raw) == pytest.approx(
        100 * both / 9.0)


def test_readers_with_nothing_to_read_leave_their_metric_out():
    """A checkout without this PR's program (no span, no counter, no
    roofline module) gives None, never an exception."""
    raw = _raw()
    raw["trace"] = None
    raw["traced"] = None
    raw["counts"]["prefill_blocks"] = {"interior": 10}
    for name in ("generate_roofline_share_ouro",
                 "ouro_prefill_attention_roofline",
                 "ouro_decode_attention_roofline",
                 "ouro_attention_busy_share",
                 "ouro_prefill_scores_computed_over_needed"):
        assert _read(name, raw) is None, name


# -- the cell ---------------------------------------------------------------------------


def test_the_cell_its_configuration_and_its_metrics_are_listed():
    """By membership: where in a list an entry stands, and how many cells
    there are, is the driver's to check, not this file's."""
    cell = cells.find_cell(BENCH, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["config"] == "ouro-2.6b-l12-int8"
    assert cell["traffic"] == "offline-mapreduce-8k-loop"
    assert cell["config"] in [c["name"] for c in BENCH["configs"]]
    mine = {m["name"] for m in cells.metrics_for(BENCH, "per_layer", CELL)}
    own = {"generate_roofline_share_ouro", "ouro_prefill_attention_roofline",
           "ouro_decode_attention_roofline", "ouro_attention_busy_share",
           "ouro_prefill_scores_computed_over_needed"}
    shared = {"host_share.offline", "generate_device_s_per_dispatch",
              "device_idle.offline", "idle_in_engine_host.offline",
              "idle_in_pipeline_host.offline", "idle_unexplained.offline"}
    assert mine == own | shared
    # one pass of the weights a token, and a driver of their own: not its
    assert not mine & {"generate_roofline_share",
                       "prefill_attention_busy_share",
                       "decode_attention_busy_share"}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in own:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "docs_per_min"
        assert m["layer"] == "model and kernels"
        spec = cells.load_layer_metric(name)
        assert spec["drivers"] == ["offline_pipeline_family"]
        if "roofline" in spec:
            assert spec["roofline"] == "roofline_ouro"
            assert spec["reader"].startswith("state_")
        if name.endswith("_roofline"):
            assert m["unit"] == "%" and m["better"] == "higher"
    assert {m["name"] for m in cells.metrics_for(BENCH, "end_to_end", CELL)
            } == {"docs_per_min", "setup_s"}
    assert cells.validate(BENCH, ROOT) == []
    traffic = cells.load_traffic("offline-mapreduce-8k-loop")
    base = cells.load_traffic("offline-mapreduce-8k")
    for key in ("doc_tokens", "chunks_per_doc", "chunk_size", "chunk_overlap",
                "token_max", "max_new_tokens", "bpe_vocab", "bpe_train_words",
                "warmup_reduce_summaries", "approach", "rehearsal"):
        assert traffic[key] == base[key], key
    assert traffic["driver"] == "offline_pipeline_family"
    assert traffic["min_group_seconds"] > 0 and traffic["trace_seconds"] > 0


def test_the_driver_finds_this_set_up_module():
    import importlib

    mod = importlib.import_module(f"benchmarks.{CONFIG['setup_module']}")
    for fn in ("model_config", "start_weights", "sizes_of", "sizes_from",
               "parity_with_reference"):
        assert callable(getattr(mod, fn)), fn


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    """The whole cell at a tiny size on the CPU, both kernels interpreted:
    the driver, the set-up module, parity, warm-up, a window, the
    readers."""
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 50), "--seconds", "2", "--trace",
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
        assert set(counted) == {"ouro_prefill_scores_computed_over_needed"}
        assert 1.0 <= counted["ouro_prefill_scores_computed_over_needed"] < 6
