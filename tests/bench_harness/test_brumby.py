"""CPU tests of the benchmark's own parts for the Brumby family: the run-time
parity check and what it has to catch (a fault of the equations, a state
kept a precision below), the rooflines against hand-worked numbers, the
readers on a known record, the cell's rehearsal, and the configuration
file's keys and arithmetic.

The cell, its configuration and its metrics are found by MEMBERSHIP: where
an entry stands in a list, and how many entries a list has, is the driver's
to check and the next cell's to change.

Nothing here touches the TPU library at import.
"""
import copy
import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import cells, engine_setup  # noqa: E402
from benchmarks import engine_setup_brumby as family_setup  # noqa: E402
from benchmarks import roofline_brumby as roof  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
NAME = "brumby-14b-l10-int8"
CONFIG = cells.load_config(BENCH, NAME)
TRAFFIC = "offline-mapreduce-8k-retention"
CELL = f"{NAME}.{TRAFFIC}"
OWN = {"generate_roofline_share_brumby", "retention_prefill_scan_roofline",
       "retention_decode_update_roofline", "retention_busy_share",
       "retention_tokens_computed_over_real"}
SHARED = {"host_share.offline", "generate_device_s_per_dispatch",
          "device_idle.offline", "idle_in_engine_host.offline",
          "idle_in_pipeline_host.offline", "idle_unexplained.offline"}
LIMITS = ("tolerance", "state_tolerance", "normaliser_tolerance",
          "state_step_tolerance")


# -- the parity check ----------------------------------------------------------


def _backend(**cfg_kw):
    import jax

    from vnsum_tpu.backend.engine import TpuBackend

    config = copy.deepcopy(CONFIG)
    cfg = family_setup.model_config(config, rehearsal=True)
    params = family_setup.start_weights(config, cfg, 11)
    cfg = dataclasses.replace(cfg, **cfg_kw)
    return TpuBackend(
        model_config=cfg, tokenizer="byte", batch_size=2, max_new_tokens=8,
        params=jax.block_until_ready(params),
        **engine_setup.backend_kwargs(config, rehearsal=True))


@pytest.fixture(scope="module")
def rehearsal_backend():
    return _backend()


def _parity(backend, faults=(), config=None, seed=3):
    return family_setup.parity_with_reference(
        backend, config or copy.deepcopy(CONFIG), seed, rehearsal=True,
        faults=faults)


def test_parity_holds_on_the_timed_programs_own_paths(rehearsal_backend):
    got = _parity(rehearsal_backend)
    assert got["ok"] and got["kernel"] and got["state_dtype"] == "float32"
    assert len(got["errors"]) == len(got["state_errors"]) == 5
    assert got["pad"] == 106 and got["bucket"] == 256
    assert got["cache_leaves"] == ["norm", "ret"]   # no keys and values
    assert len(got["slow_heads"]) == 1
    # the limits have room on both sides of what a clean run reads
    for read, limit in (("error", "tolerance"),
                        ("state_error", "state_tolerance"),
                        ("normaliser_error", "normaliser_tolerance"),
                        ("state_step_error", "state_step_tolerance")):
        assert 0 < got[read] * 1.3 < got[limit], (read, got[read])


@pytest.mark.parametrize("fault", [
    "degree_one", "no_normaliser", "normaliser_not_decayed",
    "decay_after_write", "phi_offdiag_one", "one_gate_all_heads",
    "gate_per_query_head", "no_rope", "no_qk_norm"])
def test_parity_catches_a_departure_from_the_equations(fault,
                                                       rehearsal_backend):
    got = _parity(rehearsal_backend, (fault,))
    assert not got["ok"] and got["faults"] == [fault]


@pytest.mark.parametrize("fault", ["no_scale", "scale_on_both"])
def test_the_scores_scale_is_what_parity_cannot_see(fault, rehearsal_backend):
    """Under the normaliser ``s^2`` cancels but for ``eps``: no check of
    outputs can hold the scale (``tests/test_model_brumby.py`` holds it, at
    an ``eps`` raised until it shows)."""
    got = _parity(rehearsal_backend, (fault,))
    assert got["ok"]


def test_a_state_kept_in_bfloat16_fails_by_the_states_limits():
    """The nearest precision below the configured float32 state: the same
    program with ``state_dtype`` bfloat16 (rounded after every chunk and
    step) is not correct."""
    got = _parity(_backend(state_dtype=__import__("jax").numpy.bfloat16))
    assert got["state_dtype"] == "bfloat16" and not got["ok"]
    assert got["state_step_error"] > 10 * got["state_step_tolerance"]
    assert got["normaliser_error"] > got["normaliser_tolerance"]
    assert got["error"] < got["tolerance"]      # the logits hardly show it


def test_one_broken_row_fails_the_check(monkeypatch, rehearsal_backend):
    real = rehearsal_backend.prefill_then_decode_logits

    def broken(*a, **kw):
        logits, state = real(*a, **kw)
        logits = __import__("numpy").array(logits)
        logits[2] *= 1.5
        return logits, state

    monkeypatch.setattr(rehearsal_backend, "prefill_then_decode_logits",
                        broken)
    got = _parity(rehearsal_backend)
    assert not got["ok"] and got["errors"][2] > got["tolerance"]


def test_a_prompt_that_fills_its_bucket_is_refused(rehearsal_backend):
    config = copy.deepcopy(CONFIG)
    config["rehearsal"]["parity"]["prompt_tokens"] = 256
    with pytest.raises(ValueError, match="parity prompt"):
        _parity(rehearsal_backend, config=config)


# -- the configuration file ------------------------------------------------------


def test_model_config_builds_the_published_widths_at_10_layers():
    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    assert (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.intermediate, cfg.vocab_size) == (
        10, 5120, 40, 8, 128, 17408, 151936)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.retention_degree,
            cfg.retention_eps, cfg.retention_chunk_size) == (
        1e6, 1e-6, 2, 1e-6, 256)
    assert cfg.max_seq_len == 8448 and not cfg.tie_embeddings
    kw = engine_setup.backend_kwargs(CONFIG, rehearsal=False)
    assert kw["quantize"] and kw["quantize_act"] and not kw["quantize_kv"]
    assert kw["prefill_chunk_tokens"] == 2048 and kw["mesh"] is None
    tiny = family_setup.model_config(CONFIG, rehearsal=True)
    assert (tiny.n_layers, tiny.n_heads, tiny.n_kv_heads, tiny.head_dim) == (
        3, 4, 2, 16)
    assert family_setup.sizes_from(cfg) == family_setup.sizes_of(CONFIG,
                                                                 False)


@pytest.mark.parametrize("key, value", [
    ("model_type", "qwen3"), ("attention_bias", True),
    ("hidden_act", "gelu"), ("sliding_window", 4096),
    ("use_sliding_window", True), ("rope_scaling", {"type": "yarn"}),
])
def test_a_mechanism_the_family_does_not_build_is_refused(key, value):
    config = {**copy.deepcopy(CONFIG), key: value}
    with pytest.raises(ValueError, match=key):
        family_setup.sizes_of(config, rehearsal=False)


def _catalog_row():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    return next(r for r in map(json.loads, catalog.read_text().splitlines())
                if r["name"] == "Brumby-14B-Base")


def test_config_files_keys_are_the_catalog_rows():
    """Every key of the catalog entry's config under the same name at the
    same value, but the one that is reduced."""
    row = _catalog_row()
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert CONFIG["source"] == entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 10
    assert CONFIG["published"] == {"num_hidden_layers": 40}


def test_config_file_states_the_deployment_and_every_inference():
    c = CONFIG
    assert c["chips"] == 1 and c["mesh"] is None
    assert c["checkpoint_seed"] == 60
    assert "expert_parallel" not in c
    assert set(c["assumed"]) >= {
        "retention_degree", "gate", "qk_norm_and_rope", "mixer_output",
        "retention_eps", "retention_chunk_size", "state_precision",
        "random_weights"}
    low = c["deployment"].lower()
    assert "stage 0 of a four-stage pipeline" in low
    assert "layers 0-9" in low and "whole vocabulary" in low
    assert c["engine"] == {
        "weights": "int8", "activations": "int8", "kv": "bf16",
        "state": "float32", "prefill_chunk_tokens": 2048, "batch": 12,
        "max_seq_len": 8448}
    # the harness's kv key knows int8, bf16 and auto: bf16 is "no int8
    # cache", and the notes say that there is none at all
    assert "no keys and values" in c["engine_notes"].lower()
    for key in ("assumed", "deployment", "bytes", "engine_notes", "engine",
                "checkpoint_notes", "rehearsal", "reference"):
        assert c[key], key
    assert c["reference"]["file"] == "benchmarks/reference_brumby.py"
    parity = c["reference"]["parity"]
    assert (parity["prompt_tokens"], parity["bucket"],
            parity["decode_steps"]) == (7000, 8192, 8)
    for limit in LIMITS:
        assert 0 < parity[limit] <= 0.2, limit
        assert 0 < c["rehearsal"]["parity"][limit] < 1
    assert "bfloat16" in parity["what"]


def test_config_files_byte_arithmetic_is_the_models():
    import jax

    from vnsum_tpu.models import brumby
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    tree = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                          jax.random.key(0))

    def nbytes(t):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))

    b = CONFIG["bytes"]
    assert nbytes(tree) == b["weights"] == 4_864_002_368
    assert nbytes(tree["layers"]) == b["layers_10"] == 10 * b["layer"]
    assert b["layer"] == b["mixer"] + b["feed_forward_a_layer"]
    assert nbytes(tree["embed"]) == b["embedding"]
    assert nbytes(tree["lm_head"]) == b["head"]
    cache = jax.eval_shape(lambda: brumby.init_cache(cfg, 12, 8448))
    assert nbytes(cache["ret"]) == 120 * b["state_a_row_and_layer"]
    assert nbytes(cache["norm"]) == 120 * b["normaliser_a_row_and_layer"]
    assert nbytes(cache) == 120 * b["state_and_normaliser_a_row_and_layer"] \
        == 4_152_360_960
    # the issue's table, from the row: 62.9 M of mixer products and 267.4 M
    # of SwiGLU a layer
    sizes = family_setup.sizes_of(CONFIG, False)
    assert roof.mixer_params(sizes) == 62_914_560
    assert roof.layer_params(sizes) == 62_914_560 + 267_386_880


# -- the rooflines ------------------------------------------------------------------

SIZES = family_setup.sizes_of(CONFIG, rehearsal=False)
PRECISION = engine_setup.precision_of(CONFIG)
PEAKS = {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_per_s": 819e9}


def test_params_and_a_tokens_work_by_hand():
    assert roof.phi_width(SIZES) == 8256
    assert roof.token_params(SIZES) == 10 * (
        2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408)
    assert roof.state_bytes_a_row_and_layer(SIZES) == 8 * 8256 * 129 * 4
    scan = roof.scan_a_token(SIZES)
    assert scan["ops"] == (2 * 8256 * 129 * 48          # read 40, write 8
                           + 128.5 * 40 * (256 + 258)   # the in-chunk pairs
                           + 8256 * 48)                 # phi
    # ~105 MFLOP a token and layer (the issue: 103)
    assert 104e6 < scan["ops"] < 106e6
    assert scan["bytes"] == (80 + 16) * 128 * 2 + 32


def test_kernel_rooflines_against_hand_worked_numbers():
    lens, steps = [7800, 5000], 256
    got = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, None, lens,
                                    steps)
    tokens = 12800
    scan = roof.scan_a_token(SIZES)
    scan_bytes = (scan["bytes"] * tokens + 8 * 8256 * 129 * 4 * 2) * 10
    assert got["retention_prefill_scan"]["seconds"] == pytest.approx(
        max(scan["ops"] * tokens * 10 / 197e12, scan_bytes / 819e9))
    assert got["retention_prefill_scan"]["bound"] == "compute"
    assert got["retention_decode_update"]["seconds"] == pytest.approx(
        2 * 2 * 10 * 8 * 8256 * 129 * 4 * 256 / 819e9)
    assert got["retention_decode_update"]["bound"] == "memory"


def test_dispatch_roofline_adds_up_by_hand():
    lens, steps = [7800, 5000], 256
    d = roof.dispatch(SIZES, PRECISION, PEAKS, None, lens, steps)
    k = d["kernels"]
    head = 5120 * 151936
    params = roof.token_params(SIZES)
    assert d["prefill_matmul_ops"] == 2 * params * 12800 + 2 * head * 2
    assert d["prefill_s"] == pytest.approx(
        d["prefill_matmul_ops"] / 393e12
        + k["retention_prefill_scan"]["seconds"])
    state = 2 * 2 * 10 * 8 * 8256 * 129 * 4 * 256
    assert d["decode_state_bytes"] == state
    assert d["decode_bytes"] == (params + head) * 256 + state
    assert d["decode_s"] == pytest.approx(d["decode_bytes"] / 819e9)
    assert d["total_s"] == pytest.approx(d["prefill_s"] + d["decode_s"])
    # the cell's map dispatch: the state's read and write is two thirds of a
    # decode step's bytes, and the recurrence ~half of the least time
    full = roof.dispatch(SIZES, PRECISION, PEAKS, None, [7900] * 12, 256)
    assert 0.6 < full["decode_state_bytes"] / full["decode_bytes"] < 0.7
    both = sum(v["seconds"] for v in full["kernels"].values())
    assert 0.45 < both / full["total_s"] < 0.6


# -- the readers ---------------------------------------------------------------------


def _raw():
    dispatch = {"prompt_lens": [7800, 5000], "steps": 256, "experts": None}
    return {
        "device": {"kind": "TPU v5 lite"}, "sizes": SIZES,
        "precision": PRECISION,
        "counts": {"experts": None,
                   "prefill_blocks": {"retention_tokens_real": 10 * 7800,
                                      "retention_tokens_computed": 10 * 7936}},
        "trace": {"busy_s": 10.0, "modules": {"jit_generate": 9.0},
                  "module_calls": {"jit_generate": 1},
                  "device_ops": [["retention_prefill_scan", 1.0],
                                 ["retention_decode_update", 4.0],
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
        SIZES, PRECISION, PEAKS, None, [7800, 5000], 256)
    for name, kernel, measured in (
            ("retention_prefill_scan_roofline", "retention_prefill_scan", 1.0),
            ("retention_decode_update_roofline", "retention_decode_update",
             4.0)):
        assert _read(name, raw) == pytest.approx(
            100 * least[kernel]["seconds"] / measured), name
        assert 0 < _read(name, raw) < 100
    raw["trace"]["device_ops"] += [["while", 0.5]]
    # what the profiler lost inside a loop is counted against the kernel
    assert _read("retention_decode_update_roofline", raw) == pytest.approx(
        100 * least["retention_decode_update"]["seconds"] / 4.5)
    whole = roof.dispatch(SIZES, PRECISION, PEAKS, None, [7800, 5000], 256)
    assert _read("generate_roofline_share_brumby", raw) == pytest.approx(
        100 * whole["total_s"] / 9.0)
    assert _read("retention_busy_share", raw) == pytest.approx(50.0)
    assert _read("retention_tokens_computed_over_real", raw) == \
        pytest.approx(7936 / 7800)


def test_readers_with_nothing_to_read_leave_their_metric_out():
    """As on the parent commit, whose program has no such family, kernel or
    counter: None, never an exception."""
    bare = {"device": {"kind": "TPU v5 lite"}, "counts": {}, "trace": None,
            "traced": None}
    for m in cells.metrics_for(BENCH, "per_layer", CELL):
        if m["name"] not in ("host_share.offline",):
            assert _read(m["name"], bare) is None, m["name"]
    raw = _raw()
    spec = dict(cells.load_layer_metric("retention_prefill_scan_roofline"),
                roofline="roofline_of_no_such_family")
    reader = cells.load_module("readers", "state_kernel_roofline")
    assert reader.read(spec, raw) is None
    del raw["counts"]["prefill_blocks"]["retention_tokens_real"]
    assert _read("retention_tokens_computed_over_real", raw) is None
    raw["trace"]["device_ops"] = [["fusion.7", 0.3]]
    assert _read("retention_busy_share", raw) is None
    assert _read("retention_decode_update_roofline", raw) is None
    raw["trace"]["module_calls"] = {}
    assert _read("generate_roofline_share_brumby", raw) is None


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
        assert spec["roofline"] == "roofline_brumby"
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
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == NAME] == [CELL]
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert len(entry["why"]) <= 200
    traffic = cells.load_traffic(TRAFFIC)
    base = cells.load_traffic("offline-mapreduce-8k")
    for key in ("doc_tokens", "chunks_per_doc", "chunk_size", "chunk_overlap",
                "token_max", "max_new_tokens", "bpe_vocab", "bpe_train_words",
                "warmup_reduce_summaries", "approach", "rehearsal"):
        assert traffic[key] == base[key], key
    assert traffic["driver"] == "offline_pipeline_family"
    assert traffic["min_group_seconds"] > 0 and traffic["trace_seconds"] > 0


def test_the_cell_is_only_new_files():
    """Nothing under the benchmark's paths that the parent had is edited:
    git says which files differ from HEAD's, where there is a repository."""
    p = subprocess.run(["git", "status", "--porcelain", "--", "benchmarks",
                        "tests/bench_harness"], capture_output=True,
                       text=True, cwd=ROOT)
    if p.returncode:
        pytest.skip("no git repository here")
    edited = [line for line in p.stdout.splitlines()
              if line[0] not in "A?"]     # added (staged) or untracked
    assert edited == [], edited


def test_the_driver_finds_this_familys_setup_module():
    import importlib

    mod = importlib.import_module(f"benchmarks.{CONFIG['setup_module']}")
    for fn in ("model_config", "start_weights", "sizes_of", "sizes_from",
               "parity_with_reference"):
        assert callable(getattr(mod, fn)), fn


# -- the cell, rehearsed ----------------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    """The whole cell at a tiny size on the CPU, both kernels interpreted:
    the driver, the family's set-up, parity, warm-up, a window, the
    readers."""
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 60), "--seconds", "2", "--trace",
         str(trace), "--rehearsal"],
        capture_output=True, text=True, cwd=ROOT, timeout=900,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu" and line["correct"] is False
    # correct on everything but the platform
    assert "failed checks: ['platform_is_tpu']" in p.stderr, p.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    group = "per_layer" if trace else "end_to_end"
    assert set(line["metrics"]) == {
        m["name"] for m in cells.metrics_for(BENCH, group, CELL)}
    if trace:
        counted = {n: m["value"] for n, m in line["metrics"].items()
                   if m["value"] != "not measured"}
        assert set(counted) == {"retention_tokens_computed_over_real"}
        assert 1.0 <= counted["retention_tokens_computed_over_real"] < 1.1
