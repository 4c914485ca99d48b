"""CPU tests of the benchmark's own parts for the Ling-3.0-flash family: the
run-time parity check and what it has to catch (a fault of the equations, a
state or a latent kept a precision below), the rooflines against hand-worked
numbers, the readers on a known record, the cell's rehearsal, and the
configuration file's keys and arithmetic.

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
from benchmarks import engine_setup_ling as family_setup  # noqa: E402
from benchmarks import roofline_ling as roof  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
NAME = "ling-3.0-flash-ep4-l12-int8"
CONFIG = cells.load_config(BENCH, NAME)
TRAFFIC = "offline-mapreduce-8k-kda-ep"
CELL = f"{NAME}.{TRAFFIC}"
OWN = {"generate_roofline_share_ling", "kda_prefill_scan_roofline",
       "kda_decode_update_roofline", "ling_mla_prefill_attention_roofline",
       "ling_mla_decode_attention_roofline", "ling_expert_matmul_roofline",
       "kda_busy_share", "kda_scan_tokens_computed_over_real"}
SHARED = {"host_share.offline", "generate_device_s_per_dispatch",
          "device_idle.offline", "idle_in_engine_host.offline",
          "idle_in_pipeline_host.offline", "idle_unexplained.offline",
          "expert_ffn_busy_share", "expert_load_max_over_mean",
          "expert_distinct_per_step", "expert_held_share",
          "mla_attention_busy_share"}
LIMITS = ("tolerance", "decode_tolerance", "state_tolerance",
          "state_step_tolerance", "latent_tolerance")


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
    assert got["last_row_error"] == got["errors"][-1]
    assert got["first_layer_picks_ok"]
    # every real token on 5 sparse layers x 3 picks, half the experts held
    assert got["slots_routed"] == 154 * 5 * 3
    assert 0 < got["slots_held"] < got["slots_routed"]
    assert len(got["latent_errors"]) == 2 and got["slow_channels"] == 16
    # the limits have room on both sides of what a clean run reads
    for read, limit in (("error", "tolerance"),
                        ("last_row_error", "decode_tolerance"),
                        ("state_error", "state_tolerance"),
                        ("state_step_error", "state_step_tolerance"),
                        ("latent_error", "latent_tolerance")):
        assert 0 < got[read] * 1.3 < got[limit], (read, got[read])
    assert got["latent_grid_distance"] > 2 * got["latent_grid_floor"]


@pytest.mark.parametrize("fault", [
    "decay_after_write", "no_beta_erase", "scalar_decay", "softplus_gate",
    "no_k_norm", "latent_unnormed", "no_renorm", "no_shared"])
def test_parity_catches_a_departure_from_the_equations(fault,
                                                       rehearsal_backend):
    got = _parity(rehearsal_backend, (fault,))
    assert not got["ok"] and got["faults"] == [fault]


def test_a_state_kept_in_bfloat16_fails_by_the_states_limits():
    """The nearest precision below the configured float32 state: the same
    program with ``state_dtype`` bfloat16 (rounded after every chunk and
    step) is not correct."""
    got = _parity(_backend(state_dtype=__import__("jax").numpy.bfloat16))
    assert got["state_dtype"] == "bfloat16" and not got["ok"]
    assert got["state_step_error"] > got["state_step_tolerance"]


def test_a_latent_rounded_to_int8_fails_by_the_grids_floor():
    got = _parity(_backend(latent_int8=True))
    assert not got["ok"]
    assert got["latent_grid_distance"] < got["latent_grid_floor"]


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


def test_model_config_builds_the_published_widths_at_12_layers():
    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    assert (cfg.n_layers, cfg.n_kda, cfg.n_mla, cfg.n_sparse) == (12, 10, 2,
                                                                  10)
    assert (cfg.dim, cfg.n_heads, cfg.head_dim, cfg.vocab_size) == (
        2560, 32, 128, 157184)
    assert (cfg.n_routed_experts, cfg.n_held, cfg.expert_offset) == (
        512, 128, 0)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.intermediate, cfg.moe_intermediate, cfg.shared_intermediate,
            cfg.num_experts_per_tok, cfg.n_group, cfg.topk_group,
            cfg.routed_scaling_factor) == (6144, 768, 768, 8, 8, 4, 2.5)
    assert (cfg.short_conv_kernel_size, cfg.kda_lower_bound,
            cfg.kda_chunk_size, cfg.layer_group_size,
            cfg.first_k_dense_replace) == (4, -5, 64, 6, 2)
    assert cfg.max_seq_len == 8448 and not cfg.tie_embeddings
    kw = engine_setup.backend_kwargs(CONFIG, rehearsal=False)
    assert kw["quantize"] and kw["quantize_act"] and not kw["quantize_kv"]
    assert kw["prefill_chunk_tokens"] == 2048 and kw["mesh"] is None
    tiny = family_setup.model_config(CONFIG, rehearsal=True)
    assert (tiny.n_layers, tiny.n_routed_experts, tiny.n_held) == (6, 16, 8)
    assert family_setup.sizes_from(cfg) == family_setup.sizes_of(CONFIG,
                                                                 False)


@pytest.mark.parametrize("key, value, text", [
    ("q_lora_rank", 1536, "q_lora_rank"),
    ("score_function", "softmax", "score_function"),
    ("kda_safe_gate", False, "kda_safe_gate"),
    ("gated_attention_proj_granularity_type", "channel_wise", "granularity"),
    ("use_kda_lora", True, "use_kda_lora"),
    ("group_norm_size", 4, "group_norm_size"),
])
def test_a_mechanism_the_family_does_not_build_is_refused(key, value, text):
    config = {**copy.deepcopy(CONFIG), key: value}
    with pytest.raises(ValueError, match=text):
        family_setup.sizes_of(config, rehearsal=False)


def test_a_depth_that_reaches_a_clamped_layer_is_refused():
    config = {**copy.deepcopy(CONFIG), "num_hidden_layers": 36}
    with pytest.raises(ValueError, match="clamp"):
        family_setup.sizes_of(config, rehearsal=False)


def _catalog_row():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    return next(r for r in map(json.loads, catalog.read_text().splitlines())
                if r["name"] == "Ling-3.0-flash-VL")


def test_config_files_keys_are_the_catalog_rows():
    """Every key of the catalog entry's config under the same name at the
    same value, but the two that are reduced."""
    row = _catalog_row()
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert CONFIG["source"] == entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers",
                                                     "num_experts"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"]) == (12, 128)


def test_config_file_states_the_deployment_and_every_inference():
    c = CONFIG
    assert c["chips"] == 1 and c["mesh"] is None
    assert c["checkpoint_seed"] == 56
    assert c["expert_parallel"] == {
        "chips_sharing_a_layer": 4, "expert_offset": 0, "experts_held": 128,
        "replicated": ["kda mixers", "mla mixers", "shared expert", "router",
                       "expert_bias", "embedding", "lm_head"]}
    assert set(c["assumed"]) >= {
        "layer_pattern", "tie_word_embeddings", "kda", "kda_gate", "mla",
        "use_qk_norm", "router", "norms", "precisions", "random_weights"}
    assert "experts 0-127" in c["deployment"].lower().replace(
        "EXPERTS", "experts")
    assert c["engine"] == {
        "weights": "int8", "activations": "int8", "kv": "bf16",
        "state": "float32", "prefill_chunk_tokens": 2048, "batch": 24,
        "max_seq_len": 8448}
    assert c["reference"]["file"] == "benchmarks/reference_ling.py"
    parity = c["reference"]["parity"]
    assert (parity["prompt_tokens"], parity["bucket"],
            parity["decode_steps"]) == (7000, 8192, 8)
    for limit in LIMITS:
        assert 0 < parity[limit] <= 1.5, limit
        assert 0 < c["rehearsal"]["parity"][limit] < 1
    assert 0 < parity["latent_grid_floor"] < 0.2
    assert "bfloat16" in parity["what"] and "int8" in parity["what"]


def test_config_files_byte_arithmetic_is_the_models():
    import jax

    from vnsum_tpu.models import ling
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    tree = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                          jax.random.key(0))

    def nbytes(t):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))

    b = CONFIG["bytes"]
    assert nbytes(tree) == b["weights"] == 9_175_865_344
    assert nbytes(tree["kda"]) == 10 * b["kda_mixer"]
    assert nbytes(tree["mla"]) == 2 * b["mla_mixer"]
    assert nbytes(tree["dense"]) == 2 * b["dense_ffn"]
    assert nbytes(tree["layers"]) == 10 * b["sparse_layer"]
    assert b["routed_experts_held_a_layer"] == 128 * b["routed_expert"]
    cache = jax.eval_shape(lambda: ling.init_cache(cfg, 24, 8448))
    assert nbytes(cache["latent"]) == 24 * b["latent_cache_a_row"]
    assert nbytes(cache["kda"]) == 24 * b["kda_state_a_row"] == 503_316_480
    assert nbytes(cache["conv"]) == 24 * b["conv_tail_a_row"]
    # the issue's table, from the row
    assert roof.expert_params(family_setup.sizes_of(CONFIG, False)) \
        == 5_898_240


# -- the rooflines ------------------------------------------------------------------

SIZES = family_setup.sizes_of(CONFIG, rehearsal=False)
PRECISION = engine_setup.precision_of(CONFIG)
PEAKS = {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_per_s": 819e9}
EXPERTS = {"slots_routed": 1000, "slots_held": 250, "decode_touched": 40 * 20,
           "decode_layer_steps": 20}


def test_params_by_hand():
    assert (roof.kda_layers(SIZES), roof.mla_layers(SIZES),
            roof.sparse_layers(SIZES)) == (10, 2, 10)
    assert roof.kda_params(SIZES) == 5 * 2560 * 4096 + 2 * 2560 * 32
    assert roof.mla_params(SIZES) == (
        2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
        + 32 * 128 * 2560)
    assert roof.router_params(SIZES) == 2560 * 512
    assert roof.held_share(EXPERTS) == 0.25
    assert roof.params_a_token(SIZES, 0.25) == roof.fixed_params(SIZES) \
        + 10 * 8 * 0.25 * 5_898_240
    scan = roof.kda_scan_a_token(SIZES)
    assert scan["ops"] == 32 * (5 * 64 * 128 + 6 * 128 * 128)
    assert scan["bytes"] == 4 * 4096 * 2 + 4 * 4096 + 4 * 32
    assert roof.kda_state_bytes_a_row(SIZES) == 4 * 32 * 128 * 128


def test_kernel_rooflines_against_hand_worked_numbers():
    lens, steps = [7800, 5000], 256
    got = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, EXPERTS, lens,
                                    steps)
    tokens = 12800
    scan_ops = 32 * (5 * 64 * 128 + 6 * 128 * 128) * tokens * 10
    scan_bytes = (4 * 4096 * 2 + 4 * 4096 + 128) * tokens * 10 \
        + 2 * 2 * 10 * 2_097_152
    assert got["kda_prefill_scan"]["seconds"] == pytest.approx(
        max(scan_ops / 197e12, scan_bytes / 819e9))
    assert got["kda_prefill_scan"]["bound"] == "memory"
    assert got["kda_decode_update"]["seconds"] == pytest.approx(
        2 * 2 * 10 * 2_097_152 * 256 / 819e9)
    assert got["mla_prefill_attention"]["seconds"] == pytest.approx(
        32 * 320 * 2 * (7800 ** 2 + 5000 ** 2) / 197e12)
    ctx = sum(256 * (n + 1) + 256 * 255 // 2 for n in lens) * 2
    assert got["mla_decode_attention"]["seconds"] == pytest.approx(
        max(2 * 32 * (1024 + 64) * ctx / 197e12, 576 * 2 * ctx / 819e9))
    slots = 8 * 0.25 * 10
    assert got["expert_grouped_matmul"]["seconds"] == pytest.approx(
        2 * 5_898_240 * slots * tokens / 393e12
        + max(2 * 5_898_240 * slots * 2 * 256 / 393e12,
              5_898_240 * 40 * 256 * 10 / 819e9))


def test_dispatch_roofline_adds_up_by_hand():
    lens, steps = [7800, 5000], 256
    d = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, lens, steps)
    k = d["kernels"]
    head = 2560 * 157184
    token_params = roof.params_a_token(SIZES, 0.25)
    assert d["prefill_matmul_ops"] == 2 * token_params * 12800 + 2 * head * 2
    assert d["prefill_s"] == pytest.approx(
        d["prefill_matmul_ops"] / 393e12 + k["kda_prefill_scan"]["seconds"]
        + k["mla_prefill_attention"]["seconds"])
    state = (2 * 10 * 2_097_152 + 2 * 10 * 3 * 12288 * 2) * 2 * 256
    assert d["decode_state_bytes"] == state
    ctx = sum(256 * (n + 1) + 256 * 255 // 2 for n in lens) * 2
    assert d["decode_bytes"] == pytest.approx(
        (roof.fixed_params(SIZES) + head) * 256
        + 5_898_240 * 40 * 256 * 10 + state + 576 * 2 * ctx)
    assert d["total_s"] == pytest.approx(d["prefill_s"] + d["decode_s"])
    # no counters (a program that counts no experts): the routed part is 0
    bare = roof.dispatch(SIZES, PRECISION, PEAKS, None, lens, steps)
    assert bare["decode_expert_bytes"] == 0 and bare["total_s"] < d["total_s"]


# -- the readers ---------------------------------------------------------------------


def _raw():
    dispatch = {"prompt_lens": [7800, 5000], "steps": 256, "experts": EXPERTS}
    return {
        "device": {"kind": "TPU v5 lite"}, "sizes": SIZES,
        "precision": PRECISION,
        "counts": {"experts": {**EXPERTS, "tokens": [[3, 1], [2, 2]],
                               "decode_reads_possible": 20 * 128},
                   "prefill_blocks": {"kda_tokens_real": 10 * 7800,
                                      "kda_tokens_computed": 10 * 7808,
                                      "latent_keys_real": 2 * 7800,
                                      "latent_keys_expanded": 2 * 30000}},
        "trace": {"busy_s": 10.0, "modules": {"jit_generate": 9.0},
                  "module_calls": {"jit_generate": 1},
                  "device_ops": [["kda_prefill_scan", 1.0],
                                 ["kda_decode_update", 0.5],
                                 ["mla_prefill_attention", 0.25],
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
    for name, kernel, measured in (
            ("kda_prefill_scan_roofline", "kda_prefill_scan", 1.0),
            ("kda_decode_update_roofline", "kda_decode_update", 0.5),
            ("ling_mla_prefill_attention_roofline", "mla_prefill_attention",
             0.25),
            ("ling_expert_matmul_roofline", "expert_grouped_matmul", 2.0)):
        assert _read(name, raw) == pytest.approx(
            100 * least[kernel]["seconds"] / measured), name
    assert _read("ling_mla_decode_attention_roofline", raw) is None
    raw["trace"]["device_ops"] += [["mla_decode_attention", 0.4],
                                   ["while", 0.1]]
    # what the profiler lost inside a loop is counted against the kernel
    assert _read("ling_mla_decode_attention_roofline", raw) == \
        pytest.approx(100 * least["mla_decode_attention"]["seconds"] / 0.5)
    assert _read("kda_decode_update_roofline", raw) == pytest.approx(
        100 * least["kda_decode_update"]["seconds"] / 0.6)
    whole = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, [7800, 5000], 256)
    assert _read("generate_roofline_share_ling", raw) == pytest.approx(
        100 * whole["total_s"] / 9.0)
    assert _read("kda_busy_share", raw) == pytest.approx(15.0)
    assert _read("kda_scan_tokens_computed_over_real", raw) == \
        pytest.approx(7808 / 7800)
    # the shared metrics' files hold for this cell's record as written
    assert _read("expert_ffn_busy_share", raw) == pytest.approx(20.0)
    assert _read("mla_attention_busy_share", raw) == pytest.approx(6.5)
    assert _read("expert_held_share", raw) == pytest.approx(25.0)
    assert _read("expert_load_max_over_mean", raw) == pytest.approx(5 / 4)
    assert _read("expert_distinct_per_step", raw) == pytest.approx(
        100 * 800 / (20 * 128))


def test_readers_with_nothing_to_read_leave_their_metric_out():
    """As on the parent commit, whose program has no such family, kernel or
    counter: None, never an exception."""
    bare = {"device": {"kind": "TPU v5 lite"}, "counts": {}, "trace": None,
            "traced": None}
    for m in cells.metrics_for(BENCH, "per_layer", CELL):
        if m["name"] not in ("host_share.offline",):
            assert _read(m["name"], bare) is None, m["name"]
    raw = _raw()
    spec = dict(cells.load_layer_metric("kda_prefill_scan_roofline"),
                roofline="roofline_of_no_such_family")
    reader = cells.load_module("readers", "state_kernel_roofline")
    assert reader.read(spec, raw) is None
    del raw["counts"]["prefill_blocks"]["kda_tokens_real"]
    assert _read("kda_scan_tokens_computed_over_real", raw) is None
    raw["trace"]["module_calls"] = {}
    assert _read("kda_prefill_scan_roofline", raw) is None
    assert _read("generate_roofline_share_ling", raw) is None


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
        assert spec["roofline"] == "roofline_ling"
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
    # the new entries stand at the end of their lists
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == NAME
    assert {m["name"] for m in BENCH["per_layer"][-len(OWN):]} == OWN
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
    """The whole cell at a tiny size on the CPU, every kernel interpreted:
    the driver, the family's set-up, parity, warm-up, a window, the
    readers."""
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 56), "--seconds", "2", "--trace",
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
        assert set(counted) == {"kda_scan_tokens_computed_over_real",
                                "expert_load_max_over_mean",
                                "expert_distinct_per_step",
                                "expert_held_share"}
        assert 1.0 <= counted["kda_scan_tokens_computed_over_real"] < 2.0
        assert counted["expert_load_max_over_mean"] >= 1.0
        assert 0 < counted["expert_distinct_per_step"] <= 100
        assert 20 < counted["expert_held_share"] < 80
