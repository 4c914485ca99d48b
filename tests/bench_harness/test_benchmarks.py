"""CPU tests of the benchmark harness (benchmarks/): its files resolve, its
arithmetic is right, its last line keeps the contract, and each driver runs
end to end at a tiny size through ``run.py --rehearsal``.

Nothing here touches the TPU library at import; the rehearsals run in
child processes on the CPU backend.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import cells, reading, roofline, stats, textgen, trace_reduce  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

BENCH_DIR = ROOT / "benchmarks"
BENCH = cells.load_benchmark(ROOT)
RUN = str(BENCH_DIR / "run.py")


def _stems(sub):
    return sorted(p.stem for p in (BENCH_DIR / sub).glob("*.json"))


# -- files resolve -----------------------------------------------------------


def test_benchmark_json_resolves_every_name():
    assert cells.validate(BENCH, ROOT) == []


def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(1 <= len(c[k]) <= 200 for k in ("source", "why"))
        assert len(c["reduced"]) <= 16
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200
    assert 1 <= BENCH["run_seconds"] <= 51
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("name", _stems("configs"))
def test_config_file_states_the_registry_sizes(name):
    from vnsum_tpu.models import MODEL_REGISTRY

    from benchmarks.engine_setup import HF_TO_FIELD

    cfg = cells.load_json(BENCH_DIR / "configs" / f"{name}.json")
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    published = MODEL_REGISTRY[cfg["registry_name"]]()
    for key, field in HF_TO_FIELD.items():
        if key in cfg["reduced"]:
            assert cfg[key] != cfg["published"][key]
            assert cfg["published"][key] == getattr(published, field)
        else:
            assert cfg[key] == getattr(published, field), key
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in cfg["reduced"])
    assert cfg["chips"] in (1, 4)
    assert cfg["engine"]["max_seq_len"] > 0


@pytest.mark.parametrize("name", _stems("traffic"))
def test_traffic_file_names_a_driver(name):
    traffic = cells.load_traffic(name, BENCH_DIR)
    driver = cells.load_module("drivers", traffic["driver"], BENCH_DIR)
    assert callable(driver.parent) and callable(driver.child)


@pytest.mark.parametrize("name", _stems("layer_metrics"))
def test_layer_metric_file_names_a_reader(name):
    spec = cells.load_layer_metric(name, BENCH_DIR)
    assert callable(cells.load_module("readers", spec["reader"], BENCH_DIR).read)
    assert cells.NAME_RE.match(name) and cells.UNIT_RE.match(spec["unit"])
    assert spec["source"] in cells.SOURCES
    # a file the benchmark does not list measures nothing: keep them in step
    assert name in [m["name"] for m in BENCH["per_layer"]]


def test_a_bad_name_and_a_missing_file_are_errors():
    with pytest.raises(cells.CellError):
        cells.find_cell(BENCH, "no-such-cell")
    with pytest.raises(cells.CellError):
        cells.load_module("drivers", "no_such_driver", BENCH_DIR)
    with pytest.raises(cells.CellError):
        cells.load_module("readers", "../run", BENCH_DIR)


def test_a_four_chip_configuration_is_only_new_files(tmp_path):
    """Row 1 of PERF.md's Open questions: a new configuration file, and new
    entries, no edit to a file that is there (benchmarks/README.md)."""
    root = tmp_path / "repo"
    shutil.copytree(BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    cfg = cells.load_json(BENCH_DIR / "configs" / "phi4-14b-l20-int8.json")
    cfg.update(num_hidden_layers=40, reduced=[], chips=4, mesh="model=4")
    (root / "benchmarks/configs/phi4-14b-x4.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "phi4-14b-x4", "source": cfg["source"],
        "file": "benchmarks/configs/phi4-14b-x4.json", "reduced": [],
        "why": "Phi-4 whole, sharded over four chips"})
    bench["workloads"].append({
        "name": "phi4-14b-x4.offline-mapreduce-8k", "config": "phi4-14b-x4",
        "traffic": "offline-mapreduce-8k", "chips": 4,
        "why": "collectives under shard_map"})
    # ... and its name beside the other offline cells', in each metric that
    # lists its cells
    assert cells.validate(bench, root) != []
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "phi4-14b-l20-int8.offline-mapreduce-8k" in m.get("workloads", []):
            m["workloads"].append("phi4-14b-x4.offline-mapreduce-8k")
    assert cells.validate(bench, root) == []
    loaded = cells.load_config(bench, "phi4-14b-x4", root)
    assert loaded["mesh"] == "model=4" and loaded["chips"] == 4
    # the mesh string reaches TpuBackend(mesh=) through mesh_from_spec
    from benchmarks import engine_setup

    mesh = engine_setup.make_mesh(loaded)
    assert dict(mesh.shape)["model"] == 4
    assert engine_setup.make_mesh({"mesh": None}) is None


# -- traffic -----------------------------------------------------------------


def test_permutation_is_seeded_and_keeps_the_multiset():
    items = cells.load_traffic("offline-mapreduce-8k")["doc_tokens"] * 3
    a, b = textgen.permuted(items, 7), textgen.permuted(items, 7)
    c = textgen.permuted(items, 8)
    assert a == b and a != c
    assert sorted(a) == sorted(c) == sorted(items)
    assert textgen.permuted(items, 7, cycle=1) != a
    blocks = cells.load_traffic("serve-fanout-8k")["prompt_token_blocks"]
    x, y = textgen.permuted_blocks(blocks, 7), textgen.permuted_blocks(blocks, 8)
    assert x == textgen.permuted_blocks(blocks, 7) and x != y
    assert sorted(x) == sorted(y) == sorted(sum(blocks, []))
    n = len(blocks[0])   # every run of one block's length keeps the mix
    assert {tuple(sorted(x[i:i + n])) for i in range(0, len(x), n)} == {
        tuple(sorted(b)) for b in blocks}


def test_text_is_seeded_and_seeds_past_int32_fold():
    assert textgen.TextGen(5).paragraphs(300) == textgen.TextGen(5).paragraphs(300)
    assert textgen.TextGen(5).paragraphs(300) != textgen.TextGen(6).paragraphs(300)
    assert len(textgen.TextGen(1).text_of_bytes(1000).encode()) <= 1000
    big = 2**31 + 12345
    assert 0 <= textgen.fold_seed(big) < 2**31
    assert textgen.fold_seed(big) == textgen.fold_seed(big)
    assert textgen.fold_seed(big) != textgen.fold_seed(big + 1)
    assert textgen.fold_seed(7) == 7


def test_cut_to_tokens_keeps_to_the_target():
    words = lambda texts: [t.count(" ") + 1 for t in texts]  # noqa: E731
    doc = textgen.TextGen(3).text_of_tokens(900, words, 1.0)
    assert 700 < sum(words(doc.split("\n\n"))) <= 900
    ps = ["a", "b", "c", "d"]
    assert textgen.cut_to_tokens(ps, [10, 10, 10, 10], 25) == "a\n\nb"
    assert textgen.cut_to_tokens(ps, [50, 10, 10, 10], 25) == "a"


# -- arithmetic ----------------------------------------------------------------


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4, 5], 90, 4.6),
    ([10.0], 90, 10.0),
    ([4, 1, 3, 2], 100, 4.0),
])
def test_percentile_on_known_samples(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


@pytest.mark.parametrize("text,ids,bad", [
    ("", [], True),                                  # renders as nothing
    (" \n", [5, 9, 5, 9, 5, 9, 5, 9], True),
    (" nhống" * 9, [812] * 9, True),                 # one BPE token repeated
    ("a" * 9, [97] * 9, True),
    (" nhống nhống", [812, 812], False),             # too short to judge
    ("Tóm tắt: văn bản", [3, 1, 4, 1, 5, 9, 2, 6, 5], False),
])
def test_a_degenerate_row_is_told_by_its_token_ids(text, ids, bad):
    assert stats.degenerate(text, ids) is bad


def test_at_most_needs_a_total():
    assert stats.at_most(0, 56, 0) and stats.at_most(1, 56, 1)
    assert not stats.at_most(1, 56, 0) and not stats.at_most(8, 56, 1)
    assert not stats.at_most(0, 0, 1)


def test_rate_and_covered_on_known_samples():
    assert stats.rate(8, 80.0, per=60.0) == pytest.approx(6.0)
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    spans = [(1.0, 3.0), (2.0, 4.0), (6.0, 20.0)]
    assert stats.covered(spans, 0.0, 10.0) == pytest.approx(7.0)


QWEN3_8B = cells.load_json(BENCH_DIR / "configs" / "qwen3-8b-int8.json")


def test_roofline_against_hand_worked_numbers_for_one_qwen3_8b_dispatch():
    # one layer: q,k,v 4096x(32+8+8)x128, o 4096x4096, ffn 3x4096x12288
    assert roofline.layer_matmul_params(QWEN3_8B) == (
        25_165_824 + 16_777_216 + 150_994_944)
    assert roofline.matmul_params(QWEN3_8B) == 36 * 192_937_984
    assert roofline.kv_bytes_per_token(QWEN3_8B, 1) == 73_728
    peaks = roofline.load_peaks("TPU v5 lite")
    lens = [8000] * 8
    r = roofline.least_seconds(
        QWEN3_8B, {"weights": 1, "kv": 1, "prefill_matmul": "int8"}, peaks,
        lens, 256)
    head = 4096 * 151_936
    assert r["matmul_ops"] == 2 * 6_945_767_424 * 64_000 + 2 * head * 8
    assert r["attention_ops"] == 2 * 32 * 128 * 8000 * 8000 * 8 * 36
    ctx = 8 * 8000 * 256 + 8 * 256 * 255 // 2
    assert r["bytes"] == (6_945_767_424 + head) * 256 + 73_728 * ctx
    assert r["prefill_s"] == pytest.approx(
        r["matmul_ops"] / 393e12 + r["attention_ops"] / 197e12)
    assert r["decode_bound"] == "memory"
    assert r["decode_s"] == pytest.approx(r["bytes"] / 819e9)
    assert 6.5 < r["total_s"] < 7.5
    # bf16 weights and activations: twice the bytes, half the matmul peak
    b = roofline.least_seconds(
        QWEN3_8B, {"weights": 2, "kv": 2, "prefill_matmul": "bf16"}, peaks,
        lens, 256)
    assert b["decode_s"] == pytest.approx(2 * r["decode_s"])
    assert b["prefill_s"] > r["prefill_s"]


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.load_peaks("TPU v9 imaginary")
    assert roofline.load_peaks("TPU v5 lite")["source"]


# -- trace reduction -----------------------------------------------------------


def test_trace_reduce_on_hand_made_planes():
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_f(12)", 100, 1100), ("jit_f(12)", 3000, 4000),
                            ("jit_g(3)", 4000, 4500),
                            # cut off by the end of the trace: not whole
                            ("jit_f(12)", 99_000_000, 100_000_000)],
            "XLA Ops": [("while", 100, 1100), ("fusion.1", 100, 500),
                        ("dot", 500, 1000), ("fusion.1", 3000, 4000),
                        ("copy", 4000, 4500)]},
        "/host:CPU": {"main": [
            (trace_reduce.WINDOW_MARK, 0, 100_000_000),
            ("bench:generate", 0, 2_000_000),
            ("inner", 1200, 1_001_200)]},
    }
    r = trace_reduce.reduce_planes(planes)
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(2500e-9)
    assert r["modules"]["jit_f"] == pytest.approx(2000e-9)
    assert r["module_calls"] == {"jit_f": 2.0, "jit_g": 1.0}
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(1400e-9)   # nested + top level
    assert ops["while"] == pytest.approx(100e-9)       # its body taken out
    gaps = dict(r["idle_gaps"])
    assert gaps["inner"] == pytest.approx(1900e-9)     # the shortest span wins
    assert gaps["no host span"] == pytest.approx((100_000_000 - 4500) / 1e9)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes({"/host:CPU": {}})


def test_trace_reduce_on_the_recorded_tpu_trace():
    """benchmarks/fixtures/small_trace.xplane.pb was recorded on the TPU v5e
    by fixtures/record_fixture.py: three executions of jit_fixture_step with
    a 20 ms host sleep after each, inside the window mark."""
    path = BENCH_DIR / "fixtures" / "small_trace.xplane.pb"
    r = trace_reduce.reduce_planes(trace_reduce.read_planes(str(path)))
    assert r["devices"] == 1
    assert r["module_calls"]["jit_fixture_step"] == 3
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["modules"]["jit_fixture_step"] == pytest.approx(r["busy_s"], rel=0.2)
    assert 0.06 < r["window_s"] < 1.0
    assert "bench:fixture_sleep" in dict(r["idle_gaps"])
    assert r["device_ops"] and len(r["device_ops"]) <= 10


# -- readers and the last line -------------------------------------------------

RAW = {
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
               "memory_peak_bytes": 14_000_000_000},
    "setup_s": 50.0, "window": {"seconds": 80.0},
    "values": {"docs_per_min": 6.0},
    "attempted": 8, "failed": 0, "checks": {"a": True, "b": True},
    "spans": {"generate": [[1.0, 39.0], [41.0, 79.0]]},
    "sizes": QWEN3_8B, "precision": {"weights": 1, "kv": 1,
                                     "prefill_matmul": "int8"},
    "traced": {"docs": 4, "dispatches": [
        {"prompt_lens": [8000] * 8, "steps": 256},
        {"prompt_lens": [8000] * 8, "steps": 256}]},   # the 2nd was cut off
    "trace": {"busy_s": 38.0, "window_s": 40.0,
              "modules": {"jit_generate": 36.0, "jit_other": 1.0},
              "module_calls": {"jit_generate": 1.0, "jit_other": 3.0},
              "device_ops": [["fusion", 20.0]], "idle_gaps": [["x", 2.0]]},
}


def _ctx(trace, cell="qwen3-8b-int8.offline-mapreduce-8k", rehearsal=False):
    return {"workload": cell, "trace": trace, "rehearsal": rehearsal}


def test_last_line_has_exactly_the_contract_keys():
    line = bench_run.result_line(BENCH, _ctx(0), RAW)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"docs_per_min", "setup_s"}
    assert line["metrics"]["setup_s"] == {"value": 50.0, "unit": "s"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["correct"] is True
    json.dumps(line)
    traced = bench_run.result_line(BENCH, _ctx(1), RAW)
    assert set(traced) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert traced["device"]["busy_s"] == 38.0
    assert traced["device"]["window_s"] == 40.0
    assert "docs_per_min" not in traced["metrics"]
    failed = bench_run.result_line(
        BENCH, _ctx(0), {**RAW, "checks": {"a": True, "b": False}})
    assert failed["correct"] is False


def test_readers_on_a_known_record():
    m = bench_run.layer_metrics(BENCH, _ctx(1), RAW)
    assert m["host_share.offline"]["value"] == pytest.approx(5.0)
    assert m["generate_device_s_per_dispatch"]["value"] == pytest.approx(36.0)
    assert m["device_idle.offline"]["value"] == pytest.approx(5.0)
    least = roofline.least_seconds(
        QWEN3_8B, RAW["precision"], roofline.load_peaks("TPU v5 lite"),
        [8000] * 8, 256)["total_s"]
    assert m["generate_roofline_share"]["value"] == pytest.approx(
        100 * least / 36.0)
    assert m["generate_roofline_share"]["value"] < 100


def test_a_reader_with_nothing_to_read_leaves_its_metric_out():
    bare = {**RAW, "trace": None, "traced": None}
    m = bench_run.layer_metrics(BENCH, _ctx(1), bare)
    assert set(m) == {"host_share.offline"}
    assert reading.lookup(RAW, "trace.modules.jit_generate") == 36.0
    assert reading.lookup(RAW, "trace.nothing.here") is None
    assert reading.module_seconds(RAW, ["jit_gen"]) == 36.0
    assert reading.module_seconds(RAW, ["jit_zzz"]) is None


def test_a_rehearsal_reports_no_device_number():
    line = bench_run.result_line(BENCH, _ctx(0, rehearsal=True), RAW)
    assert {m["value"] for m in line["metrics"].values()} == {"not measured"}
    traced = bench_run.layer_metrics(BENCH, _ctx(1, rehearsal=True), RAW)
    for name, m in traced.items():
        spec = cells.load_layer_metric(name)
        assert (m["value"] == "not measured") == (
            spec["source"] != "program_counter"), name


# -- the command, end to end ---------------------------------------------------


def _run(*args, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)   # one CPU device, as one chip
    return subprocess.run(
        [sys.executable, RUN, *args], cwd=ROOT, env=env, timeout=timeout,
        capture_output=True, text=True)


def _first_cell_of(driver):
    for w in BENCH["workloads"]:
        if cells.load_traffic(w["traffic"])["driver"] == driver:
            return w["name"]
    pytest.skip(f"no cell uses driver {driver}")


@pytest.mark.parametrize("driver", sorted(
    p.stem for p in (BENCH_DIR / "drivers").glob("*.py")))
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_each_driver(driver, trace):
    cell = _first_cell_of(driver)
    p = _run("--workload", cell, "--seed", str(2**31 + 77), "--seconds", "2",
             "--trace", str(trace), "--rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu"      # named honestly
    assert line["correct"] is False                 # ... and not a TPU
    assert "failed checks: ['platform_is_tpu']" in p.stderr, p.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    group = "per_layer" if trace else "end_to_end"
    assert set(line["metrics"]) == {
        m["name"] for m in cells.metrics_for(BENCH, group, cell)}
    sources = {m["name"]: m["source"] for m in BENCH[group]}
    for name, m in line["metrics"].items():
        if sources[name] != "program_counter":
            assert m["value"] == "not measured", name


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    cell = BENCH["workloads"][0]["name"]
    p = _run("--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "nothing was run" in p.stderr


def test_an_unknown_workload_exits_nonzero():
    p = _run("--workload", "nope", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("name", _stems("configs"))
def test_plain_reference_agrees_with_the_program_at_a_tiny_size(name):
    """Each configuration's plain reference (benchmarks/reference.py, with
    the file's ``qk_norm``) against the program's cache-free forward, on
    seeded random weights, float32 and then int8 weights. Tolerance 1e-5 of
    logits of order 0.5: both sides are float32 on the CPU, so only the
    order of summation differs; bf16 arithmetic would miss it by 1e-3."""
    import jax
    import jax.numpy as jnp

    from vnsum_tpu.models import init_params, tiny_llama
    from vnsum_tpu.models.llama import forward_train
    from vnsum_tpu.models.quant import quantize_params

    from benchmarks import reference

    spec = cells.load_json(BENCH_DIR / "configs" / f"{name}.json")
    qk = spec["reference"]["qk_norm"]
    group = spec["num_attention_heads"] // spec["num_key_value_heads"]
    cfg = tiny_llama(qk_norm=qk, tie_embeddings=spec["tie_word_embeddings"],
                     n_heads=2 * group, n_kv_heads=2, head_dim=16, dim=64)
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (1, 24), 0, cfg.vocab_size)
    kw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
              rope_theta=cfg.rope_theta, eps=cfg.norm_eps, qk_norm=qk)
    for p in (params, jax.jit(quantize_params)(params)):
        want = forward_train(p, cfg, tokens, remat=False)[0]
        got = reference.logits(p, tokens[0], **kw)
        assert float(jnp.max(jnp.abs(want))) > 0.1
        assert float(jnp.max(jnp.abs(want - got))) < 1e-5


@pytest.mark.parametrize("fault,ok", [(None, True), ("qk_norm", False),
                                      ("rope_theta", False)])
def test_parity_check_passes_the_program_and_catches_a_fault(fault, ok):
    """The run-time parity check (engine_setup.parity_with_reference) on a
    tiny engine with interpreted kernels: it passes the program as it is,
    and fails when the program and the reference stop being the same
    mathematics - a reference with the other ``qk_norm``, or an engine that
    rotates positions by another base than the file states."""
    import dataclasses

    from vnsum_tpu.backend.engine import TpuBackend

    from benchmarks import engine_setup

    config = cells.load_config(BENCH, "qwen3-8b-int8")
    cfg = engine_setup.model_config(config, rehearsal=True)
    params = engine_setup.start_weights(config, cfg, 11)
    if fault == "qk_norm":
        config["reference"]["qk_norm"] = not config["reference"]["qk_norm"]
    if fault == "rope_theta":
        cfg = dataclasses.replace(cfg, rope_theta=cfg.rope_theta * 2)
    backend = TpuBackend(
        model_config=cfg, tokenizer="byte", batch_size=2, max_new_tokens=8,
        params=params, **engine_setup.backend_kwargs(config, rehearsal=True))
    got = engine_setup.parity_with_reference(backend, config, 11,
                                             rehearsal=True)
    assert got["ok"] is ok, got
    assert got["kernel"] is True and got["prompt_tokens"] == 150
