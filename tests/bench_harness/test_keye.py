"""CPU tests of the benchmark's own parts for the Keye family: the run-time
parity check and what it has to catch (a fault of the equations, a cache a
precision below, index scores summed a precision below), the rooflines
against hand-worked numbers, the readers on a known record, the cell's
rehearsal, and the configuration file's keys and arithmetic.

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
from benchmarks import engine_setup_keye as family_setup  # noqa: E402
from benchmarks import reference_keye as reference  # noqa: E402
from benchmarks import roofline_keye as roof  # noqa: E402

BENCH = cells.load_benchmark(ROOT)
NAME = "keye-vl-2.0-l12-int8"
CONFIG = cells.load_config(BENCH, NAME)
TRAFFIC = "offline-mapreduce-12k-dsa"
CELL = f"{NAME}.{TRAFFIC}"
OWN = {"generate_roofline_share_keye", "dsa_index_select_roofline",
       "dsa_prefill_attention_roofline", "dsa_decode_attention_roofline",
       "keye_expert_matmul_roofline", "dsa_busy_share",
       "dsa_attention_scores_computed_over_selected",
       "dsa_index_scores_computed_over_needed"}
SHARED = {"host_share.offline", "generate_device_s_per_dispatch",
          "device_idle.offline", "idle_in_engine_host.offline",
          "idle_in_pipeline_host.offline", "idle_unexplained.offline",
          "expert_ffn_busy_share", "expert_load_max_over_mean",
          "expert_distinct_per_step"}
LIMITS = ("tolerance", "kv_tolerance", "ki_tolerance", "score_tolerance",
          "q_tolerance", "w_tolerance", "sum_tolerance", "select_band",
          "tie_band")


# -- the parity check ----------------------------------------------------------


def _backend(**cfg_kw):
    import jax

    from vnsum_tpu.backend.engine import TpuBackend

    config = copy.deepcopy(CONFIG)
    cfg = family_setup.model_config(config, rehearsal=True, **cfg_kw)
    params = family_setup.start_weights(config, cfg, 11)
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
    assert got["ok"] and got["kernel"], got
    assert len(got["errors"]) == len(got["selection"]) == 5
    assert got["bucket"] == 256 and got["topk"] == 48 < got["prompt_tokens"]
    # every layer of every row took the program's picks; its sets are
    # taken slot by slot, whole in the first layer
    assert got["took"] == [3] * 5
    assert all(1 <= whole <= 3 for whole in got["sel_took"])
    for row in got["selection"]:
        assert row["kept"] == row["reference_kept"] == 48
        assert row["same_slots_seen"]
    # the limits have room on both sides of what a clean run reads
    for read, limit in (("error", "tolerance"), ("kv_error", "kv_tolerance"),
                        ("ki_error", "ki_tolerance"),
                        ("q_error", "q_tolerance"), ("w_error", "w_tolerance"),
                        ("score_error", "score_tolerance")):
        assert 0 < got[read] * 1.3 < got[limit], (read, got[read])
    assert got["unshared_from_cut"] < got["select_band"]
    # the later layers' sets side by side, one reading a layer: few slots
    # outside the band, and ONE band for every layer
    deep = got["selection_deep"]
    assert {len(v) for v in deep.values()} == {2}
    assert max(deep["outside_band"]) * 3 <= got["deep_outside_band"]
    assert "select_band_deep" not in got
    # the kernel's own sums, from the operands the program recorded (which
    # the limits above hold to the reference's)
    assert got["selection_exact"] and got["sum_error"] * 10 < got[
        "sum_tolerance"]


def test_a_deep_layers_indexer_fault_moves_the_logits(rehearsal_backend):
    """An indexer that picks other keys in the layers after the first: the
    first layer's records read clean, the later layers' slots lie far from
    the reference's cut, the reference keeps its own there and the logits
    part."""
    clean = _parity(rehearsal_backend)
    got = _parity(rehearsal_backend, ("indexer_of_another_layer",))
    assert not got["ok"] and got["error"] > 2 * got["tolerance"]
    for name in ("score_error", "unshared_from_cut", "q_error", "w_error",
                 "kv_error", "ki_error", "sum_error"):
        assert got[name] == clean[name], name
    assert min(got["selection_deep"]["outside_band"]) \
        > 3 * got["deep_outside_band"]
    assert min(got["selection_deep"]["unshared_from_cut"]) > 5 * got[
        "select_band"]


@pytest.mark.parametrize("fault", [
    "dense_attention", "top_half", "softmax_over_visible", "no_relu",
    "no_index_norm", "selection_of_previous_layer", "no_route_renorm",
    "no_qk_norm"])
def test_parity_catches_a_departure_from_the_equations(fault,
                                                       rehearsal_backend):
    """One of each kind (the selection's size, its inputs, its use, the
    softmax over it, the routing, the skeleton); ``tests/test_model_keye.py``
    holds EVERY fault of the reference in float32, where each shows in the
    logits."""
    got = _parity(rehearsal_backend, (fault,))
    assert not got["ok"] and got["faults"] == [fault]


@pytest.mark.parametrize("fault, read", [
    ("no_index_rotary", "q_error"), ("no_index_scale", "w_error"),
    ("no_head_weights", "w_error")])
def test_the_recorded_operands_are_held_to_the_reference(fault, read,
                                                         rehearsal_backend):
    """``sum_tolerance`` compares the program's scores with sums of what the
    program recorded; the records themselves meet the reference here."""
    got = _parity(rehearsal_backend, (fault,))
    limit = read.replace("error", "tolerance")
    assert not got["ok"] and got[read] > 10 * got[limit], got[read]


def test_the_indexers_scale_fails_by_the_scores_limit(rehearsal_backend):
    got = _parity(rehearsal_backend, ("no_index_scale",))
    assert not got["ok"]
    assert got["score_error"] > 10 * got["score_tolerance"]
    assert got["error"] <= got["tolerance"]      # no top-k moves


@pytest.mark.parametrize("fault", ["components_swapped"])
def test_what_a_text_prompt_cannot_show(fault, rehearsal_backend):
    """At a text token's equal position components a swap of the sections
    is the same rotary: ``tests/test_model_keye.py`` holds it at distinct
    components."""
    assert _parity(rehearsal_backend, (fault,))["ok"]


def _int4_kv(x):
    """``models.llama._quantize_kv`` with 4 bits a value: the nearest
    precision below the configured int8 cache."""
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32), -1, keepdims=True), 1e-8) / 7.0
    return (jnp.clip(jnp.round(x32 / scale), -7, 7).astype(jnp.int8),
            scale[..., 0])


def test_a_cache_of_four_bits_fails_the_check_of_the_caches_rows(monkeypatch):
    from vnsum_tpu.models import llama

    monkeypatch.setattr(llama, "_quantize_kv", _int4_kv)
    got = _parity(_backend())
    assert got["ok"] is False
    assert got["kv_error"] > 1.3 * got["kv_tolerance"]
    assert got["ki_error"] <= got["ki_tolerance"]     # the indexer's own


def test_index_scores_summed_in_bfloat16_fail_the_sums_limit():
    import jax.numpy as jnp

    got = _parity(_backend(index_sum_dtype=jnp.bfloat16))
    assert got["ok"] is False
    assert got["sum_error"] > 3 * got["sum_tolerance"]
    # ... by that limit ALONE: its operands are the clean run's
    assert got["q_error"] <= got["q_tolerance"]
    assert got["w_error"] <= got["w_tolerance"]
    # the scores against the reference's own hardly show it (W8A8's
    # rounding of the projections is larger), the caches' rows not at all
    assert got["score_error"] <= got["score_tolerance"]
    assert got["kv_error"] <= got["kv_tolerance"] and got["selection_exact"]


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


@pytest.mark.parametrize("tokens, text", [(256 + 1, "parity prompt"),
                                          (40, "drops no key")])
def test_a_prompt_that_cannot_show_the_mechanism_is_refused(
        tokens, text, rehearsal_backend):
    config = copy.deepcopy(CONFIG)
    config["rehearsal"]["parity"]["prompt_tokens"] = tokens
    with pytest.raises(ValueError, match=text):
        _parity(rehearsal_backend, config=config)


# -- the configuration file ------------------------------------------------------


def test_model_config_builds_the_published_widths_at_12_layers():
    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    assert (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.moe_intermediate, cfg.n_routed_experts,
            cfg.num_experts_per_tok, cfg.vocab_size) == (
        12, 2048, 32, 4, 128, 768, 128, 8, 151936)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.mrope_section) == (
        1e7, 1e-6, (16, 24, 24))
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        16, 64, 2048)
    assert cfg.max_seq_len == 16640 and not cfg.tie_embeddings
    kw = engine_setup.backend_kwargs(CONFIG, rehearsal=False)
    assert kw["quantize"] and kw["quantize_act"] and kw["quantize_kv"] is True
    assert kw["prefill_chunk_tokens"] == 2048 and kw["mesh"] is None
    tiny = family_setup.model_config(CONFIG, rehearsal=True)
    assert (tiny.n_layers, tiny.n_heads, tiny.n_kv_heads, tiny.head_dim,
            tiny.index_topk) == (3, 4, 2, 16, 48)
    assert cfg.intermediate == 6144    # published, unused
    sizes = family_setup.sizes_of(CONFIG, False)
    back = family_setup.sizes_from(cfg)
    assert {k: back[k] for k in family_setup.HF_TO_FIELD} == {
        k: sizes[k] for k in family_setup.HF_TO_FIELD}
    assert back["sa_config"]["topk"] == sizes["sa_config"]["topk"] == 2048
    assert back["rope_scaling"]["mrope_section"] == [16, 24, 24]


def test_an_indexer_with_more_key_heads_is_refused():
    sizes = family_setup.sizes_of(CONFIG, False)
    sizes["sa_config"] = {**sizes["sa_config"], "indexer_num_kv_heads": 2}
    with pytest.raises(ValueError, match="ONE key head"):
        family_setup.config_kwargs(sizes)


def _catalog_row():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    return next(r for r in map(json.loads, catalog.read_text().splitlines())
                if r["name"] == "Keye-VL-2.0-30B-A3B")


def test_config_files_keys_are_the_catalog_rows():
    """Every key of the catalog entry's config under the same name at the
    same value (``sa_config`` and ``rope_scaling`` whole), but the one that
    is reduced."""
    row = _catalog_row()
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert CONFIG["source"] == entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 12
    assert CONFIG["published"] == {"num_hidden_layers": 48}


def test_config_file_states_the_deployment_and_every_inference():
    c = CONFIG
    assert c["chips"] == 1 and c["mesh"] is None
    assert c["checkpoint_seed"] == 63 and "expert_parallel" not in c
    assert c["setup_module"] == "engine_setup_keye"
    assert set(c["assumed"]) >= {
        "qk_norm", "rope", "indexer_inputs", "indexer_norm", "indexer_rope",
        "indexer_weights", "index_scores", "selection", "chunk_sizes",
        "indexer_cache", "vision_tower", "routing"}
    assert "NOT built" in c["assumed"]["chunk_sizes"]
    assert "FP8" in c["assumed"]["index_scores"]
    low = c["deployment"].lower()
    assert "stage 0 of a four-stage pipeline" in low
    assert "layers 0-11" in low and "whole vocabulary" in low
    assert "all 128 experts" in low
    assert c["engine"] == {
        "weights": "int8", "activations": "int8", "kv": "int8",
        "prefill_chunk_tokens": 2048, "batch": 8, "max_seq_len": 16640}
    for key in ("assumed", "deployment", "bytes", "engine_notes", "engine",
                "checkpoint_notes", "random_weights", "rehearsal",
                "reference"):
        assert c[key], key
    assert c["reference"]["file"] == "benchmarks/reference_keye.py"
    parity = c["reference"]["parity"]
    assert (parity["bucket"], parity["decode_steps"]) == (16384, 8)
    assert 10_000 <= parity["prompt_tokens"] <= 12_000
    for limit in LIMITS:
        assert 0 < parity[limit] <= 0.2, limit
        assert 0 < c["rehearsal"]["parity"][limit] < 1
    # one band for every layer, and a count of slots outside it
    assert "select_band_deep" not in parity
    assert 0 < parity["deep_outside_band"] <= 0.25 * c["sa_config"]["topk"]
    assert "bfloat16" in parity["what"] and "4 bits" in parity["what"]


def test_config_files_byte_arithmetic_is_the_models():
    import jax

    from vnsum_tpu.models import keye
    from vnsum_tpu.models.quant import init_params_quantized

    cfg = family_setup.model_config(CONFIG, rehearsal=False)
    tree = jax.eval_shape(lambda k: init_params_quantized(k, cfg),
                          jax.random.key(0))

    def nbytes(t):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))

    b = CONFIG["bytes"]
    lay = tree["layers"]
    assert nbytes(tree) == b["weights"] == 8_154_929_152
    assert nbytes(lay) == b["layers_12"] == 12 * b["layer"]
    assert b["layer"] == (b["attention_a_layer"] + b["indexer_a_layer"]
                          + b["router_a_layer"] + b["norms_a_layer"]
                          + b["experts_a_layer"])
    assert b["experts_a_layer"] == 128 * b["one_expert"] == sum(
        nbytes(lay[n]) for n in ("we_gate", "we_up", "we_down")) // 12
    assert b["indexer_a_layer"] == sum(nbytes(lay[n]) for n in (
        "wq_idx", "wk_idx", "w_idx", "idx_norm_g", "idx_norm_b")) // 12
    assert nbytes(tree["embed"]) == b["embedding"]
    assert nbytes(tree["lm_head"]) == b["head"]
    cache = jax.eval_shape(lambda: keye.init_cache(cfg, 8, 16640,
                                                   quantized=True))
    kv = sum(nbytes(cache[n]) for n in ("k", "v", "ks", "vs"))
    assert kv == b["kv_cache"] == 8 * b["kv_cache_a_row"]
    assert nbytes(cache["ki"]) == b["indexer_cache"] \
        == 8 * b["indexer_cache_a_row"]
    assert nbytes(cache["sel"]) + nbytes(cache["sel_scores"]) \
        == b["selection_record"]
    # the issue's table, parameters alone: 18.87 M of attention, 2.26 M of
    # indexer, 0.26 M of router, 604.0 M of experts a layer
    sizes = family_setup.sizes_of(CONFIG, False)
    assert roof.attention_params(sizes) == 18_874_368
    assert roof.indexer_params(sizes) == 2_260_992
    assert roof.router_params(sizes) == 262_144
    assert 128 * roof.expert_params(sizes) == 603_979_776


# -- the rooflines ------------------------------------------------------------------

SIZES = family_setup.sizes_of(CONFIG, rehearsal=False)
PRECISION = engine_setup.precision_of(CONFIG)
PEAKS = {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_per_s": 819e9}
EXPERTS = {"slots_routed": 1000, "slots_held": 1000, "decode_touched": 600,
           "decode_layer_steps": 24}


def test_pairs_by_hand():
    assert roof.visible_pairs(4) == 10
    assert roof.selected_pairs(4, 2048) == 10
    assert roof.selected_pairs(3000, 2048) == 2048 * 2049 // 2 + 952 * 2048
    assert roof.decode_pairs([10, 3000], 2) == 11 + 12 + 3001 + 3002
    assert roof.decode_pairs([10, 3000], 2, 2048) == 11 + 12 + 2048 * 2


def test_kernel_rooflines_against_hand_worked_numbers():
    lens, steps = [12000, 5000], 256
    got = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, EXPERTS, lens,
                                    steps)
    vis = 12000 * 12001 // 2 + 5000 * 5001 // 2
    dec = roof.decode_pairs(lens, steps)
    assert got["dsa_index_select"]["seconds"] == pytest.approx(
        2 * 64 * 16 * vis * 12 / 197e12
        + max(2 * 64 * 16 * dec * 12 / 197e12, 132 * dec * 12 / 819e9))
    assert got["dsa_index_select"]["bound"] == "compute, then memory"
    kept = sum(roof.selected_pairs(n, 2048) for n in lens)
    assert got["dsa_prefill_attention"]["seconds"] == pytest.approx(
        4 * 32 * 128 * kept * 12 / 197e12)
    # every decode step keeps 2,048 slots a row: 4 x (256 + 8) B each
    assert got["dsa_decode_attention"]["seconds"] == pytest.approx(
        2 * 256 * 2048 * 12 * 4 * 264 / 819e9)
    assert got["dsa_decode_attention"]["bound"] == "memory"
    per_expert = 3 * 2048 * 768
    assert got["expert_grouped_matmul"]["seconds"] == pytest.approx(
        2 * per_expert * 8 * 12 * 17000 / 393e12
        + per_expert * (600 / 24) * 256 * 12 / 819e9)


def test_dispatch_roofline_adds_up_by_hand():
    lens, steps = [12000, 5000], 256
    d = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, lens, steps)
    k = d["kernels"]
    head = 2048 * 151936
    a_token = 12 * (18_874_368 + 2_260_992 + 262_144 + 8 * 3 * 2048 * 768)
    assert d["prefill_matmul_ops"] == 2 * a_token * 17000 + 2 * head * 2
    assert d["prefill_s"] == pytest.approx(
        d["prefill_matmul_ops"] / 393e12 + d["prefill_index_ops"] / 197e12
        + k["dsa_prefill_attention"]["seconds"])
    assert d["total_s"] == pytest.approx(d["prefill_s"] + d["decode_s"])
    fixed = 12 * (18_874_368 + 2_260_992 + 262_144) + head
    sel = roof.index_select(SIZES, lens, steps)
    assert d["decode_bytes"] == pytest.approx(
        fixed * 256 + 3 * 2048 * 768 * (600 / 24) * 256 * 12
        + 2 * 256 * 2048 * 12 * 4 * 264 + sel["decode_bytes"])
    # the cell's full dispatch by the issue's reckoning: the mechanism
    # (index scores + attention over the selection) ~40% of the prefill
    full = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, [11900] * 8, 256)
    own = (full["prefill_index_ops"] / 197e12
           + full["kernels"]["dsa_prefill_attention"]["seconds"])
    assert 0.3 < own / full["prefill_s"] < 0.5


# -- the readers ---------------------------------------------------------------------


def _raw():
    dispatch = {"prompt_lens": [12000, 5000], "steps": 256,
                "experts": EXPERTS}
    return {
        "device": {"kind": "TPU v5 lite"}, "sizes": SIZES,
        "precision": PRECISION,
        "counts": {"experts": EXPERTS, "prefill_blocks": {
            "dsa_attention_scores_computed": 300, "dsa_index_scores_needed": 80,
            "dsa_attention_scores_selected": 100,
            "dsa_index_scores_computed": 100}},
        "traced": {"dispatches": [dispatch]},
        "trace": {"busy_s": 10.0, "device_ops": [
            ["dsa_index_select", 2.0], ["dsa_prefill_attention", 3.0],
            ["dsa_decode_attention", 0.5], ["expert_grouped_matmul", 1.0],
            ["while", 0.25]],
            "modules": {"jit_generate": 9.0},
            "module_calls": {"jit_generate": 1}},
    }


def _read(name, raw):
    spec = cells.load_layer_metric(name, ROOT / "benchmarks")
    return cells.load_module("readers", spec["reader"]).read(spec, raw)


def test_new_metrics_on_a_known_record():
    raw = _raw()
    least = roof.kernel_least_seconds(SIZES, PRECISION, PEAKS, EXPERTS,
                                      [12000, 5000], 256)
    for name, kernel, seconds in (
            ("dsa_index_select_roofline", "dsa_index_select", 2.0),
            ("dsa_prefill_attention_roofline", "dsa_prefill_attention", 3.0),
            ("dsa_decode_attention_roofline", "dsa_decode_attention", 0.5),
            ("keye_expert_matmul_roofline", "expert_grouped_matmul", 1.0)):
        assert _read(name, raw) == pytest.approx(
            100 * least[kernel]["seconds"] / (seconds + 0.25)), name
    assert _read("dsa_busy_share", raw) == pytest.approx(55.0)
    assert _read("dsa_attention_scores_computed_over_selected",
                 raw) == pytest.approx(3.0)
    assert _read("dsa_index_scores_computed_over_needed",
                 raw) == pytest.approx(1.25)
    whole = roof.dispatch(SIZES, PRECISION, PEAKS, EXPERTS, [12000, 5000],
                          256)["total_s"]
    assert _read("generate_roofline_share_keye", raw) == pytest.approx(
        100 * whole / 9.0)


def test_readers_with_nothing_to_read_leave_their_metric_out():
    """On the parent commit the program has no such kernel or counter: the
    line leaves the metric out and nothing raises."""
    raw = _raw()
    raw["trace"]["device_ops"] = [["fusion", 1.0]]
    raw["counts"]["prefill_blocks"] = {}
    for name in OWN - {"generate_roofline_share_keye"}:
        assert _read(name, raw) is None, name
    raw["traced"] = None
    assert _read("generate_roofline_share_keye", raw) is None


# -- the benchmark's entries, by membership -------------------------------------------


@pytest.mark.parametrize("name", sorted(OWN))
def test_an_own_metric_is_listed_for_this_cell_alone(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "docs_per_min"
    spec = cells.load_layer_metric(name, ROOT / "benchmarks")
    for key in ("layer", "unit", "better", "source"):
        assert spec[key] == entry[key], (name, key)
    if name.endswith("_roofline") or "roofline_share" in name:
        assert entry["unit"] == "%" and spec["roofline"] == "roofline_keye"


@pytest.mark.parametrize("name", sorted(SHARED))
def test_a_shared_metric_lists_this_cell_among_its_cells(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert CELL in entry["workloads"] and len(entry["workloads"]) > 1


def test_the_cell_is_in_the_benchmark_by_membership():
    assert cells.validate(BENCH) == []
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": NAME, "traffic": TRAFFIC,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    docs = next(m for m in BENCH["end_to_end"] if m["name"] == "docs_per_min")
    assert CELL in docs["workloads"]
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == OWN | SHARED
    # the new entries stand after every entry that was there
    names = [m["name"] for m in BENCH["per_layer"]]
    assert min(names.index(n) for n in OWN) > max(
        names.index(n) for n in SHARED)
    traffic = cells.load_traffic(TRAFFIC, ROOT / "benchmarks")
    assert traffic["driver"] == "offline_pipeline_family"
    assert (traffic["chunk_size"], traffic["chunk_overlap"],
            traffic["token_max"], traffic["chunks_per_doc"],
            traffic["max_new_tokens"]) == (12000, 200, 10000, 4, 256)
    assert traffic["doc_tokens"] == [39500, 41000, 42500, 44000]
    assert (traffic["bpe_vocab"], traffic["bpe_train_words"]) == (4096, 160000)


def test_the_cell_is_only_new_files():
    """Nothing that was under ``benchmarks/`` or ``tests/bench_harness/`` at
    the parent commit differs in the tree."""
    parent = "4dedb49b46fb7e3f9eaa32b44b59f5f0e95ae0e5"
    try:
        out = subprocess.run(
            ["git", "diff", "--name-status", parent, "--", "benchmarks",
             "tests/bench_harness"], cwd=ROOT, capture_output=True, text=True,
            check=True).stdout
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("no git history here")
    changed = [line.split("\t") for line in out.splitlines() if line]
    assert all(status == "A" for status, *_ in changed), changed


def test_the_driver_finds_this_familys_setup_module():
    import importlib

    module = importlib.import_module(f"benchmarks.{CONFIG['setup_module']}")
    for fn in ("model_config", "start_weights", "sizes_of",
               "parity_with_reference"):
        assert callable(getattr(module, fn)), fn
    assert module is family_setup


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         str(2_300_000_063 + trace), "--seconds", "2", "--trace", str(trace),
         "--rehearsal"], cwd=ROOT, capture_output=True, text=True,
        timeout=600, env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    line = json.loads(out.stdout.strip().splitlines()[-1])
    failed = [l for l in out.stderr.splitlines() if "failed checks" in l]
    # correct on everything but the platform
    assert failed and "platform_is_tpu" in failed[-1]
    assert "parity_with_reference" not in failed[-1], failed
    # whole groups of four documents, every one done
    assert line["attempted"] >= 4 and line["attempted"] % 4 == 0
    assert line["failed"] == 0
    if trace:
        assert set(line["metrics"]) == OWN | SHARED
        counted = line["metrics"]["dsa_attention_scores_computed_over_selected"]
        assert counted["value"] >= 1.0
        assert line["metrics"]["dsa_index_scores_computed_over_needed"][
            "value"] >= 1.0
    else:
        assert set(line["metrics"]) == {"docs_per_min", "setup_s"}
