"""The six per-layer metrics PR 52 appended to the served cell: the slot
loop's join and segment seconds by the program's own spans, the work PRs 48
and 49 removed as counters, and the seconds executions were held — all
window deltas of ``/metrics`` families through reader ``server_metrics``."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import cells  # noqa: E402

BENCH_DIR = ROOT / "benchmarks"
BENCH = cells.load_benchmark(ROOT)
READER = cells.load_module("readers", "server_metrics", BENCH_DIR)
CELL = "qwen3-8b-int8.serve-fanout-8k"
SPANS = ("join_span_s_per_req", "join_span_share", "segment_span_ms_per_step",
         "held_excess_ms_per_req")
COUNTERS = ("dead_row_chunk_share.serve",
            "decode_kv_blocks_skipped_share.serve")
# a window of the served cell as PERF.md has it: 7 joins of mean 1.32 s and
# 7 segments of 128 steps for 28 requests sent (27 of them joined inside the
# window), 35 of 112 pieces dead
WINDOW = {"server_metrics": {
    "requests_total": 28.0,
    "inflight_join_seconds_total": 9.24, "inflight_join_rows_total": 27.0,
    "inflight_segment_seconds_total": 12.6,
    "inflight_segment_steps_total": 896.0,
    "engine_prefill_row_chunks_total": 112.0,
    "engine_prefill_row_chunks_dead_total": 35.0,
    "engine_decode_kv_blocks_total": 1000.0,
    "engine_decode_kv_blocks_skipped_total": 369.0,
    "engine_executions_held_total": 0.0,
    "engine_held_excess_seconds_total": 0.0}}


def test_the_benchmark_validates():
    assert cells.validate(BENCH, ROOT) == []


def test_the_six_are_metrics_of_the_served_cell_and_of_no_other():
    served = {m["name"]: m
              for m in cells.metrics_for(BENCH, "per_layer", CELL)}
    assert set(SPANS + COUNTERS) <= set(served)
    for name in SPANS + COUNTERS:
        assert served[name]["workloads"] == [CELL]
        assert served[name]["source"] == (
            "program_counter" if name in COUNTERS else "program_span")


@pytest.mark.parametrize("name", SPANS + COUNTERS)
def test_each_file_names_the_reader_and_agrees_with_its_entry(name):
    spec = cells.load_layer_metric(name, BENCH_DIR)
    assert spec["reader"] == "server_metrics"
    assert spec["drivers"] == ["serve_closed_loop"]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    for key in ("layer", "unit", "better", "moves", "source"):
        assert spec[key] == entry[key], key


@pytest.mark.parametrize("name, want", [
    # per row the window's joins admitted, not per request sent in it
    ("join_span_s_per_req", 9.24 / 27),
    ("join_span_share", 100 * 9.24 / (9.24 + 12.6)),
    ("segment_span_ms_per_step", 1000 * 12.6 / 896),
    ("dead_row_chunk_share.serve", 100 * 35 / 112),
    ("decode_kv_blocks_skipped_share.serve", 36.9),
    ("held_excess_ms_per_req", 0.0),        # a clean run reads zero, not nothing
])
def test_the_reader_divides_the_window_deltas(name, want):
    spec = cells.load_layer_metric(name, BENCH_DIR)
    assert READER.read(spec, WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("name", SPANS + COUNTERS)
def test_a_program_without_the_families_reports_nothing(name):
    """The parent of PR 52 exports none of them: the reader returns None and
    the line leaves the metric out."""
    spec = cells.load_layer_metric(name, BENCH_DIR)
    assert READER.read(spec, {"server_metrics": {"requests_total": 28.0}}) \
        is None


def test_the_served_rehearsal_counts_the_shares_and_measures_no_span():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)   # one CPU device, as one chip
    p = subprocess.run(
        [sys.executable, str(BENCH_DIR / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 52), "--seconds", "2", "--trace", "1",
         "--rehearsal"],
        cwd=ROOT, env=env, timeout=600, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    metrics = json.loads(p.stdout.strip().splitlines()[-1])["metrics"]
    for name in COUNTERS:
        assert isinstance(metrics[name]["value"], float), name
        assert 0.0 <= metrics[name]["value"] <= 100.0
    for name in SPANS:
        assert metrics[name]["value"] == "not measured", name
