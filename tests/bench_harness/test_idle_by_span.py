"""Reader ``trace_idle_by_span`` and the seven per-layer metrics PR 37
appended: a cell's idle share booked by the program's own host spans, what
no span of the program explains, and the slot loop's two host counters."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import cells  # noqa: E402

BENCH_DIR = ROOT / "benchmarks"
BENCH = cells.load_benchmark(ROOT)
READER = cells.load_module("readers", "trace_idle_by_span", BENCH_DIR)
OFFLINE = ("idle_in_engine_host.offline", "idle_in_pipeline_host.offline",
           "idle_unexplained.offline")
SERVED = ("idle_in_slot_loop_host.serve", "idle_unexplained.serve",
          "slot_loop_host_gap_ms_per_req", "window_wait_ms_per_req")
PROGRAM = ["engine/", "pipeline/", "strategy/", "slot/", "serve/"]
# 0.4 s idle in a 10 s stretch; the named rows hold 0.37 of it: the ten-row
# cap dropped a 0.03 s name, which nobody can book to the program
TRACE = {"busy_s": 9.6, "window_s": 10.0, "idle_gaps": [
    ["engine/tokenize", 0.12], ["strategy/split", 0.09],
    ["engine/dispatch", 0.05], ["pipeline/read", 0.04],
    ["bench:pipeline", 0.03], ["serve/take", 0.02], ["no host span", 0.02]]}


@pytest.mark.parametrize("spec, trace, want", [
    ({"spans": ["engine/"]}, TRACE, 100 * 0.17 / 10.0),
    ({"spans": ["pipeline/", "strategy/"]}, TRACE, 100 * 0.13 / 10.0),
    ({"spans": ["slot/", "serve/"]}, TRACE, 100 * 0.02 / 10.0),
    # no gap under such a name: a reading of zero, not a missing one
    ({"spans": ["slot/"]}, TRACE, 0.0),
    # the rest: bench:*, "no host span" and the dropped 0.03 s
    ({"spans": PROGRAM, "rest": True}, TRACE, 100 * (0.4 - 0.32) / 10.0),
    # a parent program opens no such span: all of its idle is unexplained
    ({"spans": PROGRAM, "rest": True},
     {"busy_s": 9.6, "window_s": 10.0, "idle_gaps": [["no host span", 0.4]]},
     100 * 0.4 / 10.0),
    ({"spans": ["engine/"]}, None, None),
    ({"spans": PROGRAM, "rest": True}, None, None),
    ({"spans": ["engine/"]}, {**TRACE, "window_s": 0.0}, None),
])
def test_trace_idle_by_span(spec, trace, want):
    got = READER.read({**spec, "scale": 100}, {"trace": trace})
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_parts_add_up_to_the_idle_share():
    idle = cells.load_module("readers", "trace_idle", BENCH_DIR)
    raw = {"trace": TRACE}
    parts = [READER.read({**cells.load_layer_metric(n, BENCH_DIR)}, raw)
             for n in OFFLINE]
    served = READER.read(cells.load_layer_metric(SERVED[0], BENCH_DIR), raw)
    assert sum(parts) + 100 * 0.02 / 10.0 == pytest.approx(
        idle.read({"scale": 100}, raw))
    # in the served cell the slot loop's share and the rest are the whole
    rest = READER.read(cells.load_layer_metric(SERVED[1], BENCH_DIR), raw)
    assert served + rest + 100 * 0.13 / 10.0 == pytest.approx(
        idle.read({"scale": 100}, raw))


def test_benchmark_validates_with_the_seven_entries_at_the_end():
    assert cells.validate(BENCH, ROOT) == []
    assert [m["name"] for m in BENCH["per_layer"]][-7:] == list(
        OFFLINE + SERVED)


@pytest.mark.parametrize("name", OFFLINE + SERVED)
def test_metric_file_names_a_reader_and_its_cells_drivers(name):
    spec = cells.load_layer_metric(name, BENCH_DIR)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert cells.load_module("readers", spec["reader"], BENCH_DIR).read
    drivers = {cells.load_traffic(
        cells.find_cell(BENCH, w)["traffic"], BENCH_DIR)["driver"]
        for w in entry["workloads"]}
    assert drivers == set(spec["drivers"])
    assert (entry["unit"], entry["better"]) == (spec["unit"], "lower")
    assert entry["source"] == spec["source"]
    if spec["reader"] == "trace_idle_by_span":
        assert all(p.endswith("/") for p in spec["spans"])
        assert bool(spec.get("rest")) == name.startswith("idle_unexplained")


@pytest.mark.parametrize("name, family", [
    ("slot_loop_host_gap_ms_per_req", "inflight_host_gap_seconds_total"),
    ("window_wait_ms_per_req", "inflight_window_wait_seconds_total"),
])
def test_counter_metrics_read_a_registered_family(name, family):
    """The server registers the family the file divides; a parent without
    it gives no number (the metric is left out), not an error."""
    from vnsum_tpu.serve import metrics

    spec = cells.load_layer_metric(name, BENCH_DIR)
    assert spec["num"] == [family] and family in metrics._METRICS
    reader = cells.load_module("readers", spec["reader"], BENCH_DIR)
    raw = {"server_metrics": {family: 0.42, "requests_total": 28.0}}
    assert reader.read(spec, raw) == pytest.approx(1000 * 0.42 / 28)
    assert reader.read(spec, {"server_metrics": {"requests_total": 28.0}}) \
        is None
