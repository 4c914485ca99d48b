"""Reader ``trace_op_share`` and the four kernel share metrics that use it:
a kernel's seconds over the busy seconds of the traced stretch, left out
where there is nothing honest to read."""
from __future__ import annotations

import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import cells  # noqa: E402

BENCH_DIR = ROOT / "benchmarks"
BENCH = cells.load_benchmark(ROOT)
READER = cells.load_module("readers", "trace_op_share", BENCH_DIR)
SHARES = ("prefill_attention_busy_share", "decode_attention_busy_share",
          "join_attention_busy_share", "segment_attention_busy_share")
TRACE = {"busy_s": 13.8, "window_s": 14.0, "device_ops": [
    ["flash_prefill_attention", 3.08], ["flash_decode_attention", 1.75],
    ["fusion.989 bf16[8,1,4096]", 0.62]]}


@pytest.mark.parametrize("ops, trace, want", [
    (["flash_prefill_attention"], TRACE, 100 * 3.08 / 13.8),
    (["flash_prefill_attention", "flash_decode_attention"], TRACE,
     100 * (3.08 + 1.75) / 13.8),
    # not among the rows the reducer kept: no number, not a zero
    (["flash_spec_verify_attention"], TRACE, None),
    (["flash_prefill_attention"], None, None),
    (["flash_prefill_attention"], {**TRACE, "busy_s": 0.0}, None),
])
def test_trace_op_share(ops, trace, want):
    got = READER.read({"ops": ops, "scale": 100}, {"trace": trace})
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("name", SHARES)
def test_share_metric_reads_a_contracted_kernel_name(name):
    """Each metric file names an operation that a ``pallas_call`` in
    ``vnsum_tpu/ops`` is given as its ``name=``, and only its own cells."""
    spec = cells.load_layer_metric(name, BENCH_DIR)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert spec["reader"] == "trace_op_share" and spec["scale"] == 100
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "lower", "device_trace")
    contracted = set()
    for src in (ROOT / "vnsum_tpu" / "ops").glob("*.py"):
        contracted |= set(re.findall(r'^\s+name="(\w+)",$', src.read_text(),
                                     re.M))
    assert set(spec["ops"]) <= contracted
    drivers = {cells.load_traffic(
        cells.find_cell(BENCH, w)["traffic"], BENCH_DIR)["driver"]
        for w in entry["workloads"]}
    assert drivers == set(spec["drivers"])


def test_benchmark_still_validates_with_the_new_metrics():
    assert cells.validate(BENCH, ROOT) == []
    assert [m["name"] for m in BENCH["per_layer"]][-4:] == list(SHARES)
