#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json: a new process, set-up (weights and
inputs from the seed, warm-up of the cell's own shapes), a measured window,
and as the last line of standard output one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown``
with ``--trace 1``). With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.

This parent stays off JAX; one child (``--child``, started by the cell's
driver) holds the chip. Without a TPU the child ends before anything is
timed and this command exits non-zero with no result line.

``--rehearsal`` is for the tests alone: a tiny model on the CPU with the
kernels interpreted. Its line names the platform it ran on and carries no
device metric, so it is ``correct`` on everything but the platform.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path

T_START = time.time()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import cells  # noqa: E402
from benchmarks.childproc import ChildFailed  # noqa: E402

# a rehearsal ran on the CPU: only counts are numbers there, never a time,
# a rate or a share of the device
REHEARSAL_SOURCES = ("program_counter",)
NOT_MEASURED = "not measured"


def build_ctx(bench: dict, args) -> dict:
    cell = cells.find_cell(bench, args.workload)
    config = cells.load_config(bench, cell["config"], ROOT)
    traffic = cells.load_traffic(cell["traffic"], HERE)
    if args.rehearsal:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    return {
        "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds, "trace": args.trace,
        "rehearsal": args.rehearsal, "t_start": T_START,
        "cell": cell, "config": config, "traffic": traffic,
        "work_dir": tempfile.mkdtemp(prefix="vnsum-bench-"),
    }


def layer_metrics(bench: dict, ctx: dict, raw: dict) -> dict:
    """Each per-layer metric of this cell through its own reader; a reader
    that finds nothing to read leaves its metric out."""
    out = {}
    for m in cells.metrics_for(bench, "per_layer", ctx["workload"]):
        spec = cells.load_layer_metric(m["name"], HERE)
        reader = cells.load_module("readers", spec["reader"], HERE)
        value = reader.read(spec, raw)
        if ctx["rehearsal"] and m["source"] not in REHEARSAL_SOURCES:
            value = NOT_MEASURED
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(bench: dict, ctx: dict, raw: dict) -> dict:
    """The contract's last line, from a driver's raw record."""
    device = dict(raw["device"])
    if ctx["trace"]:
        metrics = layer_metrics(bench, ctx, raw)
        if raw.get("trace"):
            device["busy_s"] = raw["trace"]["busy_s"]
            device["window_s"] = raw["trace"]["window_s"]
    else:
        values = {**raw["values"], "setup_s": raw["setup_s"]}
        metrics = {
            m["name"]: {"value": NOT_MEASURED if ctx["rehearsal"]
                        else values[m["name"]], "unit": m["unit"]}
            for m in cells.metrics_for(bench, "end_to_end", ctx["workload"])
            if values.get(m["name"]) is not None}
    line = {
        "correct": all(raw["checks"].values()),
        "attempted": raw["attempted"], "failed": raw["failed"],
        "metrics": metrics, "device": device,
    }
    if ctx["trace"] and raw.get("trace"):
        line["breakdown"] = {"device_ops": raw["trace"]["device_ops"],
                             "idle_gaps": raw["trace"]["idle_gaps"]}
    return line


def run_child(spec_path: str) -> int:
    ctx = json.loads(Path(spec_path).read_text())
    driver = cells.load_module("drivers", ctx["traffic"]["driver"], HERE)
    raw = driver.child(ctx)
    Path(ctx["work_dir"], "raw.json").write_text(json.dumps(raw))
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tests only: tiny model, CPU, interpreted kernels")
    ap.add_argument("--child", metavar="SPEC", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return run_child(args.child)
    if not args.workload:
        ap.error("--workload is required")
    bench = cells.load_benchmark(ROOT)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    ctx = build_ctx(bench, args)
    try:
        driver = cells.load_module("drivers", ctx["traffic"]["driver"], HERE)
        raw = driver.parent(ctx)
        line = result_line(bench, ctx, raw)
    except ChildFailed as e:
        print(f"benchmarks/run.py: {e}; no result", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(ctx["work_dir"], ignore_errors=True)
    if not line["correct"]:
        print("benchmarks/run.py: failed checks: "
              f"{[k for k, v in raw['checks'].items() if not v]}",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except (cells.CellError, KeyError):
        traceback.print_exc()
        sys.exit(2)
