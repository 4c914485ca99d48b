"""Finding a cell's files by the names in BENCHMARK.json.

A cell is one entry of ``workloads``: a configuration under a traffic mix.
Everything that belongs to one configuration, one mix or one per-layer
metric is a file of its own, found here by name; nothing in this module
knows any particular name.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class CellError(ValueError):
    """A name that resolves to nothing, or a file that breaks the contract."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text(encoding="utf-8"))
    except FileNotFoundError as e:
        raise CellError(f"no such file: {path}") from e


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def find_cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise CellError(
        f"no workload {workload!r} in BENCHMARK.json; it has "
        f"{[w['name'] for w in bench['workloads']]}")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            cfg = load_json(Path(root) / c["file"])
            cfg["name"] = name
            return cfg
    raise CellError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, bench_dir: Path = HERE) -> dict:
    t = load_json(Path(bench_dir) / "traffic" / f"{name}.json")
    t["name"] = name
    return t


def load_layer_metric(name: str, bench_dir: Path = HERE) -> dict:
    m = load_json(Path(bench_dir) / "layer_metrics" / f"{name}.json")
    m["name"] = name
    return m


def load_module(kind: str, name: str, bench_dir: Path = HERE):
    """Import ``<bench_dir>/<kind>/<name>.py`` (a driver or a reader)."""
    if not NAME_RE.match(name):
        raise CellError(f"bad {kind} name {name!r}")
    path = Path(bench_dir) / kind / f"{name}.py"
    if not path.is_file():
        raise CellError(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, group: str, workload: str) -> list[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports: all
    without a ``workloads`` key, and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def validate(bench: dict, root: Path = ROOT) -> list[str]:
    """Every fault found in BENCHMARK.json and the files it names; an empty
    list means each name resolves and each arrow points at a metric that
    every one of its cells reports."""
    bad: list[str] = []
    bench_dir = Path(root) / bench["paths"][0]
    names = lambda xs: [x["name"] for x in xs]  # noqa: E731
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = names(bench[group])
        bad += [f"{group}: bad name {n!r}" for n in ns if not NAME_RE.match(n)]
        bad += [f"{group}: duplicate {n!r}" for n in set(ns) if ns.count(n) > 1]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better={m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source={m['source']!r}")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: an end-to-end metric is read by the "
                       "benchmark itself")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
    cells = names(bench["workloads"])
    used = set()
    for w in bench["workloads"]:
        used.add(w["config"])
        if w["config"] not in names(bench["configs"]):
            bad.append(f"{w['name']}: no configuration {w['config']!r}")
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']}: chips={w['chips']}")
        try:
            traffic = load_traffic(w["traffic"], bench_dir)
            load_module("drivers", traffic["driver"], bench_dir)
        except (CellError, KeyError) as e:
            bad.append(f"{w['name']}: {e}")
        mine = names(metrics_for(bench, "end_to_end", w["name"]))
        if "setup_s" not in mine or len(mine) < 2:
            bad.append(f"{w['name']}: reports {mine}")
        if not metrics_for(bench, "per_layer", w["name"]):
            bad.append(f"{w['name']}: no per-layer metric")
    for c in bench["configs"]:
        if c["name"] not in used:
            bad.append(f"configuration {c['name']!r} is used by no cell")
        try:
            cfg = load_json(Path(root) / c["file"])
            for key in c["reduced"]:
                if key not in cfg:
                    bad.append(f"{c['name']}: reduced key {key!r} not in file")
        except CellError as e:
            bad.append(str(e))
    for m in bench["per_layer"]:
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']}: no workload {w!r}")
        target = e2e.get(m["moves"])
        if target is None:
            bad.append(f"{m['name']}: moves unknown metric {m['moves']!r}")
        else:
            for w in m.get("workloads", cells):
                if "workloads" in target and w not in target["workloads"]:
                    bad.append(f"{m['name']}: moves {m['moves']}, which cell "
                               f"{w} does not report")
        try:
            spec = load_layer_metric(m["name"], bench_dir)
            load_module("readers", spec["reader"], bench_dir)
            for key in ("layer", "unit", "moves"):
                if spec[key] != m[key]:
                    bad.append(f"{m['name']}: {key} differs between "
                               "BENCHMARK.json and its file")
        except (CellError, KeyError) as e:
            bad.append(f"{m['name']}: {e}")
    return bad
