#!/usr/bin/env python3
"""Device seconds of a profiler trace by the scope the work was written under.

    python scripts/trace_by_scope.py <trace dir | file.xplane.pb> <maps.json>
        [--depth 2] [--json out.json]

The device trace names an operation by its HLO line (``%fusion.989 = ...``)
and carries no ``jax.named_scope``; the program does, in its compiled text.
``maps.json`` is what ``TpuBackend.scope_maps()`` returned, dumped with
``json.dump``: per program the XLA module's name and {instruction: scope
path}. This script puts the two together: each operation's self time (an
operation less the operations nested in it, as ``benchmarks/trace_reduce``
counts it) is booked under the scope path of its instruction, cut to
``--depth`` parts (1: phase; 2: phase/component), inside the XLA module
execution it ran in. Several programs share a module name (every one-shot
bucket is ``jit_generate``): each traced module takes the map that knows
most of its instructions. Printed: seconds by scope, each Pallas kernel
under its scope, and per module what fell under no scope, by operation.

Scope names (none carries a shape or a number, so cells compare):
phases ``prefill`` ``decode`` ``adopt`` (backend/engine.py); components
``embed`` ``qkv`` ``kv_write`` ``attn`` ``attn_out`` ``mlp`` ``lm_head``
(models/llama.py), ``q_lora`` ``kv_latent`` ``router`` ``experts``
``shared_experts`` beside them (models/deepseek.py) and ``sample`` ``emit``
(backend/engine.py).
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
from collections import defaultdict
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmarks import trace_reduce as tr  # noqa: E402

NO_MODULE = "(outside any module)"
NO_MAP = "(no map for this module)"
NOT_IN_MAP = "(instruction not in the map)"
NO_SCOPE = "(no scope)"
UNSCOPED = (NO_MAP, NOT_IN_MAP, NO_SCOPE)


def instruction(event_name: str) -> str:
    """``fusion.989`` of the trace's ``%fusion.989 = bf16[...] fusion(...)``."""
    m = tr._HLO.match(event_name)
    return m.group(1) if m else event_name


def window_of(planes: dict, devices: dict) -> tuple[float, float]:
    """The benchmark's window mark if the trace has one, else all of it."""
    for name, lines in planes.items():
        if name in devices:
            continue
        for events in lines.values():
            for n, s, e in events:
                if n == tr.WINDOW_MARK:
                    return s, e
    every = [x for ls in devices.values() for evs in ls.values() for x in evs]
    return min(s for _, s, _ in every), max(e for _, _, e in every)


def by_module(lines: dict, lo: float, hi: float) -> dict[str, dict[str, float]]:
    """{module execution name (with its id): {operation event name: self
    seconds}} of one device plane, clipped to the window. An operation
    belongs to the module execution its start lies in."""
    runs = sorted((s, e, n) for n, s, e in lines.get(tr.MODULE_LINE, []))
    starts = [r[0] for r in runs]
    groups: dict[str, list] = defaultdict(list)
    for ev in tr._clip(lines.get(tr.OP_LINE, []), lo, hi):
        i = bisect.bisect_right(starts, ev[1]) - 1
        inside = i >= 0 and ev[1] < runs[i][1]
        groups[runs[i][2] if inside else NO_MODULE].append(ev)
    return {m: tr.self_seconds(evs) for m, evs in groups.items()}


def pick_map(module: str, ops: dict[str, float], maps: list[dict]):
    """Of the maps made for XLA modules of this name, the one that knows most
    of the instructions seen; None if there is none."""
    base = tr._ID_SUFFIX.sub("", module)
    seen = {instruction(n) for n in ops}
    best = None
    for m in maps:
        if m["module"] == base:
            known = len(seen & m["scopes"].keys())
            if best is None or known > best[0]:
                best = (known, m)
    return best[1] if best else None


def by_scope(planes: dict, maps: list[dict], depth: int = 2) -> dict:
    devices = {n: ls for n, ls in planes.items()
               if tr._DEVICE.match(n) and ls.get(tr.OP_LINE)}
    if not devices:
        raise ValueError(f"no device plane with operations among {sorted(planes)}")
    lo, hi = window_of(planes, devices)
    k = len(devices)
    scopes: dict[str, float] = defaultdict(float)
    kernels: dict[tuple[str, str], float] = defaultdict(float)
    unscoped: dict[str, dict] = {}
    modules: dict[str, dict] = {}
    busy = 0.0
    for lines in devices.values():
        ran = tr.union([(s, e) for _, s, e in
                        tr._clip(lines[tr.OP_LINE], lo, hi)])
        busy += sum(e - s for s, e in ran) / 1e9 / k
        for module, ops in by_module(lines, lo, hi).items():
            chosen = pick_map(module, ops, maps)
            info = modules.setdefault(module, {
                "program": chosen["program"] if chosen else None,
                "seconds": 0.0, "instructions": 0, "in_map": 0})
            for name, secs in ops.items():
                secs /= k
                inst = instruction(name)
                if chosen is None:
                    path = NO_MAP
                elif inst not in chosen["scopes"]:
                    path = NOT_IN_MAP
                else:
                    path = "/".join(
                        chosen["scopes"][inst].split("/")[:depth]) or NO_SCOPE
                scopes[path] += secs
                info["seconds"] += secs
                info["instructions"] += 1
                info["in_map"] += path not in (NO_MAP, NOT_IN_MAP)
                if " custom-call(" in name:
                    kernels[(tr.short_op(name), path)] += secs
                if path in UNSCOPED:
                    u = unscoped.setdefault(
                        module, {"seconds": 0.0, "ops": defaultdict(float)})
                    u["seconds"] += secs
                    u["ops"][f"{tr.short_op(name)} {path}"] += secs
    total = sum(scopes.values())
    rank = lambda d: sorted(d.items(), key=lambda x: -x[1])  # noqa: E731
    return {
        "devices": k, "window_s": (hi - lo) / 1e9, "busy_s": busy,
        "self_s": total, "depth": depth,
        "scoped_share": (1.0 - sum(scopes[u] for u in UNSCOPED if u in scopes)
                         / total) if total else None,
        "by_scope": [[p, s] for p, s in rank(scopes)],
        "kernels": [[n, p, s] for (n, p), s in rank(kernels)],
        "unscoped": {m: {"seconds": u["seconds"],
                         "ops": [[n, s] for n, s in rank(u["ops"])[:12]]}
                     for m, u in unscoped.items()},
        "modules": modules,
    }


def render(r: dict) -> str:
    total = r["self_s"] or 1.0
    out = [f"{r['devices']} device(s); traced {r['window_s']:.3f} s, busy "
           f"{r['busy_s']:.3f} s; operations' self time {r['self_s']:.3f} s, "
           f"{100 * (r['scoped_share'] or 0):.2f}% of it under a scope",
           "", f"seconds by scope (depth {r['depth']}):"]
    out += [f"  {s:10.4f} s {100 * s / total:6.2f}%  {p}"
            for p, s in r["by_scope"]]
    out += ["", "kernels (custom calls):"]
    out += [f"  {s:10.4f} s {100 * s / total:6.2f}%  {n}  under {p}"
            for n, p, s in r["kernels"]]
    out += ["", "XLA module executions:"]
    out += [f"  {m['seconds']:10.4f} s  {name}: {m['program']}, "
            f"{m['in_map']} of {m['instructions']} instructions in its map"
            for name, m in r["modules"].items()]
    for name, u in r["unscoped"].items():
        out += ["", f"under no scope in {name}: {u['seconds']:.4f} s"]
        out += [f"  {s:10.4f} s  {n}" for n, s in u["ops"]]
    return "\n".join(out)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="profiler directory, or one *.xplane.pb")
    ap.add_argument("maps", help="JSON of TpuBackend.scope_maps()")
    ap.add_argument("--depth", type=int, default=2,
                    help="scope parts kept: 1 phase, 2 phase/component")
    ap.add_argument("--json", metavar="PATH", help="also write the result")
    args = ap.parse_args(argv)
    path = (args.trace if os.path.isfile(args.trace)
            else tr.find_xplane(args.trace))
    maps = json.loads(Path(args.maps).read_text())
    result = by_scope(tr.read_planes(path), maps, args.depth)
    if args.json:
        Path(args.json).write_text(json.dumps(result, indent=1))
    try:
        print(render(result))
    except BrokenPipeError:   # | head
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
    return 0


if __name__ == "__main__":
    sys.exit(main())
