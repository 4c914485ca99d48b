"""From the profiler's trace (``*.xplane.pb``) to numbers.

Busy time is the union of the intervals in which an operation ran on a
device; a module's seconds are the durations of its executions on the
"XLA Modules" line that lie wholly inside the traced stretch (a stretch may
end in the middle of one); an operation's seconds are its own, without the
operations nested in it (a ``while`` does not swallow its body). Idle gaps
are named after the shortest host span that covers their middle. Several
device planes are averaged. Needs only ``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_MARK = "bench:trace_window"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
_ID_SUFFIX = re.compile(r"\(\d+\)$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
MIN_HOST_SPAN_NS = 1_000_000
# an execution still running when the trace stops is written with its end
# cut at the stop, a few ms around the window mark's end by the device's
# clock: whole executions end clearly before it
EDGE_NS = 20_000_000
_HLO = re.compile(r"^%?([^ ]+) = ([^ ]+)")


def short_op(name: str) -> str:
    """The trace names an operation by its whole HLO line. Keep its name
    without the instance number (``flash_prefill_attention``), except for a
    plain ``fusion.N``, which means nothing without its number and result."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    op, result = m.groups()
    base = re.sub(r"\.\d+$", "", op)
    if base == "fusion":
        return f"{op} {result.split('{')[0]}"[:80]
    return base[:80]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def read_planes(path: str) -> dict:
    """{plane name: {line name: [(name, start_ns, end_ns), ...]}}."""
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events)
    return planes


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_seconds(events: list[tuple[str, float, float]]) -> dict[str, float]:
    """Seconds per operation name, each event less the events nested in it."""
    total: dict[str, float] = defaultdict(float)
    stack: list[list] = []   # [name, end, own_ns]

    def close() -> None:
        name, _end, own = stack.pop()
        total[name] += own / 1e9

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][1]:
            close()
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    while stack:
        close()
    return dict(total)


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def reduce_planes(planes: dict, top: int = 10) -> dict:
    devices = {n: ls for n, ls in planes.items()
               if _DEVICE.match(n) and (ls.get(OP_LINE) or ls.get(MODULE_LINE))}
    if not devices:
        raise ValueError(f"no device plane with operations among {sorted(planes)}")
    host = [(n, s, e) for name, ls in planes.items() if name not in devices
            for evs in ls.values() for n, s, e in evs]
    marks = [(s, e) for n, s, e in host if n == WINDOW_MARK]
    if marks:
        lo, hi = marks[0]
    else:
        every = [x for ls in devices.values() for evs in ls.values() for x in evs]
        lo, hi = min(s for _, s, _ in every), max(e for _, _, e in every)
    # "$..." are the Python tracer's call events: a gap is named after a
    # span someone put there on purpose, not after time.sleep
    spans = sorted(((e - s, n, s, e) for n, s, e in host
                    if n != WINDOW_MARK and not n.startswith("$")
                    and e - s >= MIN_HOST_SPAN_NS))

    def doing(at: float) -> str:
        for _d, n, s, e in spans:           # shortest first
            if s <= at <= e:
                return n
        return "no host span"

    busy, modules, calls = 0.0, defaultdict(float), defaultdict(int)
    ops: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, str]] = []
    for lines in devices.values():
        op_events = _clip(lines.get(OP_LINE) or lines.get(MODULE_LINE), lo, hi)
        ran = union([(s, e) for _, s, e in op_events])
        busy += sum(e - s for s, e in ran) / 1e9
        for n, s in self_seconds(op_events).items():
            ops[short_op(n)] += s
        for n, s, e in lines.get(MODULE_LINE, []):
            if s >= lo and e <= hi - EDGE_NS:     # whole executions only
                n = _ID_SUFFIX.sub("", n)
                modules[n] += (e - s) / 1e9
                calls[n] += 1
        edges = [lo] + [x for se in ran for x in se] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append(((e - s) / 1e9, doing((s + e) / 2)))
    k = len(devices)
    by_gap: dict[str, float] = defaultdict(float)
    for secs, what in gaps:
        by_gap[what] += secs / k
    rank = lambda d: [[n, s] for n, s in sorted(  # noqa: E731
        d.items(), key=lambda x: -x[1])[:top]]
    return {
        "devices": k,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / k,
        "modules": {n: s / k for n, s in modules.items()},
        "module_calls": {n: c / k for n, c in calls.items()},
        "device_ops": rank({n: s / k for n, s in ops.items()}),
        "idle_gaps": rank(by_gap),
        "longest_gap_s": max((g for g, _ in gaps), default=0.0),
    }


def reduce_trace(trace_dir: str) -> dict:
    return reduce_planes(read_planes(find_xplane(trace_dir)))
