"""Reader ``state_kernel_roofline``: ``family_kernel_roofline``'s
arithmetic for a family that may count no experts. The least time the chip
could take for ONE kernel in the traced dispatches, by the roofline module
the metric file names (``roofline``: a module of ``benchmarks`` with
``kernel_least_seconds(sizes, precision, peaks, experts, prompt_lens,
steps)``; ``experts`` is the dispatch's counters or None), over that
kernel's own device seconds in the traced stretch (``trace.device_ops``, by
the ``name`` of its ``pallas_call``). The dispatches counted are the whole
executions of ``modules`` in the stretch, in the order they were sent. None
without a trace, without a whole execution, when the kernel is not among
the operations the reducer kept, and when the roofline module is not there
(a checkout that lacks the family).

A loop has no work of its own: where the reducer shows self seconds for
``while``, they are operations the profiler lost inside a loop, and this
kernel's calls may be among them. They are counted against the kernel, so a
trace that lost events reads LOW, never over 100%."""
import importlib

from benchmarks import reading, roofline


def counted(raw: dict, spec: dict):
    """The whole executions' dispatches, with or without counters."""
    dispatches = reading.lookup(raw, "traced.dispatches")
    calls = int(reading.module_calls(raw, spec["modules"]))
    if not dispatches or not calls:
        return None
    return dispatches[:calls]


def roofline_module(spec: dict):
    try:
        return importlib.import_module(f"benchmarks.{spec['roofline']}")
    except ImportError:
        return None


def read(spec: dict, raw: dict):
    rows = reading.lookup(raw, "trace.device_ops")
    dispatches = counted(raw, spec)
    module = roofline_module(spec)
    if rows is None or dispatches is None or module is None:
        return None
    ops = dict(rows)
    measured = ops.get(spec["kernel"])
    if not measured:
        return None
    measured += ops.get("while", 0.0)
    peaks = roofline.load_peaks(raw["device"]["kind"])
    least = sum(
        module.kernel_least_seconds(
            raw["sizes"], raw["precision"], peaks, d.get("experts"),
            d["prompt_lens"], d["steps"])[spec["kernel"]]["seconds"]
        for d in dispatches)
    return reading.finish(spec, raw, least / measured)
