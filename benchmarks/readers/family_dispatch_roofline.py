"""Reader ``family_dispatch_roofline``: the least time the chip could take
for the traced dispatches, whole, by the roofline module the metric file
names (``roofline``: a module of ``benchmarks`` with ``dispatch(sizes,
precision, peaks, experts, prompt_lens, steps)``), over the device seconds
of the modules that ran them. Dispatches and their counters as
``family_kernel_roofline`` takes them. None without a trace or counters."""
import importlib

from benchmarks import cells, reading, roofline

_kernel = cells.load_module("readers", "family_kernel_roofline")


def read(spec: dict, raw: dict):
    measured = reading.module_seconds(raw, spec["modules"])
    dispatches = _kernel.counted(raw, spec)
    if not measured or dispatches is None:
        return None
    module = importlib.import_module(f"benchmarks.{spec['roofline']}")
    peaks = roofline.load_peaks(raw["device"]["kind"])
    least = sum(
        module.dispatch(raw["sizes"], raw["precision"], peaks, d["experts"],
                        d["prompt_lens"], d["steps"])["total_s"]
        for d in dispatches)
    return reading.finish(spec, raw, least / measured)
