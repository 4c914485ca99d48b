"""Reader ``state_dispatch_roofline``: ``family_dispatch_roofline``'s
arithmetic for a family that may count no experts. The least time the chip
could take for the traced dispatches, whole, by the roofline module the
metric file names (``roofline``: a module of ``benchmarks`` with
``dispatch(sizes, precision, peaks, experts, prompt_lens, steps)``;
``experts`` is the dispatch's counters or None), over the device seconds of
the modules that ran them. Dispatches as ``state_kernel_roofline`` takes
them. None without a trace, without a whole execution and without the
roofline module."""
from benchmarks import cells, reading, roofline

_kernel = cells.load_module("readers", "state_kernel_roofline")


def read(spec: dict, raw: dict):
    measured = reading.module_seconds(raw, spec["modules"])
    dispatches = _kernel.counted(raw, spec)
    module = _kernel.roofline_module(spec)
    if not measured or dispatches is None or module is None:
        return None
    peaks = roofline.load_peaks(raw["device"]["kind"])
    least = sum(
        module.dispatch(raw["sizes"], raw["precision"], peaks,
                        d.get("experts"), d["prompt_lens"],
                        d["steps"])["total_s"]
        for d in dispatches)
    return reading.finish(spec, raw, least / measured)
