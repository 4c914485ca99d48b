"""Reader ``kernel_roofline``: the least time the chip could take for ONE
kernel of the DeepSeek-V2 family in the traced dispatches
(``roofline_deepseek_v2.kernel_least_seconds``, from their real prompt
lengths, the configuration and the window's expert counters) over that
kernel's own device seconds in the traced stretch (``trace.device_ops``, by
the ``name`` of its ``pallas_call``). The dispatches counted are the whole
executions of ``modules`` in the stretch, in the order they were sent. None
without a trace, without counters, and when the kernel is not among the
operations the reducer kept."""
from benchmarks import reading, roofline, roofline_deepseek_v2


def read(spec: dict, raw: dict):
    rows = reading.lookup(raw, "trace.device_ops")
    dispatches = reading.lookup(raw, "traced.dispatches")
    experts = reading.lookup(raw, "counts.experts")
    if rows is None or not dispatches or not experts:
        return None
    measured = dict(rows).get(spec["kernel"])
    calls = int(reading.module_calls(raw, spec["modules"]))
    if not measured or not calls:
        return None
    peaks = roofline.load_peaks(raw["device"]["kind"])
    least = sum(
        roofline_deepseek_v2.kernel_least_seconds(
            raw["sizes"], raw["precision"], peaks, experts,
            d["prompt_lens"], d["steps"])[spec["kernel"]]["seconds"]
        for d in dispatches[:calls])
    return reading.finish(spec, raw, least / measured)
