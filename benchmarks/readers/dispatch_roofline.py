"""Reader ``dispatch_roofline``: the least time the chip could take for the
traced dispatches of the DeepSeek-V2 family, whole
(``roofline_deepseek_v2.dispatch``: prefill matmuls over every real token,
the three kernels, decode steps that read each weight they use once; from
the dispatches' real prompt lengths, the configuration and the window's
expert counters), over the device seconds of the modules that ran them.
The dispatches counted are the whole executions of ``modules`` in the
stretch, in the order they were sent. None without a trace or counters."""
from benchmarks import reading, roofline, roofline_deepseek_v2


def read(spec: dict, raw: dict):
    measured = reading.module_seconds(raw, spec["modules"])
    dispatches = reading.lookup(raw, "traced.dispatches")
    experts = reading.lookup(raw, "counts.experts")
    if not measured or not dispatches or not experts:
        return None
    calls = int(reading.module_calls(raw, spec["modules"]))
    peaks = roofline.load_peaks(raw["device"]["kind"])
    least = sum(
        roofline_deepseek_v2.dispatch(
            raw["sizes"], raw["precision"], peaks, experts,
            d["prompt_lens"], d["steps"])["total_s"]
        for d in dispatches[:calls])
    return reading.finish(spec, raw, least / measured)
