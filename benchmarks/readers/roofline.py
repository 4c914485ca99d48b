"""Reader ``roofline``: the least time the chip could take for the traced
dispatches (roofline.least_seconds, from their real prompt lengths and the
configuration) over the device seconds of the modules that ran them, over
the executions that lie wholly inside the traced stretch."""
from benchmarks import reading, roofline


def read(spec: dict, raw: dict):
    measured = reading.module_seconds(raw, spec["modules"])
    dispatches = reading.lookup(raw, "traced.dispatches")
    if not measured or not dispatches:
        return None
    # the stretch may end inside a dispatch: the trace's whole executions
    # are the first of the dispatches, in the order they were sent
    calls = reading.module_calls(raw, spec["modules"])
    dispatches = dispatches[:int(calls)]
    peaks = roofline.load_peaks(raw["device"]["kind"])
    least = sum(
        roofline.least_seconds(raw["sizes"], raw["precision"], peaks,
                               d["prompt_lens"], d["steps"])["total_s"]
        for d in dispatches)
    return reading.finish(spec, raw, least / measured)
