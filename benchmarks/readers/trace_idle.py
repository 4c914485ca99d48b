"""Reader ``trace_idle``: the share of the traced stretch in which no
operation ran on the device (averaged over the chips used)."""
from benchmarks import reading


def read(spec: dict, raw: dict):
    busy = reading.lookup(raw, "trace.busy_s")
    window = reading.lookup(raw, "trace.window_s")
    if busy is None or not window:
        return None
    return reading.finish(spec, raw, 1.0 - busy / window)
