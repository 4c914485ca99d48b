"""Reader ``trace_op_share``: the seconds of the device operations named in
``ops`` (as ``trace.device_ops`` spells them: a kernel by the ``name`` of its
``pallas_call``) over the device's busy seconds in the traced stretch. Both
sides are clipped to the stretch alike, so the share is honest where a
roofline share of a clipped kernel would not be. None without a trace, and
when a named operation is not among the rows the reducer kept."""
from benchmarks import reading


def read(spec: dict, raw: dict):
    rows = reading.lookup(raw, "trace.device_ops")
    busy = reading.lookup(raw, "trace.busy_s")
    if rows is None or not busy:
        return None
    seconds = dict(rows)
    if any(op not in seconds for op in spec["ops"]):
        return None
    return reading.finish(
        spec, raw, sum(seconds[op] for op in spec["ops"]) / busy)
