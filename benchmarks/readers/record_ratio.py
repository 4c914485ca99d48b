"""Reader ``record_ratio``: the number at ``num`` over the number at
``den``, both dotted paths into the driver's raw record (counters the
program summed and the driver copied there). None where either is missing
or the divisor is zero."""
from benchmarks import reading


def read(spec: dict, raw: dict):
    num, den = reading.lookup(raw, spec["num"]), reading.lookup(raw, spec["den"])
    if num is None or not den:
        return None
    return reading.finish(spec, raw, num / den)
