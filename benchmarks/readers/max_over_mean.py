"""Reader ``max_over_mean``: the largest entry of the table of counts at
``path`` (a dotted path into the raw record; rows are summed first, so a
[layers][experts] table gives a load per expert over all layers) over its
mean entry: 1.0 is a perfectly even load. None without the table or when
it is all zero."""
from benchmarks import reading


def read(spec: dict, raw: dict):
    table = reading.lookup(raw, spec["path"])
    if not table:
        return None
    load = [sum(col) for col in zip(*table)]
    if not sum(load):
        return None
    return reading.finish(spec, raw, max(load) / (sum(load) / len(load)))
