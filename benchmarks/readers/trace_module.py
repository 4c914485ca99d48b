"""Reader ``trace_module``: device seconds of the XLA modules whose names
start with one of ``modules``, from the profiler's trace, over ``per``."""
from benchmarks import reading


def read(spec: dict, raw: dict):
    return reading.finish(
        spec, raw, reading.module_seconds(raw, spec["modules"]))
