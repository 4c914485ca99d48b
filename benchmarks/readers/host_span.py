"""Reader ``host_span``: the share of the window that the benchmark's own
host spans named ``span`` cover (or, with ``complement``, do not cover),
by the host's clock."""
from benchmarks import reading, stats


def read(spec: dict, raw: dict):
    spans = reading.lookup(raw, f"spans.{spec['span']}")
    window = reading.lookup(raw, "window.seconds")
    if spans is None or not window:
        return None
    share = stats.covered([tuple(s) for s in spans], 0.0, window) / window
    if spec.get("complement"):
        share = 1.0 - share
    return reading.finish(spec, raw, share)
