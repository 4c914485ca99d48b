"""Reader ``server_metrics``: Prometheus families of the server's
``/metrics``, as window deltas in ``raw["server_metrics"]``. ``num`` and
optional ``den`` list family names (labels folded)."""
from benchmarks import reading


def read(spec: dict, raw: dict):
    return reading.ratio(spec, raw, "server_metrics")
