"""Reader ``trace_idle_by_span``: the share of the traced stretch in which
the device was idle under one of the program's own host spans.

``trace.idle_gaps`` names each idle gap of the device after the shortest
host span that covers its middle (``benchmarks/trace_reduce.py``); the
program opens such spans as ``<layer>/<name>`` (``core.profiling.host_span``).
This reader sums the seconds of the gaps whose name starts with one of
``spans`` and divides by ``trace.window_s``. With ``rest`` it gives what is
left instead: ``(window_s - busy_s - those seconds) / window_s``, so that a
name the reducer's ten-row cap dropped, the benchmark's own ``bench:*``
spans and "no host span" all count as NOT explained by the program. None
without a trace.
"""
from benchmarks import reading


def read(spec: dict, raw: dict):
    gaps = reading.lookup(raw, "trace.idle_gaps")
    busy = reading.lookup(raw, "trace.busy_s")
    window = reading.lookup(raw, "trace.window_s")
    if gaps is None or busy is None or not window:
        return None
    under = sum(s for n, s in gaps
                if any(n.startswith(p) for p in spec["spans"]))
    if spec.get("rest"):
        under = window - busy - under
    return reading.finish(spec, raw, under / window)
