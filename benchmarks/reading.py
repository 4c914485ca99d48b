"""Arithmetic every reader shares: look a number up in a driver's raw
record by a dotted path, and apply a metric file's ``per`` and ``scale``."""
from __future__ import annotations


def lookup(raw: dict, path: str):
    """``raw["a"]["b"]`` for ``"a.b"``; None where any step is missing."""
    at = raw
    for key in path.split("."):
        if not isinstance(at, dict) or at.get(key) is None:
            return None
        at = at[key]
    return at


def total(raw: dict, paths: list[str]):
    """Sum of the numbers at these paths; None if any is missing."""
    values = [lookup(raw, p) for p in paths]
    if any(v is None for v in values):
        return None
    return float(sum(values))


def ratio(spec: dict, raw: dict, group: str):
    """Sum of ``spec["num"]`` over sum of the optional ``spec["den"]``, both
    lists of keys of ``raw[group]``, then ``finish``."""
    value = total(raw, [f"{group}.{k}" for k in spec["num"]])
    if value is not None and spec.get("den"):
        den = total(raw, [f"{group}.{k}" for k in spec["den"]])
        value = value / den if den else None
    return finish(spec, raw, value)


def finish(spec: dict, raw: dict, value):
    """``value`` over the optional ``per`` divisor(s), times ``scale``."""
    if value is None:
        return None
    if spec.get("per"):
        per = spec["per"] if isinstance(spec["per"], list) else [spec["per"]]
        for path in per:
            d = lookup(raw, path)
            if not d:
                return None
            value = value / d
    return value * spec.get("scale", 1.0)


def module_seconds(raw: dict, prefixes: list[str]):
    """Device seconds of the trace's XLA modules whose names start with one
    of ``prefixes``; None without a trace or without such a module."""
    modules = lookup(raw, "trace.modules")
    if modules is None:
        return None
    hit = [s for n, s in modules.items()
           if any(n.startswith(p) for p in prefixes)]
    return sum(hit) if hit else None


def module_calls(raw: dict, prefixes: list[str]) -> float:
    """Whole executions of those modules in the trace."""
    calls = lookup(raw, "trace.module_calls") or {}
    return sum(c for n, c in calls.items()
               if any(n.startswith(p) for p in prefixes))
