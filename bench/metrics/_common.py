"""Shared arithmetic of the metric readers."""
from __future__ import annotations


def idle_pct(run):
    t = run.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)


def scope_ms_per(run, prefixes, count: int):
    """Device milliseconds under the scopes per counted operation (the
    slowest device where there are several)."""
    t = run.trace
    if t is None or not t.devices or count <= 0:
        return None
    s = t.scope_s(prefixes)
    return 1e3 * s / count if s > 0 else None
