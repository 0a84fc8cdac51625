"""Arithmetic of the readers of the program's phase scopes, host spans and
counts (scopes nested inside a stage, e.g. ``stage/final/`` and
``/tour/euler/``; ``engine/*`` host spans; ``certificate/rounds``)."""
from __future__ import annotations

from bench.tracefile import merge_intervals, union_ns


def phase_s(trace, needles) -> float:
    """Device seconds under every one of ``needles`` at once (the slowest
    device where there are several)."""
    lo, hi = trace.window
    per = [union_ns([(max(e.start, lo), min(e.end, hi)) for e in ev
                     if e.end > lo and e.start < hi
                     and all(n in e.scope for n in needles)]) * 1e-9
           for ev in trace.devices]
    return max(per) if per else 0.0


def phase_ms_per(run, needles, count: int):
    """Device milliseconds under the phase per counted operation, or None
    where the program has no such phase (a parent without the scope)."""
    t = run.trace
    if t is None or not t.devices or not count:
        return None
    s = phase_s(t, needles)
    return 1e3 * s / count if s > 0 else None


def program_count(name: str):
    """The total the program counted under ``name`` in the traced window,
    from the engine's tracer once tracing has ended; None where the
    program keeps no such count."""
    from repro import obs

    last = getattr(obs, "last_tracer", None)
    tracer = last() if last is not None else None
    return tracer.counts().get(name) if tracer is not None else None


def host_only_s(trace) -> float:
    """Seconds inside the engine's ``engine/*`` host spans (their union, so
    the outermost ones) in which no operation ran on the device, averaged
    over the devices."""
    lo, hi = trace.window
    spans = merge_intervals([(max(h.start, lo), min(h.end, hi))
                             for h in trace.host
                             if h.name.startswith("engine/")
                             and h.end > lo and h.start < hi])
    inside = sum(e - s for s, e in spans)
    out = []
    for ev in trace.devices:
        busy = merge_intervals([(max(e.start, lo), min(e.end, hi))
                                for e in ev if e.end > lo and e.start < hi])
        out.append(inside - _overlap_ns(spans, busy))
    return sum(out) / len(out) * 1e-9 if out else 0.0


def _overlap_ns(a, b) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
