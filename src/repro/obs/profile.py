"""Opt-in on-device profiler capture (``jax.profiler.trace``).

The span tracer times stages from the HOST side; this knob captures the
DEVICE side in the same run. Both land on the profiler's clock: every
live span (``obs.Tracer.span``) is also a ``jax.profiler.TraceAnnotation``,
so the capture holds the engine's ``engine/*``, ``stage/*`` and
``host/*`` spans as host events, and every device operation carries the
``jax.named_scope`` labels of the stage (and phase) that launched it in
its HLO op metadata (DESIGN.md §Observability has the taxonomy). The
Chrome trace that ``Tracer.chrome_trace`` writes keeps its own
``perf_counter`` clock; the profile is the view in which host spans and
device operations can be lined up.

Off by default and zero-cost when unused: the profiler is only started
inside the context manager, and ``named_scope`` annotations are metadata
on the jaxpr (they never change the compiled program or its cache key).
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def profiler_trace(logdir: str | None):
    """``with profiler_trace(dir):`` captures a jax device profile into
    ``dir`` (view with XProf/TensorBoard); ``None`` disables — the same
    code path stays a no-op, which is how CLI knobs thread it through."""
    if not logdir:
        yield None
        return
    import jax

    with jax.profiler.trace(str(logdir)):
        yield logdir
