"""Span tracer: nested, labeled wall-clock spans with device-sync boundaries.

The tracer answers the question the paper's cost decomposition poses —
*which stage did the milliseconds go to?* — with zero dependencies beyond
the standard library (jax is imported lazily, only when a span is opened
or syncs a device value):

* ``Tracer.span(name, **attrs)`` opens a nested wall-clock span as a
  context manager. Calling ``sp.sync(out)`` inside the block makes the
  span ``jax.block_until_ready`` the value before stamping its end time,
  so asynchronously-dispatched device work is attributed to the stage
  that launched it instead of to whichever later host sync happens to
  absorb it (the *device_sync boundary* rule, DESIGN.md §Observability).
  The span also enters a ``jax.profiler.TraceAnnotation`` of its name, so
  a profiler capture taken meanwhile shows it as a host event on the
  profiler's own clock, beside the device operations it launched.

* ``Tracer.count(name, value)`` adds ``int(value)`` to a named total
  (``counts()``): what the program computes but no span can time, e.g.
  the Borůvka rounds a certificate took inside one XLA ``while_loop``.

* ``chrome_trace()`` exports the Chrome trace-event format (load the file
  in ``chrome://tracing`` / Perfetto), spans as complete events and counts
  as counter events; ``rollup()`` folds the spans into a per-name
  {count, total, self} table; ``stage_rollup()`` extracts the outermost
  stage-classified spans — the per-stage cost table whose sum is compared
  against end-to-end wall time (``benchmarks/run.py --trace``).

A DISABLED tracer is the module-level ``NULL_TRACER`` singleton: every
``span()`` call returns one shared no-op handle, ``count`` returns
without touching its value (a device scalar is never read back), and no
clock is read — instrumented hot paths pay one attribute lookup and one
method call (bounded by
``tests/test_obs.py::test_disabled_tracer_overhead``). Enabling tracing
changes no program: spans wrap host-side dispatch only, so cache keys and
traced computations are untouched (the no-retrace tests gate this).

Single-threaded by design, like the serving loop it instruments: spans
must be closed in LIFO order on one thread.
"""
from __future__ import annotations

import json
import time

#: name prefixes classified as *stages* for the per-stage rollup: device
#: dispatch stages, merge-schedule phases, and host pre/post-processing. Request-level ``engine/*`` spans are containers,
#: not stages — their children carry the cost.
STAGE_PREFIXES = ("stage/", "merge/", "host/")


class Span:
    """One open (then closed) span. Use via ``with tracer.span(...) as sp``.

    ``sp.sync(value)`` registers a device value (any pytree) to
    ``jax.block_until_ready`` at span close. ``sp.t0``/``sp.dur``/
    ``sp.index`` are readable after the with-block.
    """

    __slots__ = ("tracer", "name", "attrs", "t0", "dur", "index", "depth",
                 "parent", "_pending")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.t0 = self.dur = 0.0
        self.index = -1
        self.depth = 0
        self.parent = -1
        self._pending = None

    def sync(self, value):
        """Block on ``value`` at span close (device_sync boundary)."""
        self._pending = value
        return value

    def __enter__(self):
        tr = self.tracer
        self.depth = len(tr._stack)
        self.parent = tr._stack[-1].index if tr._stack else -1
        self.index = tr._reserve()
        tr._stack.append(self)
        self.t0 = tr._clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._pending is not None:
            import jax

            jax.block_until_ready(self._pending)
            self._pending = None
        tr = self.tracer
        self.dur = tr._clock() - self.t0
        assert tr._stack and tr._stack[-1] is self, (
            f"span {self.name!r} closed out of LIFO order")
        tr._stack.pop()
        tr._commit(self)
        return False


class ProfiledSpan(Span):
    """A span that is also a ``jax.profiler.TraceAnnotation`` of its name:
    while a profiler capture runs, the span appears in it as a host event
    on the profiler's clock (and costs one no-op annotation otherwise)."""

    __slots__ = ("_annotation",)

    def __enter__(self):
        import jax

        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        try:
            return super().__exit__(exc_type, exc, tb)
        finally:
            self._annotation.__exit__(exc_type, exc, tb)


class Tracer:
    """Collects spans and counts; export via ``chrome_trace`` / ``rollup``
    / ``counts``."""

    enabled = True

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self.reset()

    def reset(self) -> None:
        #: closed spans as dicts, slot-ordered by span START (index)
        self._spans: list[dict | None] = []
        self._stack: list[Span] = []
        self._counts: dict[str, int] = {}
        #: (name, clock time, running total) per ``count`` call
        self._count_events: list[tuple[str, float, int]] = []

    # ------------------------------------------------------------ recording
    def span(self, name: str, **attrs) -> Span:
        return ProfiledSpan(self, name, attrs)

    #: a tracer is a callable: ``with tracer("stage/x"):`` == ``.span``
    __call__ = span

    def _reserve(self) -> int:
        self._spans.append(None)
        return len(self._spans) - 1

    def _commit(self, sp: Span) -> None:
        self._spans[sp.index] = {
            "name": sp.name, "t0": sp.t0, "dur": sp.dur, "depth": sp.depth,
            "parent": sp.parent, "index": sp.index, "attrs": sp.attrs,
        }

    def count(self, name: str, value) -> None:
        """Add ``int(value)`` to the total ``name`` (a device scalar is
        read back here, so call it where the value is already synced)."""
        total = self._counts.get(name, 0) + int(value)
        self._counts[name] = total
        self._count_events.append((name, self._clock(), total))

    # -------------------------------------------------------------- exports
    def spans(self) -> list[dict]:
        """Closed spans, start-ordered (open spans are excluded)."""
        return [s for s in self._spans if s is not None]

    def counts(self) -> dict[str, int]:
        """Totals of every ``count`` name since the last ``reset``."""
        return dict(self._counts)

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON object (``traceEvents``: spans as
        complete events with their attrs under ``args``, each ``count``
        call as a counter event carrying the running total; microsecond
        timestamps)."""
        events = [{
            "name": "process_name", "ph": "M", "pid": 0, "tid": 0,
            "args": {"name": "repro.obs"},
        }]
        for s in self.spans():
            events.append({
                "name": s["name"], "ph": "X", "pid": 0, "tid": 0,
                "ts": s["t0"] * 1e6, "dur": s["dur"] * 1e6,
                "args": {k: _jsonable(v) for k, v in s["attrs"].items()},
            })
        for name, t, total in self._count_events:
            events.append({"name": name, "ph": "C", "pid": 0, "tid": 0,
                           "ts": t * 1e6, "args": {name: total}})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def rollup(self) -> dict[str, dict]:
        """Per-name rollup: {count, total_s, self_s, max_s}. ``self_s`` is
        a span's duration minus its direct children's — the per-stage cost
        table of the paper's decomposition."""
        spans = self.spans()
        child_total: dict[int, float] = {}
        for s in spans:
            if s["parent"] >= 0:
                child_total[s["parent"]] = (child_total.get(s["parent"], 0.0)
                                            + s["dur"])
        table: dict[str, dict] = {}
        for s in spans:
            row = table.setdefault(
                s["name"],
                {"count": 0, "total_s": 0.0, "self_s": 0.0, "max_s": 0.0})
            row["count"] += 1
            row["total_s"] += s["dur"]
            row["self_s"] += s["dur"] - child_total.get(s["index"], 0.0)
            row["max_s"] = max(row["max_s"], s["dur"])
        return table

    def stage_rollup(self, prefixes=STAGE_PREFIXES) -> dict[str, dict]:
        """Rollup restricted to OUTERMOST stage-classified spans: a span
        counts iff its name starts with one of ``prefixes`` and no ancestor
        already counted (so nested probes/rounds are not double-billed).
        The sum of ``total_s`` here is the number compared against wall
        time by the ``--trace`` coverage check."""
        spans = self.spans()
        by_index = {s["index"]: s for s in spans}

        def outermost(s) -> bool:
            if not s["name"].startswith(prefixes):
                return False
            p = s["parent"]
            while p >= 0:
                ps = by_index.get(p)
                if ps is None:
                    break
                if ps["name"].startswith(prefixes):
                    return False
                p = ps["parent"]
            return True

        table: dict[str, dict] = {}
        for s in spans:
            if not outermost(s):
                continue
            row = table.setdefault(
                s["name"], {"count": 0, "total_s": 0.0, "max_s": 0.0})
            row["count"] += 1
            row["total_s"] += s["dur"]
            row["max_s"] = max(row["max_s"], s["dur"])
        return table

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
            f.write("\n")


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


class _NullSpan:
    """Shared no-op span handle: enter/exit/sync all do nothing."""

    __slots__ = ()
    t0 = 0.0
    dur = 0.0
    index = -1

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def sync(self, value):
        return value


_NULL_SPAN = _NullSpan()


class NullTracer:
    """The disabled tracer: every call is a no-op returning shared
    singletons — the zero-overhead off-hot-path contract."""

    enabled = False

    def span(self, name: str = "", **attrs) -> _NullSpan:
        return _NULL_SPAN

    __call__ = span

    def count(self, name: str, value) -> None:
        return None

    def reset(self) -> None:
        return None

    def spans(self) -> list:
        return []

    def counts(self) -> dict:
        return {}

    def chrome_trace(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def rollup(self) -> dict:
        return {}

    def stage_rollup(self, prefixes=STAGE_PREFIXES) -> dict:
        return {}


NULL_TRACER = NullTracer()
