"""Observability subsystem: span tracer, metrics registry, and the
no-retrace / zero-overhead contracts of the instrumented engine
(DESIGN.md §Observability)."""
import json
import time
import timeit as _timeit

import numpy as np
import pytest

from repro import obs
from repro.engine import BridgeEngine
from repro.graph import generators as gen
from repro.obs import (
    NULL_TRACER,
    Histogram,
    MetricsRegistry,
    Tracer,
    default_latency_buckets,
)

from helpers import blob_chain, nx_bridges


@pytest.fixture(autouse=True)
def _no_tracer_leak():
    """Every test starts and ends on the disabled tracer."""
    obs.disable_tracing()
    yield
    obs.disable_tracing()


# ------------------------------------------------------------------ tracer
def test_span_nesting_and_ordering():
    tr = Tracer()
    with tr.span("outer", kind="x"):
        with tr.span("stage/a"):
            pass
        with tr.span("stage/b"):
            with tr.span("stage/c"):
                pass
    spans = tr.spans()
    names = [s["name"] for s in spans]
    # slot-ordered by span START, not close
    assert names == ["outer", "stage/a", "stage/b", "stage/c"]
    by_name = {s["name"]: s for s in spans}
    assert by_name["outer"]["parent"] == -1 and by_name["outer"]["depth"] == 0
    assert by_name["stage/a"]["parent"] == by_name["outer"]["index"]
    assert by_name["stage/c"]["parent"] == by_name["stage/b"]["index"]
    assert by_name["stage/c"]["depth"] == 2
    assert by_name["outer"]["attrs"] == {"kind": "x"}
    # children are contained in the parent's time interval
    o, c = by_name["outer"], by_name["stage/c"]
    assert o["t0"] <= c["t0"] and c["t0"] + c["dur"] <= o["t0"] + o["dur"] + 1e-9


def test_span_lifo_enforced():
    tr = Tracer()
    a = tr.span("a")
    b = tr.span("b")
    a.__enter__()
    b.__enter__()
    with pytest.raises(AssertionError, match="LIFO"):
        a.__exit__(None, None, None)


def test_rollup_self_time_excludes_children():
    tr = Tracer()
    with tr.span("stage/parent"):
        with tr.span("stage/child"):
            time.sleep(0.005)
    roll = tr.rollup()
    p, c = roll["stage/parent"], roll["stage/child"]
    assert p["count"] == 1 and c["count"] == 1
    assert p["total_s"] >= c["total_s"]
    assert p["self_s"] == pytest.approx(p["total_s"] - c["total_s"])


def test_stage_rollup_outermost_only():
    tr = Tracer()
    with tr.span("engine/analyze"):         # container: not a stage
        with tr.span("stage/pipeline"):     # outermost stage: counted
            with tr.span("stage/inner"):    # nested stage: not double-billed
                pass
    with tr.span("host/readback"):          # stage at top level: counted
        pass
    staged = tr.stage_rollup()
    assert set(staged) == {"stage/pipeline", "host/readback"}


def test_chrome_trace_schema():
    tr = Tracer()
    with tr.span("stage/a", n=4, label="x"):
        with tr.span("stage/b"):
            pass
    doc = tr.chrome_trace()
    # must be valid JSON end to end
    doc2 = json.loads(json.dumps(doc))
    assert doc2["displayTimeUnit"] == "ms"
    events = doc2["traceEvents"]
    xs = [ev for ev in events if ev["ph"] == "X"]
    assert len(xs) == 2
    for ev in xs:
        assert set(ev) >= {"name", "ph", "ts", "dur", "pid", "tid", "args"}
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
    assert xs[0]["args"] == {"n": 4, "label": "x"}


def test_write_chrome_trace(tmp_path):
    tr = Tracer()
    with tr.span("stage/a"):
        pass
    path = tmp_path / "trace.json"
    tr.write_chrome_trace(str(path))
    with open(path) as f:
        doc = json.load(f)
    assert any(ev.get("ph") == "X" for ev in doc["traceEvents"])


def test_disabled_tracer_overhead():
    """The NULL_TRACER hot path must stay within a small constant factor
    of an empty function call — the instrumented-everywhere budget."""
    tr = NULL_TRACER

    def probe():
        with tr.span("stage/x"):
            pass

    def baseline():
        pass

    n = 20000
    t_probe = min(_timeit.repeat(probe, number=n, repeat=3))
    t_base = min(_timeit.repeat(baseline, number=n, repeat=3))
    # generous bound: shared singleton span => no allocation, no clock read
    assert t_probe < max(t_base * 40, 0.05), (
        f"disabled tracer overhead {t_probe / max(t_base, 1e-12):.1f}x")


def test_get_tracer_switches_at_call_time():
    assert obs.get_tracer() is NULL_TRACER
    live = obs.enable_tracing()
    assert obs.get_tracer() is live and live.enabled
    obs.disable_tracing()
    assert obs.get_tracer() is NULL_TRACER


# ----------------------------------------------------------------- metrics
def test_counter_and_gauge():
    m = MetricsRegistry()
    c = m.counter("x/count")
    c.inc()
    c.inc(3)
    g = m.gauge("x/step_s")
    before = time.time()
    g.set(0.25)
    snap = m.snapshot()
    assert snap["x/count"] == 4
    assert snap["x/step_s"]["value"] == 0.25
    assert before <= snap["x/step_s"]["updated_at"] <= time.time()


def test_metric_type_conflict_rejected():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        m.gauge("x")
    with pytest.raises(ValueError, match="already registered"):
        m.histogram("x")


def test_histogram_percentiles_vs_numpy():
    """Bucketed percentiles must match np.quantile within one bucket
    width over the hit region."""
    rng = np.random.default_rng(0)
    samples = rng.uniform(1e-4, 1.0, 5000)
    h = Histogram("lat", default_latency_buckets())
    for v in samples:
        h.observe(float(v))
    bounds = np.asarray(h.bounds)
    for q in (0.5, 0.95, 0.99):
        want = float(np.quantile(samples, q))
        got = h.percentile(q)
        i = int(np.searchsorted(bounds, want))
        lo = bounds[i - 1] if i > 0 else 0.0
        hi = bounds[i] if i < len(bounds) else float(samples.max())
        assert abs(got - want) <= (hi - lo) + 1e-12, (
            f"q={q}: got {got}, want {want}, bucket width {hi - lo}")


def test_histogram_exact_at_extremes():
    h = Histogram("lat")
    for v in (0.2, 0.4, 0.9):
        h.observe(v)
    assert h.percentile(0.0) == pytest.approx(0.2)
    assert h.percentile(1.0) == pytest.approx(0.9)
    snap = h.snapshot()
    assert snap["count"] == 3
    assert snap["min"] == 0.2 and snap["max"] == 0.9
    assert snap["mean"] == pytest.approx(0.5)


def test_histogram_empty_snapshot():
    snap = Histogram("lat").snapshot()
    assert snap["count"] == 0
    assert snap["p50"] is None and snap["mean"] is None


# ------------------------------------------- engine under tracing: contracts
N, E = 48, 256


def _graph(seed):
    src, dst, _ = gen.planted_bridge_graph(N, E, n_bridges=2, seed=seed)
    return src, dst


def test_enabled_tracer_no_retrace_and_result_parity():
    """Enabling tracing mid-process must add ZERO retraces on warm
    analyze / insert_edges / delete_edges and change no results."""
    eng = BridgeEngine()
    s0, d0 = _graph(0)
    s1, d1 = _graph(1)
    cold = eng.analyze(s0, d0, N, kind="bridges")
    eng.load(s0, d0, N)
    eng.insert_edges(s1[:16], d1[:16])
    eng.delete_edges(s1[:8], d1[:8])

    # second engine pass, same buckets: everything warm
    traces = eng.stats.traces
    tr = obs.enable_tracing()
    warm = eng.analyze(s0, d0, N, kind="bridges")
    eng.insert_edges(s1[16:32], d1[16:32])
    eng.delete_edges(s1[16:24], d1[16:24])
    assert eng.stats.traces == traces, "tracing caused a retrace"
    assert warm == cold
    names = {s["name"] for s in tr.spans()}
    assert {"engine/analyze/bridges", "stage/pipeline/bridges",
            "engine/insert_edges", "stage/merge/2ec", "stage/append",
            "engine/delete_edges", "stage/tombstone"} <= names


def test_engine_snapshot_one_rollup():
    eng = BridgeEngine()
    s0, d0 = _graph(2)
    eng.analyze(s0, d0, N)
    snap = eng.snapshot()
    assert snap["programs"] == len(eng._programs)
    assert snap["misses"] == eng.stats.misses
    assert snap["traces"] == eng.stats.traces
    assert "rebuilds" not in snap  # no live graph yet
    eng.load(s0, d0, N)
    eng.delete_edges(s0[:4], d0[:4])
    snap = eng.snapshot()
    assert snap["rebuilds_total"] == sum(snap["rebuilds"].values())
    assert snap["rebuilds"] == eng.live_rebuilds
    assert snap["live_graph_edges"] == eng.num_live_graph_edges


# ------------------------------------------------ counts and the profiler clock
def test_count_totals_and_chrome_counter_events():
    tr = Tracer()
    tr.count("certificate/rounds", 3)
    tr.count("certificate/rounds", np.int32(5))
    tr.count("other", 1)
    assert tr.counts() == {"certificate/rounds": 8, "other": 1}
    doc = json.loads(json.dumps(tr.chrome_trace()))
    cs = [ev for ev in doc["traceEvents"] if ev["ph"] == "C"]
    # one counter event per call, carrying the running total
    assert [ev["args"] for ev in cs] == [{"certificate/rounds": 3},
                                        {"certificate/rounds": 8},
                                        {"other": 1}]
    assert all(isinstance(ev["ts"], float) for ev in cs)
    tr.reset()
    assert tr.counts() == {}
    assert not [ev for ev in tr.chrome_trace()["traceEvents"]
                if ev["ph"] == "C"]


def test_null_tracer_count_never_converts():
    """With tracing off a count must not read its value: on the engine's
    path that would be a device readback."""

    class Unreadable:
        def __int__(self):
            raise AssertionError("value read back with tracing off")

    NULL_TRACER.count("certificate/rounds", Unreadable())
    assert NULL_TRACER.counts() == {}
    with pytest.raises(AssertionError, match="read back"):
        Tracer().count("certificate/rounds", Unreadable())


def test_disable_tracing_returns_tracer_and_last_tracer_keeps_it():
    assert obs.disable_tracing() is NULL_TRACER  # nothing live to remove
    live = obs.enable_tracing()
    live.count("certificate/rounds", 2)
    assert obs.last_tracer() is live
    assert obs.disable_tracing() is live
    assert obs.get_tracer() is NULL_TRACER
    # the counts outlive the session
    assert obs.last_tracer().counts() == {"certificate/rounds": 2}
    newer = obs.enable_tracing(Tracer())
    assert obs.last_tracer() is newer and newer is not live


def _host_event_names(logdir) -> list[str]:
    """Names of the host events of the newest profile under ``logdir``."""
    from pathlib import Path

    from jax.profiler import ProfileData

    files = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    assert files, f"no profile written under {logdir}"
    pd = ProfileData.from_serialized_xspace(files[-1].read_bytes())
    return [ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_profiler_capture_holds_engine_spans(tmp_path):
    """A live span is a profiler annotation: a CPU capture holds the
    engine's spans as host events, once each."""
    eng = BridgeEngine()
    s, d = _graph(4)
    eng.analyze(s, d, N)  # compile outside the capture
    obs.enable_tracing()
    with obs.profiler_trace(str(tmp_path)):
        eng.analyze(s, d, N)
    names = _host_event_names(tmp_path)
    for want in ("engine/analyze/bridges", "stage/upload", "stage/pad",
                 "stage/pipeline/bridges", "stage/convert"):
        assert names.count(want) == 1, (want, sorted(set(names)))


def test_serve_bridges_profile_holds_engine_spans(tmp_path):
    """``serve_bridges --trace-out --profile-dir``: the device profile holds
    the engine's ``engine/*`` and ``stage/*`` spans as host events, on the
    profiler's clock, beside the Chrome trace."""
    from repro.launch.serve_bridges import main

    trace_out = tmp_path / "trace.json"
    main(["--smoke", "--workload", "churn", "--delete-ratio", "1",
          "--queries", "2", "--batch", "2", "--deltas", "1", "--n", "32",
          "--edges", "128", "--trace-out", str(trace_out),
          "--profile-dir", str(tmp_path / "profile")])
    names = set(_host_event_names(tmp_path / "profile"))
    assert {"engine/analyze/bridges", "engine/analyze_batch/bridges",
            "engine/delete_edges", "stage/upload", "stage/pipeline/bridges",
            "stage/tombstone", "host/readback", "stage/final/bridges"} <= names
    chrome = json.loads(trace_out.read_text())["traceEvents"]
    assert any(ev["name"] == "certificate/rounds" and ev["ph"] == "C"
               for ev in chrome)


def test_one_shot_rounds_match_certificate_rounds():
    """The one-shot pipeline returns its certificate's forest rounds, and
    the engine counts them while tracing is on: single, batched (padding
    rows not counted), and zeros where no certificate is built."""
    import jax

    from repro.core.certificate import sparse_certificate_ex
    from repro.engine import make_analysis_fn
    from repro.graph.datastructs import EdgeList

    s, d = _graph(5)
    el = EdgeList.from_arrays(s, d, N)
    _, _, _, (r1, r2), (w1, w2) = sparse_certificate_ex(el)
    want, want_full = [int(r1), int(r2)], [int(w1), int(w2)]
    # a buffer below 2^10 slots has no narrow rounds: only each forest's
    # convergence check skips its full-width scatter-min
    assert want_full == [r - 1 for r in want]
    _, rounds, full = jax.jit(make_analysis_fn(N, "bridges", "device"))(
        el.src, el.dst, el.mask)
    assert rounds.tolist() == want
    assert full.tolist() == want_full
    _, rounds, full = jax.jit(make_analysis_fn(N, "cuts", "device"))(
        el.src, el.dst, el.mask)
    assert rounds.tolist() == full.tolist() == [0, 0]

    s2, d2 = _graph(6)
    _, _, _, (q1, q2), _ = sparse_certificate_ex(
        EdgeList.from_arrays(s2, d2, N))
    eng = BridgeEngine()
    tr = obs.enable_tracing()
    eng.analyze(s, d, N)
    assert tr.counts() == {"certificate/rounds": sum(want),
                           "certificate/full_rounds": sum(want_full)}
    tr.reset()
    # padded to 4 rows; under vmap the plain loop scans every round fully
    eng.analyze_batch([(s, d), (s2, d2), (s, d)], N)
    total = 2 * sum(want) + int(q1) + int(q2)
    assert tr.counts() == {"certificate/rounds": total,
                           "certificate/full_rounds": total}


def test_full_rounds_counted_below_rounds_where_the_loop_compacts():
    """``certificate/full_rounds`` counts the rounds whose scatter-min ran
    over the whole buffer: never more than ``certificate/rounds``, and
    fewer where the filtered loop stops at zero and compacts (a 2^16-slot
    buffer: 4000 links among 8 blobs, padded with self-loops)."""
    s, d, n = blob_chain(8, 64, 4000, seed=0)
    loops = np.arange((1 << 16) - len(s), dtype=np.int32) % n
    s, d = np.concatenate([s, loops]), np.concatenate([d, loops])
    eng = BridgeEngine()
    tr = obs.enable_tracing()
    got = eng.analyze(s, d, n)
    counts = tr.counts()
    obs.disable_tracing()
    assert got == nx_bridges(s, d, n)
    rounds = counts["certificate/rounds"]
    assert 0 < counts["certificate/full_rounds"] <= rounds - 3


def _op_names(compiled_text: str) -> list[str]:
    import re

    return re.findall(r'op_name="([^"]*)"', compiled_text)


def test_phase_scopes_cover_final_and_certificate_programs():
    """In the compiled one-shot and final programs, every instruction of
    the final stage carries one of its phases, and every Borůvka round of
    the certificate one of its two forests."""
    import re

    import jax

    from repro.core.certificate import certificate_capacity
    from repro.engine.batched import make_analysis_fn
    from repro.engine.dispatch import build_final_program

    n, cap = 64, 256
    cc = certificate_capacity(n)

    def structs(c):
        return (jax.ShapeDtypeStruct((c,), np.int32),
                jax.ShapeDtypeStruct((c,), np.int32),
                jax.ShapeDtypeStruct((c,), np.bool_))

    one_shot = _op_names(jax.jit(make_analysis_fn(n, "bridges", "device"))
                         .lower(*structs(cap)).compile().as_text())
    final = _op_names(build_final_program(n, "bridges", lambda: None)
                      .lower(*structs(cc)).compile().as_text())
    phase = re.compile(r"stage/final/bridges/(tour/(forest|euler|lowhigh)"
                       r"|emit)/")
    for ops in (one_shot, final):
        in_final = [o for o in ops if "stage/final/" in o]
        assert in_final
        assert [o for o in in_final if not phase.search(o)] == []
        assert any("tour/forest" in o and "kernel/round/boruvka" in o
                   for o in in_final)
    rounds = [o for o in one_shot if "stage/certificate_build/" in o
              and "kernel/round/boruvka" in o]
    assert rounds
    assert [o for o in rounds
            if not re.search(r"/cert/forest[12]/", o)] == []
    assert any("/cert/compact/" in o for o in one_shot)


def test_hook_scope_under_both_forests_of_the_certificate():
    """The one-shot program's HLO metadata holds ``kernel/hook/boruvka``
    under ``cert/forest1`` and under ``cert/forest2``, and the hook's
    operations are outside the round scopes, which time the edge scan."""
    import jax

    from repro.engine.batched import make_analysis_fn

    n, cap = 64, 256
    structs = (jax.ShapeDtypeStruct((cap,), np.int32),
               jax.ShapeDtypeStruct((cap,), np.int32),
               jax.ShapeDtypeStruct((cap,), np.bool_))
    hooks = [o for o in _op_names(
        jax.jit(make_analysis_fn(n, "bridges", "device"))
        .lower(*structs).compile().as_text())
        if "kernel/hook/boruvka" in o]
    for forest in ("/cert/forest1/", "/cert/forest2/"):
        assert any("stage/certificate_build/" in o and forest in o
                   for o in hooks), forest
    assert [o for o in hooks if "kernel/round/" in o
            or "kernel/narrow/" in o] == []
