"""The readers of the program's phase scopes, host spans and round count,
on hand-built traces and runs, and on a traced what-if run at a test size
(CPU only; no chip is described or touched)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, tracefile  # noqa: E402
from bench.tracefile import Event, Trace  # noqa: E402
from faults import DENSE, mix  # noqa: E402

from repro import obs  # noqa: E402

MS = 1_000_000  # ns
FINAL = "jit(run)/stage/final/bridges"
CERT = "jit(one)/stage/certificate_build/2ec"


def ev(start_ms, end_ms, name="fusion", scope=""):
    return Event(int(start_ms * MS), int(end_ms * MS), name, scope)


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read


def run_of(trace, n_ops=2, ok=True):
    ops = [harness.Op("read", 0.0, 1.0, ok) for _ in range(n_ops)]
    return harness.Run(None, ops, (0.0, 1.0), 0.0, None, trace)


def final_trace() -> Trace:
    """One device in a 100 ms window: two final stages, each split into its
    four phases (an enclosing loop event counted once), a tombstone, and
    host spans of the engine with gaps in which the device idles."""
    d0 = []
    for t in (0, 50):
        d0 += [
            ev(t + 0, t + 4, "while", f"{FINAL}/tour/forest/jit(_forest_impl)"
               "/while"),
            ev(t + 1, t + 3, "scatter", f"{FINAL}/tour/forest/jit(_forest_"
               "impl)/while/body/kernel/round/boruvka/scatter"),
            ev(t + 4, t + 10, "sort", f"{FINAL}/tour/euler/jit(euler_tour)/"
               "sort"),
            ev(t + 10, t + 18, "gather", f"{FINAL}/tour/lowhigh/gather"),
            ev(t + 18, t + 20, "select", f"{FINAL}/emit/select"),
        ]
    d0.append(ev(30, 35, "copy", "jit(run)/stage/tombstone"))
    host = [
        ev(0, 100, tracefile.WINDOW_SPAN),
        ev(0, 25, "engine/current/bridges"),     # 5 ms with the device idle
        ev(0, 20, "stage/final/bridges"),
        ev(28, 48, "engine/delete_edges"),       # 15 ms idle
        ev(40, 47, "engine/current/bridges"),    # nested: counted once
        ev(50, 70, "engine/current/bridges"),    # no idle
        ev(70, 100, "bench/wait_arrival"),       # outside the engine
    ]
    return Trace([d0], host, (0, 100 * MS))


@pytest.fixture
def counted():
    """The engine's tracer after a window that counted 12 rounds."""
    tr = obs.enable_tracing(obs.Tracer())
    tr.count("certificate/rounds", 5)
    tr.count("certificate/rounds", 7)
    obs.disable_tracing()
    yield tr


def test_final_phases_split_the_final_stage():
    run = run_of(final_trace())
    got = {p: reader(f"final_{p}_ms.live")(run)
           for p in ("forest", "euler", "lowhigh", "emit")}
    # per op: two final stages over two operations
    assert got == {"forest": pytest.approx(4.0), "euler": pytest.approx(6.0),
                   "lowhigh": pytest.approx(8.0), "emit": pytest.approx(2.0)}
    assert sum(got.values()) == pytest.approx(
        reader("final_stage_ms.live")(run))


def test_phase_readers_are_silent_without_the_scopes():
    """A program without the phase scopes (the parent of this change)
    gives no number, and raises nothing."""
    t = final_trace()
    for e in t.devices[0]:
        e.scope = e.scope.split("/tour/")[0].split("/emit/")[0]
    run = run_of(t)
    for p in ("forest", "euler", "lowhigh", "emit"):
        assert reader(f"final_{p}_ms.live")(run) is None
    assert reader("final_stage_ms.live")(run) == pytest.approx(20.0)
    assert reader("final_forest_ms.live")(run_of(None)) is None


def test_host_time_is_engine_span_time_with_the_device_idle():
    run = run_of(final_trace(), n_ops=4)
    # (5 + 15 + 0) ms over 4 operations
    assert reader("host_ms.live")(run) == pytest.approx(5.0)
    assert reader("host_ms.live")(run_of(None)) is None
    bare = final_trace()
    bare.host = [h for h in bare.host if not h.name.startswith("engine/")]
    assert reader("host_ms.live")(run_of(bare)) is None


def test_cert_rounds_read_the_program_count(counted):
    run = run_of(final_trace(), n_ops=3)
    assert reader("cert_rounds.whatif")(run) == pytest.approx(4.0)
    assert reader("cert_rounds.whatif")(run_of(None)) is None
    assert reader("cert_rounds.whatif")(run_of(final_trace(), ok=False)) \
        is None


def test_cert_round_time_is_forest_time_per_counted_round(counted):
    t = Trace([[
        ev(0, 30, "while", f"{CERT}/cert/forest1/jit(_forest_impl)/while"),
        ev(30, 54, "while", f"{CERT}/cert/forest2/jit(_forest_impl)/while"),
        ev(54, 60, "scatter", f"{CERT}/cert/compact/scatter"),
        ev(60, 90, "while", f"{FINAL}/tour/forest/jit(_forest_impl)/while"),
    ]], [], (0, 100 * MS))
    # 54 ms of the certificate's two forests over 12 rounds
    assert reader("cert_round_ms.whatif")(run_of(t)) == pytest.approx(4.5)


def test_count_readers_are_silent_where_the_program_counts_nothing(
        monkeypatch, counted):
    """The parent keeps no count (and its ``obs`` has no ``last_tracer``):
    no number, and nothing raised."""
    monkeypatch.delattr(obs, "last_tracer")
    run = run_of(final_trace())
    assert reader("cert_rounds.whatif")(run) is None
    assert reader("cert_round_ms.whatif")(run) is None
    monkeypatch.setattr(obs, "last_tracer", lambda: None, raising=False)
    assert reader("cert_rounds.whatif")(run) is None


def test_traced_whatif_run_counts_rounds_and_spans_once():
    """A ``--trace 1`` what-if run through the harness at a test size: the
    engine counts the certificate's rounds, and each engine span reaches
    the profile once (the harness's annotating tracer and the engine's
    own annotation do not both write it)."""
    import time

    names = ["cert_rounds.whatif", "cert_round_ms.whatif", "host_ms.live"]
    cell = harness.Cell("test", 1, DENSE,
                        mix("whatif", check_answers=1),
                        [], [{"name": n, "unit": "x"} for n in names])
    seen = {}
    read = tracefile.read

    def keep(directory):
        # a CPU profile has no device plane: stand in one device that ran
        # nothing, as the harness's reduction expects one
        seen["trace"] = read(directory)
        seen["trace"].devices = seen["trace"].devices or [[]]
        return seen["trace"]

    tracefile.read = keep
    try:
        line = harness.run_cell(cell, 2**31 + 29, 1.0, True,
                                t_process=time.perf_counter(),
                                log=lambda *a: None)
    finally:
        tracefile.read = read
    assert line["correct"], line["checks"]
    requests = line["summary"]["requests"]
    assert line["metrics"]["cert_rounds.whatif"]["value"] >= 2
    host = [h.name for h in seen["trace"].host]
    assert host.count("engine/analyze/bridges") == requests
    assert host.count("stage/upload") == requests
    # no device time to read; the engine spans' time is all host time
    assert "cert_round_ms.whatif" not in line["metrics"]
    assert line["metrics"]["host_ms.live"]["value"] > 0
