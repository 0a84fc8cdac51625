"""The reader of the program's count of full-width Borůvka rounds, on
hand-built runs and on a traced what-if run at a test size whose buffer
the filtered loop takes (CPU only; no chip is described or touched)."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, tracefile  # noqa: E402
from bench.tracefile import Trace  # noqa: E402
from faults import mix  # noqa: E402

from repro import obs  # noqa: E402

NAME = "cert_full_rounds.whatif"


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read


def run_of(trace, n_ops=3, ok=True):
    ops = [harness.Op("whatif", 0.0, 1.0, ok) for _ in range(n_ops)]
    return harness.Run(None, ops, (0.0, 1.0), 0.0, None, trace)


def counting(**counts):
    tr = obs.enable_tracing(obs.Tracer())
    for name, values in counts.items():
        for v in values:
            tr.count(f"certificate/{name}", v)
    obs.disable_tracing()
    return tr


def empty_trace() -> Trace:
    return Trace([[]], [], (0, 1))


def test_full_rounds_read_the_program_count():
    """12 rounds of which 9 full-width, over three requests."""
    counting(rounds=[5, 7], full_rounds=[4, 5])
    run = run_of(empty_trace())
    assert reader(NAME)(run) == pytest.approx(3.0)
    assert reader("cert_rounds.whatif")(run) == pytest.approx(4.0)
    assert reader(NAME)(run_of(None)) is None
    assert reader(NAME)(run_of(empty_trace(), ok=False)) is None


def test_no_full_round_reads_zero():
    """Every round of a window narrow or stopped: 0, not silence."""
    counting(rounds=[3], full_rounds=[0])
    assert reader(NAME)(run_of(empty_trace())) == 0.0


def test_silent_where_the_program_counts_no_full_rounds(monkeypatch):
    """The parent counts rounds alone, or (older) nothing: no number, and
    nothing raised."""
    counting(rounds=[5, 7])
    run = run_of(empty_trace())
    assert reader(NAME)(run) is None
    monkeypatch.setattr(obs, "last_tracer", lambda: None)
    assert reader(NAME)(run) is None
    monkeypatch.delattr(obs, "last_tracer")
    assert reader(NAME)(run) is None


def test_traced_whatif_run_counts_full_rounds_below_rounds():
    """A ``--trace 1`` what-if run at a test size whose 2^16-slot buffer
    runs the filtered loop: the full-width rounds are counted, and never
    more than the rounds."""
    config = {"generator": "planted", "n_nodes": 2000, "n_edges": 40000,
              "n_bridges": 5}
    names = ["cert_rounds.whatif", NAME]
    cell = harness.Cell("test", 1, config, mix("whatif", check_answers=1),
                        [], [{"name": n, "unit": "rounds"} for n in names])
    read = tracefile.read

    def one_device(directory):
        # a CPU profile has no device plane: stand in one device that ran
        # nothing, as the harness's reduction expects one
        t = read(directory)
        t.devices = t.devices or [[]]
        return t

    tracefile.read = one_device
    try:
        line = harness.run_cell(cell, 2**31 + 41, 1.0, True,
                                t_process=time.perf_counter(),
                                log=lambda *a: None)
    finally:
        tracefile.read = read
    assert line["correct"], line["checks"]
    got = {n: line["metrics"][n]["value"] for n in names}
    assert 0 < got[NAME] <= got["cert_rounds.whatif"]
