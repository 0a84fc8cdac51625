"""The live cell's comparison comes out false for the control and for each
fault the timed path can have, and true for the program as it is (CPU,
test size; the chip look is skipped)."""
from __future__ import annotations

from faults import DENSE, alter, mix, run

from repro.engine.engine import BridgeEngine

LIVE = mix("live-flaps", pool_links=64, rate_per_s=8.0)


def test_program_as_it_is_is_correct():
    line = run(DENSE, LIVE)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 10
    assert line["checks"]["live_edges_gap"] == {"value": 0.0, "limit": 0.0}


def test_control_is_not_correct():
    line = run(DENSE, LIVE, control=True)
    assert not line["correct"]
    assert line["checks"]["wrong_answers"]["value"] >= 1


def test_writes_that_leave_the_state_unchanged_are_not_correct(monkeypatch):
    def unchanged(self, src, dst, **kw):
        return self.current_analysis(kw.get("kind", "bridges"))

    insert = BridgeEngine.insert_edges
    calls = []

    def insert_after_setup(self, src, dst, **kw):
        # set-up's own inserts go through; the window's do nothing
        calls.append(len(src))
        if len(calls) <= 2:
            return insert(self, src, dst, **kw)
        return unchanged(self, src, dst, **kw)

    monkeypatch.setattr(BridgeEngine, "insert_edges", insert_after_setup)
    monkeypatch.setattr(BridgeEngine, "delete_edges", unchanged)
    assert not run(DENSE, LIVE)["correct"]


def test_altered_answer_is_not_correct(monkeypatch):
    current = BridgeEngine.current_analysis
    monkeypatch.setattr(
        BridgeEngine, "current_analysis",
        lambda self, kind="bridges", **kw: alter(current(self, kind, **kw)))
    assert not run(DENSE, LIVE)["correct"]
