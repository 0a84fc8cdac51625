"""The what-if cells' comparison comes out false for the control and for
each fault the timed path can have, and true for the program as it is
(CPU, test size; the chip look is skipped)."""
from __future__ import annotations

import pytest

from faults import DENSE, KRON, alter, mix, run

from repro.engine.engine import BridgeEngine

CONFIGS = {"dense": DENSE, "kron": KRON}


@pytest.fixture
def analyze():
    return BridgeEngine.analyze


@pytest.mark.parametrize("config", CONFIGS)
def test_program_as_it_is_is_correct(config):
    line = run(CONFIGS[config], mix("whatif"))
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["checks"]["wrong_answers"] == {"value": 0.0, "limit": 0.0}


@pytest.mark.parametrize("config", CONFIGS)
def test_control_is_not_correct(config):
    line = run(CONFIGS[config], mix("whatif"), control=True)
    assert not line["correct"]
    assert line["checks"]["wrong_answers"]["value"] >= 1


def _faulty(monkeypatch, analyze, change_delete=None, change_answer=None):
    def patched(self, src, dst, n, **kw):
        if change_delete is not None:
            kw["delete"] = change_delete(kw.get("delete"))
        out = analyze(self, src, dst, n, **kw)
        return change_answer(out) if change_answer else out

    monkeypatch.setattr(BridgeEngine, "analyze", patched)


@pytest.mark.parametrize("config", CONFIGS)
def test_state_left_unchanged_is_not_correct(config, monkeypatch, analyze):
    # the failure set never reaches the graph
    _faulty(monkeypatch, analyze, change_delete=lambda d: None)
    assert not run(CONFIGS[config], mix("whatif"))["correct"]


@pytest.mark.parametrize("config", CONFIGS)
def test_half_the_failure_set_left_out_is_not_correct(config, monkeypatch,
                                                      analyze):
    _faulty(monkeypatch, analyze,
            change_delete=lambda d: (d[0][: len(d[0]) // 2],
                                     d[1][: len(d[1]) // 2]))
    assert not run(CONFIGS[config], mix("whatif"))["correct"]


@pytest.mark.parametrize("config", CONFIGS)
def test_altered_answer_is_not_correct(config, monkeypatch, analyze):
    _faulty(monkeypatch, analyze, change_answer=alter)
    assert not run(CONFIGS[config], mix("whatif"))["correct"]
