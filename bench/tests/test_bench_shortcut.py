"""The reader of the certificate's pointer-doubling steps
(``cert_shortcut_steps.whatif``) on hand-built traces (CPU only; no chip is
described or touched)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from bench.tracefile import Event, Trace  # noqa: E402

MS = 1_000_000  # ns
CERT = "jit(one)/stage/certificate_build/2ec"
FINAL = "jit(one)/stage/final/bridges"
HOOK = "jit(_forest_impl)/while/body/kernel/hook/boruvka"
SHORTCUT = "kernel/hook/shortcut/while"
PARENT = "while"  # the fixed-step loop: no shortcut scope


def reader():
    path = harness.BENCH / "metrics" / "cert_shortcut_steps.whatif.py"
    return harness.load_module(path).read


def run_of(trace, n_ops=2, ok=True):
    ops = [harness.Op("whatif", 0.0, 1.0, ok) for _ in range(n_ops)]
    return harness.Run(None, ops, (0.0, 1.0), 0.0, None, trace)


def shortcut_loop(t, stage, steps, body, loop=SHORTCUT):
    """One shortcut loop of ``steps`` iterations from ``t`` ms: per step a
    gather named under ``stage``, one named by the loop's own name stack
    alone, and the convergence test; the condition once more than the
    body, in a computation of its own."""
    out = []
    for i in range(steps):
        s = t + i
        out += [
            Event(s * MS, s * MS + MS // 4, "fusion.1",
                  f"{stage}/{HOOK}/{loop}/body/gather", body),
            Event(s * MS + MS // 4, s * MS + MS // 2, "fusion.2",
                  f"while/body/kernel/hook/boruvka/{loop}/body/gather", body),
            Event(s * MS + MS // 2, s * MS + 3 * MS // 4, "fusion.3",
                  f"{stage}/{HOOK}/{loop}/body/reduce_and", body),
        ]
    out += [Event((t + i) * MS + 3 * MS // 4, (t + i) * MS + 4 * MS // 5,
                  "lt.1", f"{stage}/{HOOK}/{loop}/cond/lt", body + ".cond")
            for i in range(steps + 1)]
    return out


def shortcut_trace(loop=SHORTCUT) -> Trace:
    """Two requests: in each, a 4-step shortcut in the first forest and a
    3-step one in the second (each forest's loop body a computation of its
    own), and a 5-step one in the final stage's tour forest."""
    d0 = []
    for t in (0, 50):
        d0 += shortcut_loop(t, f"{CERT}/cert/forest1", 4,
                            "jit_one/wide.region_6.10.clone", loop)
        d0 += shortcut_loop(t + 10, f"{CERT}/cert/forest2", 3,
                            "jit_one/wide.region_6.10.clone.clone", loop)
        d0 += shortcut_loop(t + 20, f"{FINAL}/tour/forest", 5,
                            "jit_one/wide.region_31.47.clone", loop)
    return Trace([d0], [], (0, 100 * MS))


def test_shortcut_steps_are_the_certificates_loop_bodies_per_request():
    # (4 + 3) steps of the certificate in each of two requests; the tour
    # forest's five and every loop's condition are left out
    assert reader()(run_of(shortcut_trace())) == pytest.approx(7.0)
    assert reader()(run_of(shortcut_trace(), n_ops=7)) == pytest.approx(2.0)


def test_shortcut_reader_is_silent_without_the_scope_or_the_trace():
    """The parent's fixed-step loop has no shortcut scope: no number, and
    nothing raised."""
    assert reader()(run_of(shortcut_trace(PARENT))) is None
    assert reader()(run_of(None)) is None
    assert reader()(run_of(shortcut_trace(), ok=False)) is None
