"""The reader of the certificate's hooking time (``cert_hook_ms.whatif``) on
hand-built traces, and the what-if cell's readers on a traced run of the
road grid at a test size (CPU only; no chip is described or touched)."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, tracefile  # noqa: E402
from bench.tracefile import Event, Trace  # noqa: E402
from faults import mix  # noqa: E402

MS = 1_000_000  # ns
CERT = "jit(one)/stage/certificate_build/2ec"
FINAL = "jit(one)/stage/final/bridges"
BODY = "jit(_forest_impl)/while/body"


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read


def run_of(trace, n_ops=2, ok=True):
    ops = [harness.Op("whatif", 0.0, 1.0, ok) for _ in range(n_ops)]
    return harness.Run(None, ops, (0.0, 1.0), 0.0, None, trace)


def ev(start_ms, end_ms, scope, body=""):
    return Event(int(start_ms * MS), int(end_ms * MS), "fusion", scope, body)


def hook_trace(with_hook=True) -> Trace:
    """Two requests: rounds and hooks in both forests (one hook operation
    named by the loop's own name stack alone, in the certificate's loop
    body), a hook in the final stage's tour forest, also one bare-named
    there (neither the certificate's), the compaction."""
    hook = "/kernel/hook/boruvka" if with_hook else ""
    cert_body, tour_body = "jit_one/wide.region_2", "jit_one/wide.region_28"
    d0 = []
    for t in (0, 50):
        d0 += [
            ev(t + 0, t + 10, f"{CERT}/cert/forest1/{BODY}/kernel/round/"
               "boruvka/scatter-min", cert_body),
            ev(t + 10, t + 14, f"{CERT}/cert/forest1/{BODY}{hook}/gather",
               cert_body),
            ev(t + 14, t + 16, f"while/body{hook}/gather", cert_body),
            ev(t + 16, t + 24, f"{CERT}/cert/forest2/{BODY}/kernel/round/"
               "boruvka/scatter-min", cert_body),
            ev(t + 24, t + 28, f"{CERT}/cert/forest2/{BODY}{hook}/gather",
               cert_body),
            ev(t + 28, t + 30, f"{CERT}/cert/compact/scatter"),
            ev(t + 30, t + 38, f"{FINAL}/tour/forest/{BODY}{hook}/gather",
               tour_body),
            ev(t + 38, t + 40, f"while/body{hook}/gather", tour_body),
        ]
    return Trace([d0], [], (0, 100 * MS))


def test_hook_time_is_certificate_hook_time_per_request():
    # (4 + 2 + 4) ms of the certificate's hooking in each of two requests;
    # the tour forest's hook is the final stage's
    assert reader("cert_hook_ms.whatif")(run_of(hook_trace())) == \
        pytest.approx(10.0)
    assert reader("cert_hook_ms.whatif")(run_of(hook_trace(), n_ops=4)) == \
        pytest.approx(5.0)


def test_hook_reader_is_silent_without_the_scope_or_the_trace():
    """The parent has no hook scope: no number, and nothing raised."""
    assert reader("cert_hook_ms.whatif")(run_of(hook_trace(False))) is None
    assert reader("cert_hook_ms.whatif")(run_of(None)) is None
    assert reader("cert_hook_ms.whatif")(run_of(hook_trace(), ok=False)) \
        is None


def test_traced_grid_run_reads_the_rounds():
    """A ``--trace 1`` what-if run of the road grid at a test size through
    the harness: answers correct, the certificate's rounds counted (more
    full-width ones than on the dense shape), and no device time to read on
    the CPU."""
    names = ["cert_rounds.whatif", "cert_full_rounds.whatif",
             "cert_hook_ms.whatif"]
    read = tracefile.read

    def one_device(directory):
        # a CPU profile has no device plane: stand in one device that ran
        # nothing, as the harness's reduction expects one
        t = read(directory)
        t.devices = t.devices or [[]]
        return t

    got = {}
    tracefile.read = one_device
    try:
        for shape, config in (
                ("grid", {"generator": "grid", "side": 48, "tiles": 4}),
                ("dense", {"generator": "planted", "n_nodes": 300,
                           "n_edges": 4000, "n_bridges": 5})):
            cell = harness.Cell("test", 1, config,
                                mix("whatif", check_answers=1), [],
                                [{"name": n, "unit": "x"} for n in names])
            line = harness.run_cell(cell, 2**31 + 43, 1.0, True,
                                    t_process=time.perf_counter(),
                                    log=lambda *a: None)
            assert line["correct"], (shape, line["checks"])
            got[shape] = line["metrics"]
    finally:
        tracefile.read = read
    full = {k: v["cert_full_rounds.whatif"]["value"] for k, v in got.items()}
    assert full["grid"] > full["dense"] > 0
    assert got["grid"]["cert_rounds.whatif"]["value"] >= full["grid"]
    assert "cert_hook_ms.whatif" not in got["grid"]
