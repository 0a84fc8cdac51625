"""The trace reduction, the percentile and due-time arithmetic, and the
byte model, on hand-built inputs (CPU only; no chip is described or
touched)."""
from __future__ import annotations

import inspect
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import kernel_bytes, stats, tracefile  # noqa: E402
from bench.tracefile import Event, Trace  # noqa: E402

MS = 1_000_000  # ns


def ev(start_ms, end_ms, name="fusion", scope="", body=""):
    return Event(int(start_ms * MS), int(end_ms * MS), name, scope, body)


def small_trace() -> Trace:
    """Two devices in a 100 ms window. Device 0: a forest loop (body1)
    of two rounds, each a scatter and a gather, then a hooking op; a second
    forest loop (body2) of one round; a loop event that encloses the first
    round; a final-stage op; an op that starts before the window."""
    r = "jit(run)/stage/certificate_build/2ec/while/body/kernel/round/boruvka"
    b = "jit(run)/stage/certificate_build/2ec/while/body"
    d0 = [
        ev(-5, 5, "copy", "jit(run)/stage/tombstone"),
        ev(10, 30, "while", "jit(run)/stage/certificate_build/2ec/while"),
        ev(10, 15, "scatter", r, "body1"),
        ev(15, 16, "copy-start", "", "body1"),
        ev(16, 20, "gather", r, "body1"),
        ev(20, 22, "hook", b, "body1"),
        ev(22, 24, "scatter", r, "body1"),
        ev(24, 25, "gather", r, "body1"),
        ev(25, 30, "hook", b, "body1"),
        ev(40, 50, "scatter.2", r, "body2"),
        ev(70, 80, "euler", "jit(run)/stage/final/bridges/tour"),
    ]
    d1 = [ev(0, 50, "all", "jit(run)/merge/phase0")]
    host = [
        ev(0, 100, tracefile.WINDOW_SPAN),
        ev(0, 60, "bench/whatif"),
        ev(30, 40, "stage/pad"),
        ev(55, 100, "bench/wait_arrival"),
    ]
    return Trace([d0, d1], host, (0, 100 * MS))


def test_busy_is_the_union_clipped_to_the_window():
    t = small_trace()
    # device 0: [0,5] + [10,30] + [40,50] + [70,80] = 45 ms; device 1: 50
    assert t.busy_s() == pytest.approx((45 + 50) / 2 / 1e3)
    assert t.window_s == pytest.approx(0.1)


def test_scope_time_counts_nested_events_once():
    t = small_trace()
    assert t.scope_s(["kernel/round/"], reduce=lambda v: v[0]) == \
        pytest.approx(0.022)
    assert t.scope_s(["stage/certificate_build/"],
                     reduce=lambda v: v[0]) == pytest.approx(0.030)
    assert t.scope_s(["merge/phase"]) == pytest.approx(0.050)  # slowest
    assert t.scope_s(["stage/final/", "stage/tombstone"],
                     reduce=lambda v: v[0]) == pytest.approx(0.015)


def test_rounds_are_loop_body_executions():
    t = small_trace()
    assert t.iterations(["kernel/round/boruvka"]) == 3  # 2 + 1
    assert t.iterations(["stage/final/"]) == 1
    assert t.iterations(["kernel/round/sfs"]) == 0


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = small_trace().idle_gaps(10)
    # device 0 idles in [5,10], [30,40], [50,70] and [80,100] ms
    assert gaps == [
        ["bench/wait_arrival", pytest.approx(0.020)],
        ["bench/wait_arrival", pytest.approx(0.020)],
        ["stage/pad", pytest.approx(0.010)],
        ["bench/whatif", pytest.approx(0.005)],
    ]


def test_top_ops_carry_their_scope():
    top = dict((k, v) for k, v in small_trace().top_ops(10))
    assert top["merge/phase0:all"] == pytest.approx(0.025)
    assert top["stage/certificate_build/2ec/while/body/kernel/round/"
               "boruvka:scatter"] == pytest.approx(0.0035)
    assert top["stage/tombstone:copy"] == pytest.approx(0.0025)


def test_read_finds_the_window_in_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    def fn(x):
        with jax.named_scope("stage/final/bridges"):
            return jnp.sin(x) @ x

    f = jax.jit(fn)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tracefile.capture(tmp_path):
        with jax.profiler.TraceAnnotation(tracefile.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench/whatif"):
                f(x).block_until_ready()
    t = tracefile.read(tmp_path)
    # the op metadata of the program's HLO carries the named scope
    raw = next(tmp_path.rglob("*.xplane.pb")).read_bytes()
    scopes = tracefile.hlo_scopes(raw)
    fn_ops = [ops for prog, ops in scopes.items() if prog.startswith("jit_fn")]
    assert fn_ops and any("stage/final/bridges" in op_name
                          for op_name, _ in fn_ops[0].values())
    names = {h.name for h in t.host}
    assert {"bench/whatif", tracefile.WINDOW_SPAN} <= names
    assert t.window_s > 0
    assert t.devices == []  # a CPU profile has no device plane


def test_percentile_is_an_exact_nearest_rank_sample():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([3.0, 1.0, 2.0], 95) == 3.0
    assert stats.percentile([7.5], 50) == 7.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latency_is_timed_from_the_due_time():
    # the second request was due at 1.0 but waited behind the first
    due = [0.0, 1.0, 2.0]
    done = [1.5, 2.5, 2.1]
    assert stats.latencies_from_due(due, done) == \
        pytest.approx([1.5, 1.5, 0.1])


def test_round_bytes_depend_on_shapes_alone():
    # no implementation switch (use_pallas or other): the count is the
    # algorithm's, whatever computes the round
    params = inspect.signature(kernel_bytes.boruvka_round_bytes).parameters
    assert list(params) == ["edge_slots", "n_vertices"]
    assert kernel_bytes.boruvka_round_bytes(1 << 24, 1 << 17) == \
        17 * (1 << 24) + 4 * (1 << 17)
    assert kernel_bytes.bucket(10_000_000) == 1 << 24
    assert kernel_bytes.bucket(100_000) == 1 << 17
    assert kernel_bytes.bucket(3) == 16


def test_round_bytes_match_the_engine_buckets():
    from repro.graph.datastructs import admission_capacity

    for m in (1, 17, 1000, 100_000, 10_000_000, 1 << 24):
        assert kernel_bytes.bucket(m) == admission_capacity(m)
