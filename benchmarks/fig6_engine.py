"""Fig 6 (beyond-paper): BridgeEngine serving throughput.

Four operating points on the SAME query distribution (random planted-bridge
graphs whose sizes land in one power-of-two shape bucket):

  * cold_compile — a fresh engine's first query: trace + XLA compile + run.
  * cached       — second-and-later queries: the bucketed program is reused,
                   zero retrace (asserted via the engine's trace counter).
  * batched      — B queries resolved in one vmapped device dispatch;
                   reported per query.
  * incremental  — an edge delta folded into the live certificate by the
                   warm-start merge + final stage only; reported per update.
  * decremental  — a batch of link failures tombstoned out of the live
                   buffer; certificate untouched unless a certificate edge
                   died (DESIGN.md §Decremental); reported per update.

This is the amortization story the engine exists for: compile cost is paid
once per bucket, dispatch cost once per batch, certificate cost once per
live graph.

The closing ``fig6/engine_cache`` record pins the program-cache counters
(programs/misses/traces) for this fixed operating sequence — they are
deterministic, so ``scripts/check_bench.py`` compares them EXACTLY against
``BENCH_baseline.json`` and a compile-cache regression (an unexpected
retrace) fails CI. The ``fig6/hybrid_*`` records extend the sequence with
the hybrid certificate on the live substrate (materialize + cached cuts +
deletions) and pin the counters again (``fig6/hybrid_cache``).
"""
from __future__ import annotations

import itertools
import time

from benchmarks.common import csv_row, timeit
from repro.engine import BridgeEngine
from repro.graph import generators as gen
from repro.obs import get_tracer


def run(out, smoke: bool = False):
    v, e, b = (96, 800, 4) if smoke else (192, 3000, 8)
    # sized so the insert phase never outgrows the full-buffer bucket: the
    # timed sequence stays same-bucket churn (the no-retrace serving case)
    n_deltas = 48

    def query(seed):
        # host/datagen is a stage span so the --trace coverage check can
        # account for generation time (free no-op when tracing is off)
        with get_tracer().span("host/datagen", seed=seed):
            n = v - (seed % 7)  # jitter inside the bucket
            src, dst, _ = gen.planted_bridge_graph(n, e, n_bridges=3,
                                                   seed=seed)
            return src, dst, n

    engine = BridgeEngine()

    # cold: first query pays trace + compile + run
    s0, d0, n0 = query(0)
    t0 = time.perf_counter()
    engine.find_bridges(s0, d0, n0)
    t_cold = time.perf_counter() - t0
    out.append(csv_row("fig6/cold_compile", t_cold, f"V={v} E={e}"))

    # cached: same bucket, different graph — no retrace
    s1, d1, n1 = query(1)
    traces_before = engine.stats.traces
    t_cached = timeit(lambda: engine.find_bridges(s1, d1, n1))
    assert engine.stats.traces == traces_before, "engine retraced on a cache hit"
    out.append(csv_row(
        "fig6/cached", t_cached,
        f"V={v} E={e} speedup_vs_cold={t_cold / max(t_cached, 1e-9):.0f}x"))

    # batched: B queries in one dispatch, reported per query
    batch = [query(2 + i) for i in range(b)]
    gs = [(s, d) for s, d, _ in batch]
    ns = [n for _, _, n in batch]
    t_batch = timeit(lambda: engine.find_bridges_batch(gs, ns)) / b
    out.append(csv_row(
        "fig6/batched_per_query", t_batch,
        f"B={b} speedup_vs_single={t_cached / max(t_batch, 1e-9):.1f}x"))

    # incremental: delta insert into the live certificate vs full recompute.
    # Each timed call gets a FRESH delta: re-inserting the same edges is a
    # no-op for the warm-start merge and would flatter the number.
    engine.load(s0, d0, n0)
    with get_tracer().span("host/datagen", what="deltas"):
        delta_list = [gen.random_graph(n0, n_deltas, seed=99 + k)
                      for k in range(8)]
    deltas = iter(delta_list)
    t_inc = timeit(lambda: engine.insert_edges(*next(deltas)))
    out.append(csv_row(
        "fig6/incremental_update", t_inc,
        f"delta={n_deltas} speedup_vs_full={t_cached / max(t_inc, 1e-9):.1f}x "
        f"cert_edges={engine.num_live_edges}"))

    # decremental: fail a batch of just-inserted links per timed call. Random
    # edges of a dense graph are rarely certificate edges, so the common case
    # is the tombstone-only path; the derived column records how many of the
    # timed deletions did force a certificate rebuild.
    n_keys = 16
    dels = iter((s[:n_keys], d[:n_keys]) for s, d in delta_list)
    t_del = timeit(lambda: engine.delete_edges(*next(dels)))
    out.append(csv_row(
        "fig6/decremental_update", t_del,
        f"keys={n_keys} rebuilds={sum(engine.live_rebuilds.values())} "
        f"speedup_vs_full={t_cached / max(t_del, 1e-9):.1f}x"))

    # pinned compile-once counters for the whole fixed sequence above —
    # read off the ONE engine rollup (BridgeEngine.snapshot), same keys
    # and values as the pre-split cache_info, so the baseline is unchanged
    info = engine.snapshot()
    out.append(csv_row(
        "fig6/engine_cache", 0.0,
        f"programs={info['programs']} misses={info['misses']} "
        f"traces={info['traces']}"))

    # hybrid certificate on the live substrate: the first cuts query with
    # certificate='hybrid' materializes the pair from the live full buffer
    # (one load program), then serving is final-stage-only; deletions probe
    # it like any other live certificate. Keys come from the delta-list
    # tail, cycled so the phase survives any timeit call count; everything
    # is seed-deterministic, so the rebuild count and the pinned
    # fig6/hybrid_cache counters stay baseline-stable either way.
    engine.current_analysis("cuts", certificate="hybrid")
    t_hyb = timeit(
        lambda: engine.current_analysis("cuts", certificate="hybrid"))
    dels2 = itertools.cycle((s[:n_keys], d[:n_keys])
                            for s, d in delta_list[4:])
    t_hdel = timeit(lambda: engine.delete_edges(*next(dels2), kind="cuts",
                                                certificate="hybrid"))
    out.append(csv_row(
        "fig6/hybrid_cuts_cached", t_hyb,
        f"V={v} E={e} rebuilds={engine.live_rebuilds.get('hybrid', 0)}"))
    out.append(csv_row(
        "fig6/hybrid_delete", t_hdel,
        f"keys={n_keys} rebuilds={sum(engine.live_rebuilds.values())}"))
    # pinned counters again: the hybrid phase must add exactly its load +
    # cuts-final programs and reuse every probe/tombstone program
    info = engine.snapshot()
    out.append(csv_row(
        "fig6/hybrid_cache", 0.0,
        f"programs={info['programs']} misses={info['misses']} "
        f"traces={info['traces']}"))

    return out
