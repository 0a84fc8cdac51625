"""Fig 7 (beyond-paper): connectivity-subsystem serving throughput.

For each failure-point query kind served by the BridgeEngine — cuts
(articulation points), 2ecc (component labels), bridge_tree, bcc
(biconnected blocks) — three operating points on the same jittered
planted-bridge query distribution as fig6:

  * cold  — a fresh shape bucket's first query: trace + XLA compile + run.
  * cached — second-and-later queries: zero retrace (asserted).
  * batched — B queries in one vmapped dispatch, reported per query.

Plus the host Tarjan articulation-point reference on the same graph, so
the device-vs-host crossover for the new query family is tracked next to
fig5's bridges baseline. Sanity: every timed engine result is checked once
against the planted ground truth of a failure scenario.

The closing ``fig7/path_world_rounds`` record tracks the hybrid
certificate's reason to exist: on an n=1024 path world the plain SFS pair
pays one BFS round per vertex, while the hybrid contracts the chain first
and scans a constant-diameter graph. The round counters are deterministic
and pinned exactly by ``scripts/check_bench.py`` against the committed
baseline, with the ≥4× bound asserted inline.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import csv_row, timeit
from repro.connectivity.host import articulation_points_dfs
from repro.core.certificate import hybrid_certificate_ex, sfs_certificate_ex
from repro.engine import BridgeEngine
from repro.graph import generators as gen
from repro.graph.datastructs import EdgeList

KINDS = ("cuts", "2ecc", "bridge_tree", "bcc")

#: path-world size for the round-count record (acceptance: n >= 1024)
PATH_N = 1024


def run(out, smoke: bool = False):
    v, e, b = (96, 800, 4) if smoke else (192, 3000, 8)

    def query(seed):
        n = v - (seed % 7)  # jitter inside the bucket
        src, dst, _ = gen.planted_bridge_graph(n, e, n_bridges=3, seed=seed)
        return src, dst, n

    engine = BridgeEngine()

    # planted-scenario sanity: the engine must reproduce the ground truth
    sc = gen.chain_of_cliques(3, 4)
    assert engine.find_cuts(sc["src"], sc["dst"], sc["n"]) == sc["cuts"]
    assert (len(np.unique(engine.find_two_ecc(sc["src"], sc["dst"], sc["n"])))
            == sc["n_2ecc"])

    s0, d0, n0 = query(0)
    s1, d1, n1 = query(1)
    cached = {}
    for kind in KINDS:
        t0 = time.perf_counter()
        engine.analyze(s0, d0, n0, kind=kind)
        t_cold = time.perf_counter() - t0
        out.append(csv_row(f"fig7/{kind}_cold", t_cold, f"V={v} E={e}"))

        traces_before = engine.stats.traces
        t_cached = timeit(lambda: engine.analyze(s1, d1, n1, kind=kind))
        assert engine.stats.traces == traces_before, \
            f"engine retraced {kind} on a cache hit"
        cached[kind] = t_cached
        out.append(csv_row(
            f"fig7/{kind}_cached", t_cached,
            f"V={v} E={e} speedup_vs_cold={t_cold / max(t_cached, 1e-9):.0f}x"))

        batch = [query(2 + i) for i in range(b)]
        gs = [(s, d) for s, d, _ in batch]
        ns = [n for _, _, n in batch]
        t_batch = timeit(
            lambda: engine.analyze_batch(gs, ns, kind=kind)) / b
        out.append(csv_row(
            f"fig7/{kind}_batched_per_query", t_batch,
            f"B={b} speedup_vs_single={t_cached / max(t_batch, 1e-9):.1f}x"))

    # host Tarjan reference for the new family (cuts is the representative:
    # same DFS skeleton as 2ecc/bridge-tree, no device dispatch)
    t_host = timeit(lambda: articulation_points_dfs(s1, d1, n1))
    out.append(csv_row("fig7/host_tarjan_cuts", t_host,
                       f"V={v} E={e} vs_device="
                       f"{t_host / max(cached['cuts'], 1e-9):.1f}x"))

    # path world: SFS vs hybrid BFS-round counts (both deterministic; the
    # check_bench gate pins them exactly, the assert enforces the bound)
    ps = np.arange(PATH_N - 1, dtype=np.int32)
    el = EdgeList.from_arrays(ps, ps + 1, PATH_N)
    _, _, _, (sr1, sr2), _ = sfs_certificate_ex(el)
    sfs_rounds = int(sr1) + int(sr2)
    t_hyb = timeit(lambda: hybrid_certificate_ex(el))
    _, (hr0, hr1, hr2), _ = hybrid_certificate_ex(el)
    hybrid_rounds = int(hr1) + int(hr2)
    assert hybrid_rounds * 4 <= sfs_rounds, \
        f"hybrid rounds {hybrid_rounds} not >=4x under sfs {sfs_rounds}"
    out.append(csv_row(
        "fig7/path_world_rounds", t_hyb,
        f"V={PATH_N} sfs_rounds={sfs_rounds} hybrid_rounds={hybrid_rounds} "
        f"chain_rounds={int(hr0)}"))
    return out
