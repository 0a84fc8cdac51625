"""Merge-schedule equivalence: ``paper``, ``xor``, and ``hierarchical`` must
produce identical results — for EVERY analysis-registry kind, not just
bridges.

Certificate union is associative, commutative, and idempotent (for both the
2-edge Borůvka pair and the scan-first-search pair), so every schedule
computes an equivalent final certificate. ``core.merge.simulate_merge_host``
drives the REAL phase-permutation logic (``merge._phase_perm``) and the real
per-phase certify step machine-by-machine on host — no collectives — so the
equivalence property is testable in a single-device environment. The
end-to-end shard_map version runs too when this jax build supports it.
"""
import jax
import numpy as np
import pytest

from repro.connectivity.registry import ANALYSIS_KINDS, get_analysis
from repro.core.bridges_host import bridges_from_edgelist
from repro.core.certificate import certificate_capacity
from repro.core.certs import certificate_builder
from repro.core.merge import simulate_merge_host
from repro.core.partition import partition_edges
from repro.engine import BridgeEngine, make_analysis_fn
from repro.graph import generators as gen
from repro.graph.datastructs import EdgeList

from helpers import nx_bridges

M, GRID = 8, (2, 4)

# one engine so the single-device reference programs compile once
ENGINE = BridgeEngine()


def _local_certs(src, dst, n, certify, seed=0):
    psrc, pdst, pmask = partition_edges(src, dst, n, M, seed=seed)
    cap = certificate_capacity(n)
    return [
        certify(EdgeList(psrc[i], pdst[i], pmask[i], n), capacity=cap)
        for i in range(M)
    ]


CASES = [
    ("planted", lambda: gen.planted_bridge_graph(96, 2000, 4, seed=5)[:2] + (96,)),
    ("barbell", lambda: gen.barbell(10, 5)[:2] + (gen.barbell(10, 5)[3],)),
]


@pytest.mark.parametrize("name,make", CASES, ids=[c[0] for c in CASES])
def test_three_schedules_identical_bridges(name, make):
    src, dst, n = make()
    want = nx_bridges(src, dst, n)
    certify = certificate_builder("2ec")
    results = {}
    for schedule in ("paper", "xor", "hierarchical"):
        certs = simulate_merge_host(
            _local_certs(src, dst, n, certify), schedule, certify=certify,
            grid=GRID)
        # paper: machine 0 answers; xor/hierarchical: every machine answers
        answer_on = [0] if schedule == "paper" else range(len(certs))
        got = {i: bridges_from_edgelist(certs[i]) for i in answer_on}
        assert all(g == want for g in got.values()), (schedule, name)
        results[schedule] = got[0]
    assert results["paper"] == results["xor"] == results["hierarchical"]


@pytest.mark.parametrize("kind", ANALYSIS_KINDS)
def test_distributed_kind_matches_single_device_all_schedules(kind):
    """Acceptance: for every registry kind, the distributed path (the
    kind's certificate type merged by the host-simulated schedules, then
    the kind's device final stage at the answering machine) produces
    results identical to the single-device engine path, under all three
    merge schedules."""
    analysis = get_analysis(kind)
    certify = certificate_builder(analysis.certificate)
    src, dst, n = CASES[0][1]()
    want = ENGINE.analyze(src, dst, n, kind=kind)
    final_fn = jax.jit(make_analysis_fn(n, kind, "device"))
    for schedule in ("paper", "xor", "hierarchical"):
        certs = simulate_merge_host(
            _local_certs(src, dst, n, certify), schedule, certify=certify,
            grid=GRID)
        answer_on = [0] if schedule == "paper" else [0, M - 1]
        for i in answer_on:
            c = certs[i]
            got = analysis.to_result(final_fn(c.src, c.dst, c.mask), n)
            if analysis.kind == "2ecc":
                assert np.array_equal(got, want), (kind, schedule, i)
            else:
                assert got == want, (kind, schedule, i)
        # final='host' substrate: the kind's sequential reference on the
        # answering machine's merged certificate
        s, d = certs[0].to_numpy()
        host_got = analysis.host_fn(s, d, n)
        if analysis.kind == "2ecc":
            assert np.array_equal(host_got, want), (kind, schedule)
        else:
            assert host_got == want, (kind, schedule)


def test_three_schedules_end_to_end_shard_map():
    """Full collective pipeline (subprocess with 8 forced host devices)."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import numpy as np
            import jax
            from jax.sharding import AxisType
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            from repro.core import find_bridges
            from repro.core.bridges_host import bridges_dfs
            from repro.engine import BridgeEngine
            from repro.connectivity.registry import ANALYSIS_KINDS, get_analysis
            from repro.graph import generators as gen
            for name, (src, dst, n) in {
                "planted": gen.planted_bridge_graph(96, 2000, 4, seed=5)[:2] + (96,),
                "barbell": gen.barbell(10, 5)[:2] + (gen.barbell(10, 5)[3],),
            }.items():
                want = bridges_dfs(src, dst, n)
                got = {s: find_bridges(src, dst, n, mesh=mesh,
                                       machine_axes=("data", "model"),
                                       schedule=s, final="device", seed=1)
                       for s in ("paper", "xor", "hierarchical")}
                assert got["paper"] == got["xor"] == got["hierarchical"] == want, name
            # every registry kind through the distributed engine path
            eng_single = BridgeEngine()
            eng = BridgeEngine(mesh=mesh, machine_axes=("data", "model"),
                              schedule="xor")
            src, dst, n = gen.planted_bridge_graph(96, 2000, 4, seed=5)[:2] + (96,)
            for kind in ANALYSIS_KINDS:
                want = eng_single.analyze(src, dst, n, kind=kind)
                got = eng.analyze(src, dst, n, kind=kind, seed=1)
                same = (np.array_equal(got, want)
                        if get_analysis(kind).kind == "2ecc" else got == want)
                assert same, kind
            print("OK")
        """)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
