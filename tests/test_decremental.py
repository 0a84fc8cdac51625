"""Decremental serving (DESIGN.md §Decremental): ``delete_edges`` on the
live engine state, one-shot ``delete=`` on the single/batched/distributed
substrates, the tombstone + certificate-hit rebuild rule, and the
``scripts/check_bench.py`` CI bench-regression gate.

Correctness oracle throughout: host recompute of the kind's sequential
reference on the tracked live edge multiset (deletion removes ALL copies of
an unordered endpoint pair — a pair names a link).

Shapes are pinned to one bucket family (n=48 -> n_bucket 64, base edges ->
cap 256, deltas/keys -> bucket 16) and one module-level engine is shared,
so the whole module compiles each program once (1-core CI box).
"""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.connectivity.registry import ANALYSIS_KINDS, get_analysis
from repro.core.certificate import certificate_capacity
from repro.core.certs import certificate_builder
from repro.core.merge import simulate_churn_host, simulate_merge_host
from repro.core.partition import partition_edges
from repro.engine import BatchedEdgeList, BridgeEngine
from repro.graph import generators as gen
from repro.graph.datastructs import EdgeList

from _hyp import given, st

N, E0 = 48, 150          # n_bucket 64, full-buffer bucket 256
DELTA = 12               # insert/delete batch sizes land in key bucket 16

ENGINE = BridgeEngine()


# ------------------------------------------------------------------ helpers
def _host(kind, pairs, n=N):
    a = get_analysis(kind)
    s = np.array([x for x, _ in pairs], np.int32)
    d = np.array([y for _, y in pairs], np.int32)
    return a.host_fn(s, d, n)


def _same(kind, got, want):
    if get_analysis(kind).kind == "2ecc":
        return np.array_equal(np.asarray(got), np.asarray(want))
    return got == want


def _keys(pairs):
    return (np.array([x for x, _ in pairs], np.int32),
            np.array([y for _, y in pairs], np.int32))


def _drop(pairs, dels):
    """Host mirror of delete_edges: remove ALL copies of the keyed pairs."""
    kset = set((min(x, y), max(x, y)) for x, y in dels)
    return [(x, y) for x, y in pairs if (min(x, y), max(x, y)) not in kset]


def _base(seed=1):
    s, d = gen.random_graph(N, E0, seed=seed)
    return s, d, list(zip(s.tolist(), d.tolist()))


def _cert_pairs(eng, name="2ec"):
    cs, cd, cm = (np.asarray(x) for x in eng._live["certs"][name][:3])
    return list(zip(cs[cm].tolist(), cd[cm].tolist()))


# ------------------------------------------------------------- live serving
def test_delete_bridge_edge_rebuilds_and_answers():
    src = np.array([0, 1, 2, 3], np.int32)
    dst = np.array([1, 2, 3, 0], np.int32)
    eng = ENGINE.load(src, dst, N)
    assert eng.current_bridges() == set()
    got = eng.delete_edges([0], [1])
    assert got == {(1, 2), (2, 3), (0, 3)}  # cycle minus an edge is a path
    assert eng.live_rebuilds["2ec"] == 1    # every cycle edge is in the cert
    assert eng.num_live_graph_edges == 3
    # insert the failed link back: cycle again, no bridges
    assert eng.insert_edges([1], [0]) == set()


def test_noncertificate_deletion_is_free():
    """The certificate-hit rule's payoff: deleting an edge outside both
    certificate pairs leaves them untouched (no rebuild) and still answers
    correctly — the common case on dense graphs (cert <= 2(n-1) << E)."""
    s, d, pairs = _base()
    eng = ENGINE.load(s, d, N)
    certset = set((min(x, y), max(x, y)) for x, y in _cert_pairs(eng))
    eng.current_analysis("cuts")  # materialize the SFS pair too
    ss, sd, sm = (np.asarray(x) for x in eng._live["certs"]["sfs"][:3])
    certset |= set((min(int(a), int(b)), max(int(a), int(b)))
                   for a, b in zip(ss[sm], sd[sm]))
    noncert = [p for p in pairs
               if (min(p), max(p)) not in certset][:DELTA]
    assert noncert, "dense base graph must have non-certificate edges"
    got = eng.delete_edges(*_keys(noncert), kind="bridges")
    assert eng.live_rebuilds == {"2ec": 0, "sfs": 0}
    live = _drop(pairs, noncert)
    assert got == _host("bridges", live)
    assert _same("cuts", eng.current_analysis("cuts"), _host("cuts", live))


@pytest.mark.parametrize("kind", ANALYSIS_KINDS)
def test_certificate_hit_delete_matches_host(kind):
    """Deleting certificate edges forces the rebuild path; the rebuilt
    state must answer every kind exactly like a host recompute."""
    s, d, pairs = _base()
    eng = ENGINE.load(s, d, N)
    dels = _cert_pairs(eng)[:3]
    got = eng.delete_edges(*_keys(dels), kind=kind)
    assert eng.live_rebuilds["2ec"] == 1
    assert _same(kind, got, _host(kind, _drop(pairs, dels))), kind


def test_interleaved_churn_all_kinds_no_retrace_after_warmup():
    """Acceptance: arbitrary interleaved insert/delete sequences serve
    every kind correctly, and same-bucket churn causes ZERO retraces once
    the deletion/insertion programs are warm."""
    s, d, live = _base(seed=3)
    eng = ENGINE.load(s, d, N)
    rng = np.random.default_rng(7)

    def insert(seed):
        ds, dd = gen.random_graph(N, DELTA, seed=seed)
        out = eng.insert_edges(ds, dd)
        live.extend(zip(ds.tolist(), dd.tolist()))
        return out

    def delete():
        pick = [live[i] for i in rng.choice(len(live), 5, replace=False)]
        out = eng.delete_edges(*_keys(pick))
        live[:] = _drop(live, pick)
        return out

    # warm-up: materialize SFS and every kind's final-stage program, then
    # compile insert/append/delete/rebuild programs for this bucket family
    # (insert twice: the SFS fold-in only exists once the SFS pair is
    # live). Warms ALL kinds so this test is order-independent.
    for kind in ANALYSIS_KINDS:
        eng.current_analysis(kind)
    insert(100)
    delete()
    insert(101)
    traces = eng.stats.traces
    for step in range(6):
        got = delete() if rng.random() < 0.5 else insert(200 + step)
        assert got == _host("bridges", live), step
        for kind in ANALYSIS_KINDS:
            assert _same(kind, eng.current_analysis(kind),
                         _host(kind, live)), (step, kind)
    assert eng.stats.traces == traces, "same-bucket churn retraced"
    assert eng.num_live_graph_edges == len(live)


@given(st.integers(0, 2 ** 31 - 1), st.sampled_from(ANALYSIS_KINDS),
       st.lists(st.booleans(), min_size=1, max_size=4))
def test_churn_property_matches_host(seed, kind, is_delete):
    """Property: any interleaved insert/delete sequence matches the host
    recompute for any kind (shapes pinned to the module's bucket family
    so hypothesis examples reuse the compiled programs)."""
    rng = np.random.default_rng(seed)
    s, d, live = _base(seed=seed % 1000)
    eng = ENGINE.load(s, d, N)
    for i, dele in enumerate(is_delete):
        if dele and len(live) > DELTA:
            pick = [live[j] for j in
                    rng.choice(len(live), DELTA, replace=False)]
            got = eng.delete_edges(*_keys(pick), kind=kind)
            live = _drop(live, pick)
        else:
            ds, dd = gen.random_graph(N, DELTA, seed=seed + i)
            got = eng.insert_edges(ds, dd, kind=kind)
            live = live + list(zip(ds.tolist(), dd.tolist()))
        assert _same(kind, got, _host(kind, live)), (i, kind)


def test_delete_requires_load_and_valid_kind():
    eng = BridgeEngine()
    with pytest.raises(RuntimeError, match="load"):
        eng.delete_edges([0], [1])
    s, d, _ = _base()
    with pytest.raises(ValueError, match="unknown analysis kind"):
        ENGINE.load(s, d, N).delete_edges([0], [1], kind="nope")


def test_non_decremental_kind_refused():
    import dataclasses

    from repro.connectivity import registry

    frozen = dataclasses.replace(get_analysis("bridges"),
                                 kind="frozen_kind", decremental=False)
    registry.register(frozen)
    try:
        s, d, _ = _base()
        eng = ENGINE.load(s, d, N)
        with pytest.raises(NotImplementedError, match="decremental"):
            eng.delete_edges([0], [1], kind="frozen_kind")
    finally:
        registry._REGISTRY.pop("frozen_kind")


# ----------------------------------------------------- one-shot and batched
def test_one_shot_analyze_delete_all_kinds_cached():
    s, d, pairs = _base(seed=5)
    dels = pairs[::7][:10]
    live = _drop(pairs, dels)
    for kind in ANALYSIS_KINDS:
        got = ENGINE.analyze(s, d, N, kind=kind, delete=_keys(dels))
        assert _same(kind, got, _host(kind, live)), kind
    # same bucket family again: cached program, no retrace
    traces = ENGINE.stats.traces
    dels2 = pairs[1::7][:8]
    got = ENGINE.analyze(s, d, N, kind="bridges", delete=_keys(dels2))
    assert got == _host("bridges", _drop(pairs, dels2))
    assert ENGINE.stats.traces == traces


def test_batched_analyze_per_graph_deletions():
    graphs, deletes, lives = [], [], []
    for i in range(3):
        s, d, pairs = _base(seed=20 + i)
        graphs.append((s, d))
        if i == 1:
            deletes.append(None)  # mixed: this row has no failures
            lives.append(pairs)
        else:
            dels = pairs[::5][:8]
            deletes.append(_keys(dels))
            lives.append(_drop(pairs, dels))
    for kind in ("bridges", "cuts"):
        got = ENGINE.analyze_batch(graphs, N, kind=kind, delete=deletes)
        for i in range(3):
            assert _same(kind, got[i], _host(kind, lives[i])), (kind, i)
    with pytest.raises(ValueError, match="deletion lists"):
        ENGINE.analyze_batch(graphs, N, delete=deletes[:2])


def test_batched_edgelist_delete_edges():
    graphs = [_base(seed=30 + i)[:2] for i in range(2)]
    bel = BatchedEdgeList.from_graphs(graphs, N, capacity=256, batch_pad=2)
    pairs0 = list(zip(graphs[0][0].tolist(), graphs[0][1].tolist()))
    dels = pairs0[:5]
    out = bel.delete_edges([_keys(dels), None])
    sm = np.asarray(out.mask)
    got0 = set((min(int(a), int(b)), max(int(a), int(b)))
               for a, b in zip(np.asarray(out.src)[0][sm[0]],
                               np.asarray(out.dst)[0][sm[0]]))
    assert got0 == set((min(x, y), max(x, y)) for x, y in _drop(pairs0, dels))
    assert int(sm[1].sum()) == len(graphs[1][0])  # None row untouched
    with pytest.raises(ValueError, match="deletion lists"):
        bel.delete_edges([None, None, None])


# -------------------------------------------------------------- distributed
@pytest.mark.parametrize("certificate,kind", [("2ec", "bridges"),
                                              ("sfs", "cuts")])
@pytest.mark.parametrize("schedule", ["paper", "xor"])
def test_simulate_churn_host_matches_recompute(certificate, kind, schedule):
    """Distributed deletion rule (tombstone shard -> re-certify ->
    re-merge), host-simulated: the answering machine's merged certificate
    must answer exactly like a host recompute on the surviving edges."""
    s, d, pairs = _base(seed=9)
    dels = pairs[::6][:10]
    live = _drop(pairs, dels)
    m = 4
    psrc, pdst, pmask = partition_edges(s, d, N, m, seed=2)
    shards = [EdgeList(psrc[i], pdst[i], pmask[i], N) for i in range(m)]
    certify = certificate_builder(certificate)
    certs = simulate_churn_host(shards, *_keys(dels), schedule=schedule,
                                certify=certify)
    want = _host(kind, live)
    answer_on = [0] if schedule == "paper" else range(m)
    for i in answer_on:
        cs, cd = certs[i].to_numpy()
        assert _same(kind, get_analysis(kind).host_fn(cs, cd, N), want), i
    # sanity: deletion changed the certificate vs the no-deletion merge
    base = simulate_merge_host(
        [certify(sh, capacity=certificate_capacity(N)) for sh in shards],
        schedule, certify=certify)
    assert len(certs[0].to_numpy()[0]) <= len(base[0].to_numpy()[0])


def test_distributed_deletion_end_to_end_shard_map():
    """Engine analyze(delete=...) on a mesh == single-device analyze with
    the same deletions, every kind (subprocess with 4 forced host devs)."""
    import os
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import numpy as np
            import jax
            from jax.sharding import AxisType
            from repro.engine import BridgeEngine
            from repro.connectivity.registry import ANALYSIS_KINDS, get_analysis
            from repro.graph import generators as gen
            mesh = jax.make_mesh((4,), ("machines",),
                                 axis_types=(AxisType.Auto,))
            src, dst = gen.random_graph(48, 150, seed=1)
            pairs = list(zip(src.tolist(), dst.tolist()))
            dels = pairs[::7][:10]
            ks = np.array([x for x, _ in dels], np.int32)
            kd = np.array([y for _, y in dels], np.int32)
            single = BridgeEngine()
            dist = BridgeEngine(mesh=mesh, machine_axes=("machines",),
                                schedule="xor")
            for kind in ANALYSIS_KINDS:
                want = single.analyze(src, dst, 48, kind=kind,
                                      delete=(ks, kd))
                got = dist.analyze(src, dst, 48, kind=kind, seed=1,
                                   delete=(ks, kd))
                same = (np.array_equal(got, want)
                        if get_analysis(kind).kind == "2ecc"
                        else got == want)
                assert same, kind
            print("OK")
        """)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


# -------------------------------------------------------- check_bench gate
def _check_bench():
    spec = importlib.util.spec_from_file_location(
        "check_bench",
        Path(__file__).resolve().parent.parent / "scripts" / "check_bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_bench_passes_within_tolerance_and_exact_counters():
    cb = _check_bench()
    base = [{"name": "fig6/cached", "us_per_call": 100.0, "derived": "V=96"},
            {"name": "fig6/engine_cache", "us_per_call": 0.0,
             "derived": "programs=7 misses=7 traces=7"}]
    cur = [{"name": "fig6/cached", "us_per_call": 900.0, "derived": "V=96"},
           {"name": "fig6/engine_cache", "us_per_call": 0.0,
            "derived": "programs=7 misses=7 traces=7"}]
    assert cb.compare(base, cur, tolerance=50.0) == []
    # speedups never fail
    cur[0]["us_per_call"] = 0.1
    assert cb.compare(base, cur, tolerance=50.0) == []


def test_check_bench_fails_on_injected_retrace_regression():
    """Acceptance: an injected retrace (traces counter off by one) fails
    the gate even though every timing is within tolerance."""
    cb = _check_bench()
    base = [{"name": "fig6/engine_cache", "us_per_call": 0.0,
             "derived": "programs=7 misses=7 traces=7"}]
    cur = [{"name": "fig6/engine_cache", "us_per_call": 0.0,
            "derived": "programs=8 misses=8 traces=9"}]
    fails = cb.compare(base, cur, tolerance=50.0)
    assert any("traces" in f for f in fails)
    assert cb.compare(base, base, tolerance=50.0) == []


def test_check_bench_fails_on_slowdown_and_missing_records():
    cb = _check_bench()
    base = [{"name": "a", "us_per_call": 10.0, "derived": ""},
            {"name": "b", "us_per_call": 10.0, "derived": ""}]
    cur = [{"name": "a", "us_per_call": 10_000.0, "derived": ""}]
    fails = cb.compare(base, cur, tolerance=50.0)
    assert any("missing" in f for f in fails)
    assert any("50x baseline" in f for f in fails)
    # ignores float-valued derived tokens (speedup_vs_full=12.3x)
    assert cb.parse_counters("delta=48 speedup_vs_full=12.3x traces=5") == {
        "delta": 48, "traces": 5}


def test_check_bench_pins_round_counters_exactly():
    """The fig7/path_world_rounds record's round counters are pinned like
    program-cache counters: a depth regression (hybrid rounds creeping up)
    fails the gate even with identical timings."""
    cb = _check_bench()
    derived = "V=1024 sfs_rounds=1025 hybrid_rounds=2 chain_rounds=2"
    base = [{"name": "fig7/path_world_rounds", "us_per_call": 50.0,
             "derived": derived}]
    cur = [{"name": "fig7/path_world_rounds", "us_per_call": 50.0,
            "derived": "V=1024 sfs_rounds=1025 hybrid_rounds=200 "
                       "chain_rounds=2"}]
    fails = cb.compare(base, cur, tolerance=50.0)
    assert any("hybrid_rounds" in f for f in fails)
    assert cb.compare(base, base, tolerance=50.0) == []
    for key in ("sfs_rounds", "hybrid_rounds", "chain_rounds"):
        assert key in cb.EXACT_KEYS


def test_check_bench_covers_hybrid_cache_record():
    """fig6/hybrid_cache rides the same exact-counter rule as
    fig6/engine_cache: an extra hybrid-phase program fails the gate."""
    cb = _check_bench()
    base = [{"name": "fig6/hybrid_cache", "us_per_call": 0.0,
             "derived": "programs=10 misses=10 traces=10"}]
    cur = [{"name": "fig6/hybrid_cache", "us_per_call": 0.0,
            "derived": "programs=11 misses=11 traces=11"}]
    fails = cb.compare(base, cur, tolerance=50.0)
    assert any("programs" in f for f in fails)


def test_registry_decremental_flag():
    for kind in ANALYSIS_KINDS:
        assert get_analysis(kind).decremental, kind
