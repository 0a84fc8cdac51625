"""Certificate registry (DESIGN.md §Certificate registry): descriptor
validation, the hybrid Borůvka⊕SFS certificate's correctness on sparse /
path-like / barbell worlds for every analysis kind, its bounded BFS depth,
and the engine serving substrates with ``certificate='hybrid'``.

Shapes are pinned to one bucket family (n=48 -> n_bucket 64, base edges ->
cap 256, deltas/keys -> bucket 16) and one module-level engine is shared,
so the whole module compiles each program once (1-core CI box). Worlds are
SIMPLE graphs where 2-edge kinds are asserted (the sfs/hybrid multigraph
contract covers the vertex kinds only — parallel copies of a scanned pair
are dup-excluded, same as ``sfs_certificate``).
"""
import dataclasses

import numpy as np
import pytest

from repro.connectivity.registry import ANALYSIS_KINDS, get_analysis, register
from repro.core import certs
from repro.core.certificate import (
    certificate_capacity,
    hybrid_certificate,
    hybrid_certificate_ex,
    sfs_certificate_ex,
)
from repro.core.certs import (
    CERTIFICATE_NAMES,
    Certificate,
    certificate_builder,
    get_certificate,
    primary_certificate,
    register_certificate,
)
from repro.core.merge import simulate_merge_host
from repro.core.partition import partition_edges
from repro.engine import BridgeEngine
from repro.graph import generators as gen
from repro.graph.datastructs import EdgeList

from _hyp import given, st

N, E0 = 48, 150          # n_bucket 64, full-buffer bucket 256
DELTA = 12               # insert/delete batches land in key bucket 16
CAP = 256                # shared raw-edge capacity: one compiled shape

ENGINE = BridgeEngine()

VERTEX_KINDS = ("cuts", "bcc")


# ------------------------------------------------------------------ helpers
def _same(kind, got, want):
    if get_analysis(kind).kind == "2ecc":
        return np.array_equal(np.asarray(got), np.asarray(want))
    return got == want


def _host(kind, s, d, n):
    return get_analysis(kind).host_fn(np.asarray(s, np.int32),
                                      np.asarray(d, np.int32), n)


def _pair(cert):
    s, d, m = np.asarray(cert.src), np.asarray(cert.dst), np.asarray(cert.mask)
    return s[m], d[m]


def _path_world(n=N):
    s = np.arange(n - 1, dtype=np.int32)
    return s, s + 1, n


def _worlds():
    """sparse / path / barbell worlds, all inside the (64, 256) buckets."""
    bs, bd, _, bn = gen.barbell(6, 8)
    return [
        ("sparse", *gen.random_graph(N, E0, seed=3), N),
        ("sparser", *gen.random_graph(N, N, seed=4), N),
        ("path", *_path_world()),
        ("barbell", bs, bd, bn),
    ]


# --------------------------------------------------------------- validation
def test_builtin_registry_contents():
    assert CERTIFICATE_NAMES == ("2ec", "sfs", "hybrid")
    assert primary_certificate() == "2ec"
    assert not get_certificate("2ec").lazy
    assert get_certificate("sfs").lazy and get_certificate("hybrid").lazy
    assert get_certificate("2ec").warm_merge
    assert not get_certificate("hybrid").warm_merge
    assert certificate_builder("hybrid") is hybrid_certificate


def test_unknown_certificate_lookup_raises():
    with pytest.raises(ValueError, match="choose from"):
        get_certificate("nope")


def test_register_certificate_validation_errors():
    ok = get_certificate("sfs")
    with pytest.raises(ValueError, match="non-empty"):
        register_certificate(dataclasses.replace(ok, name=""))
    with pytest.raises(ValueError, match="unknown structure"):
        register_certificate(dataclasses.replace(
            ok, name="bad", preserves=frozenset({"kappa9"})))
    assert "bad" not in certs.certificate_names()


def test_analysis_registration_validates_against_cert_registry():
    with pytest.raises(ValueError, match="unknown certificate type"):
        register(dataclasses.replace(get_analysis("bridges"),
                                     kind="broken", certificate="nope"))


def test_engine_certificate_resolution():
    # per-call override: strict — a lambda2 kind cannot ride a kappa2-only
    # certificate and vice versa
    with pytest.raises(ValueError, match="does not preserve"):
        ENGINE._resolve_certificate(get_analysis("bridges"), "hybrid")
    with pytest.raises(ValueError, match="does not preserve"):
        ENGINE._resolve_certificate(get_analysis("cuts"), "2ec")
    assert ENGINE._resolve_certificate(get_analysis("cuts"), "hybrid") == "hybrid"
    # engine-wide preference: permissive — falls back per kind
    eng = BridgeEngine(certificate="hybrid")
    assert eng.certificate_for("cuts") == "hybrid"
    assert eng.certificate_for("bcc") == "hybrid"
    assert eng.certificate_for("bridges") == "2ec"
    assert BridgeEngine().certificate_for("cuts") == "sfs"
    with pytest.raises(ValueError, match="choose from"):
        BridgeEngine(certificate="nope")


# ------------------------------------------------- hybrid pair vs host refs
@pytest.mark.parametrize("kind", ANALYSIS_KINDS)
def test_hybrid_pair_preserves_every_kind_on_worlds(kind):
    """The hybrid pair answers every registry kind exactly like the full
    graph, on sparse/path/barbell worlds (host reference on the pair's
    edges vs host reference on the full edge set)."""
    for name, s, d, n in _worlds():
        el = EdgeList.from_arrays(s, d, N if n <= N else n, capacity=CAP)
        nn = el.n_nodes
        cert = hybrid_certificate(el)
        cs, cd = _pair(cert)
        assert len(cs) <= certificate_capacity(nn), (name, kind)
        got = _host(kind, cs, cd, nn)
        want = _host(kind, s, d, nn)
        assert _same(kind, got, want), (name, kind)


@given(st.integers(0, 2 ** 31 - 1), st.sampled_from(ANALYSIS_KINDS),
       st.sampled_from(["sparse", "path", "barbell"]))
def test_hybrid_pair_property_simple_worlds(seed, kind, world):
    """Property: on any simple sparse/path/barbell world the hybrid pair
    preserves the kind's answer (shapes pinned to the module buckets)."""
    rng = np.random.default_rng(seed)
    if world == "sparse":
        s, d = gen.random_graph(N, int(rng.integers(10, E0)), seed=seed)
    elif world == "path":
        k = int(rng.integers(2, N))       # path on k of the N vertices
        s = np.arange(k - 1, dtype=np.int32)
        d = s + 1
    else:
        s, d, _, bn = gen.barbell(int(rng.integers(3, 7)),
                                  int(rng.integers(1, 9)))
        assert bn <= N
    cert = hybrid_certificate(EdgeList.from_arrays(s, d, N, capacity=CAP))
    cs, cd = _pair(cert)
    assert _same(kind, _host(kind, cs, cd, N), _host(kind, s, d, N)), \
        (kind, world)


@given(st.integers(0, 2 ** 31 - 1), st.sampled_from(VERTEX_KINDS))
def test_hybrid_pair_property_multigraph_vertex_kinds(seed, kind):
    """Property: on multigraphs (parallel edges, self loops) the hybrid
    pair still preserves the vertex-connectivity kinds — the sfs contract
    it inherits."""
    s, d = gen.random_graph(N, E0, seed=seed, simple=False)
    cert = hybrid_certificate(EdgeList.from_arrays(s, d, N, capacity=CAP))
    cs, cd = _pair(cert)
    assert _same(kind, _host(kind, cs, cd, N), _host(kind, s, d, N)), kind


def test_hybrid_counterexample_graph_keeps_hub_noncut():
    """The DESIGN §Connectivity counterexample (hub + two triangles +
    cross edges) through the hybrid path: the graph is 2-vertex-connected
    and the hybrid pair must keep it so."""
    src = np.array([1, 2, 3, 4, 5, 6, 0, 0, 0, 0, 0, 0, 1, 2, 3], np.int32)
    dst = np.array([2, 3, 1, 5, 6, 4, 1, 2, 3, 4, 5, 6, 4, 5, 6], np.int32)
    cert = hybrid_certificate(EdgeList.from_arrays(src, dst, 7, capacity=16))
    cs, cd = _pair(cert)
    assert _host("cuts", cs, cd, 7) == set()


# ------------------------------------------------------------ bounded depth
def test_hybrid_bfs_rounds_far_below_sfs_on_long_path():
    """Acceptance: on an n>=1024 path world the hybrid's BFS rounds are
    >=4x below the SFS pair's (in fact O(1) vs O(n): the chain contracts
    to nothing)."""
    s, d, n = _path_world(1024)
    el = EdgeList.from_arrays(s, d, n)
    _, _, _, (sr1, sr2), _ = sfs_certificate_ex(el)
    cert, (r_chain, hr1, hr2), _ = hybrid_certificate_ex(el)
    sfs_rounds = int(sr1) + int(sr2)
    hybrid_rounds = int(hr1) + int(hr2)
    assert sfs_rounds >= n - 1          # one BFS layer per path vertex
    assert hybrid_rounds * 4 <= sfs_rounds
    assert hybrid_rounds <= 4           # contracted path has no real edges
    # the Borůvka chain contraction stays logarithmic
    assert int(r_chain) <= 12
    # and the certificate is still exact: a path is all bridges
    cs, cd = _pair(cert)
    assert _host("bridges", cs, cd, n) == _host("bridges", s, d, n)


# -------------------------------------------------- engine: live substrate
def test_engine_hybrid_no_retrace_after_warmup():
    """Same-bucket churn with certificate='hybrid' causes ZERO retraces
    once the hybrid load/fold/rebuild programs are warm."""
    s, d = gen.random_graph(N, E0, seed=11)
    live = list(zip(s.tolist(), d.tolist()))
    eng = ENGINE.load(s, d, N)
    rng = np.random.default_rng(5)
    for kind in VERTEX_KINDS:           # materialize + final programs
        eng.current_analysis(kind, certificate="hybrid")
    assert "hybrid" in eng.live_rebuilds

    def insert(seed):
        ds, dd = gen.random_graph(N, DELTA, seed=seed)
        live.extend(zip(ds.tolist(), dd.tolist()))
        return eng.insert_edges(ds, dd, kind="cuts", certificate="hybrid")

    def delete(pick):
        ks = np.array([x for x, _ in pick], np.int32)
        kd = np.array([y for _, y in pick], np.int32)
        live[:] = [(x, y) for x, y in live
                   if (min(x, y), max(x, y))
                   not in {(min(a, b), max(a, b)) for a, b in pick}]
        return eng.delete_edges(ks, kd, kind="cuts", certificate="hybrid")

    # warm-up: fold-in, append, tombstone, and the rebuild path (deleting
    # a hybrid certificate edge forces its cert_load rebuild program)
    insert(100)
    hs, hd, hm = (np.asarray(x) for x in eng._live["certs"]["hybrid"][:3])
    delete(list(zip(hs[hm][:3].tolist(), hd[hm][:3].tolist())))
    assert eng.live_rebuilds["hybrid"] >= 1
    insert(101)
    traces = eng.stats.traces
    for step in range(4):
        if rng.random() < 0.5 and len(live) > DELTA:
            pick = [live[i] for i in
                    rng.choice(len(live), 5, replace=False)]
            got = delete(pick)
        else:
            got = insert(200 + step)
        want = _host("cuts", [x for x, _ in live], [y for _, y in live], N)
        assert got == want, step
    assert eng.stats.traces == traces, "hybrid churn retraced"


@given(st.integers(0, 2 ** 31 - 1), st.sampled_from(VERTEX_KINDS),
       st.lists(st.booleans(), min_size=1, max_size=4))
def test_engine_hybrid_churn_property_matches_host(seed, kind, is_delete):
    """Property: interleaved insert/delete churn served with
    certificate='hybrid' matches the host recompute for the vertex kinds
    (module bucket family, compiled programs reused)."""
    rng = np.random.default_rng(seed)
    s, d = gen.random_graph(N, E0, seed=seed % 1000)
    live = list(zip(s.tolist(), d.tolist()))
    eng = ENGINE.load(s, d, N)
    eng.current_analysis(kind, certificate="hybrid")
    for i, dele in enumerate(is_delete):
        if dele and len(live) > DELTA:
            pick = [live[j] for j in
                    rng.choice(len(live), DELTA, replace=False)]
            ks = np.array([x for x, _ in pick], np.int32)
            kd = np.array([y for _, y in pick], np.int32)
            got = eng.delete_edges(ks, kd, kind=kind, certificate="hybrid")
            kset = {(min(x, y), max(x, y)) for x, y in pick}
            live = [(x, y) for x, y in live
                    if (min(x, y), max(x, y)) not in kset]
        else:
            ds, dd = gen.random_graph(N, DELTA, seed=seed + i)
            got = eng.insert_edges(ds, dd, kind=kind, certificate="hybrid")
            live = live + list(zip(ds.tolist(), dd.tolist()))
        want = _host(kind, [x for x, _ in live], [y for _, y in live], N)
        assert _same(kind, got, want), (i, kind)


# ------------------------------------------------- every-substrate serving
def test_one_shot_host_final_and_batched_with_hybrid():
    """One-shot single and batched queries with certificate='hybrid'
    (final='host' routes through the hybrid builder inside the cached
    program)."""
    s, d = gen.random_graph(N, E0, seed=21)
    for kind in VERTEX_KINDS:
        got = ENGINE.analyze(s, d, N, kind=kind, final="host",
                             certificate="hybrid")
        assert _same(kind, got, _host(kind, s, d, N)), kind
    graphs = [gen.random_graph(N, E0, seed=30 + i) for i in range(3)]
    got = ENGINE.analyze_batch(graphs, N, kind="cuts", final="host",
                               certificate="hybrid")
    for i, (gs, gd) in enumerate(graphs):
        assert got[i] == _host("cuts", gs, gd, N), i


@pytest.mark.parametrize("schedule", ["paper", "xor"])
def test_hybrid_composes_under_merge_schedules(schedule):
    """Distributed substrate (host-simulated): per-machine hybrid
    certificates merged by the real phase permutations answer the vertex
    kinds exactly — union-then-recertify composability."""
    s, d = gen.random_graph(N, E0, seed=9)
    m = 4
    psrc, pdst, pmask = partition_edges(s, d, N, m, seed=2)
    certs_in = [hybrid_certificate(EdgeList(psrc[i], pdst[i], pmask[i], N),
                                   capacity=certificate_capacity(N))
                for i in range(m)]
    merged = simulate_merge_host(certs_in, schedule,
                                 certify=hybrid_certificate)
    answer_on = [0] if schedule == "paper" else range(m)
    for kind in VERTEX_KINDS:
        want = _host(kind, s, d, N)
        for i in answer_on:
            cs, cd = merged[i].to_numpy()
            assert _same(kind, _host(kind, cs, cd, N), want), (kind, i)


def test_new_registered_certificate_served_with_no_engine_edits():
    """Registering a NEW certificate type makes it immediately servable:
    the engine materializes, folds, rebuilds, and resolves it purely
    through the registry (here: a clone of hybrid under another name)."""
    clone = dataclasses.replace(get_certificate("hybrid"), name="hybrid2")
    register_certificate(clone)
    try:
        s, d = gen.random_graph(N, E0, seed=33)
        eng = BridgeEngine(certificate="hybrid2")
        assert eng.certificate_for("cuts") == "hybrid2"
        eng.load(s, d, N)
        assert eng.current_analysis("cuts") == _host("cuts", s, d, N)
        ds, dd = gen.random_graph(N, DELTA, seed=34)
        got = eng.insert_edges(ds, dd, kind="cuts")
        assert got == _host("cuts", np.concatenate([s, ds]),
                            np.concatenate([d, dd]), N)
        assert "hybrid2" in eng.live_rebuilds
    finally:
        certs._REGISTRY.pop("hybrid2")
