"""Spanning-forest properties: acyclic, component-spanning, label-correct —
for both the Borůvka hooking forest and the scan-first-search (BFS-layer)
frontier-hooking primitive."""
import jax
import networkx as nx
import numpy as np
import pytest

from repro.core.forest import (
    _ceil_log2,
    _shortcut,
    connected_components,
    narrow_slots,
    scan_first_forest,
    scan_first_forest_ex,
    spanning_forest,
    spanning_forest_ex,
)
from repro.graph import generators as gen
from repro.graph.datastructs import EdgeList

from _hyp import given, st
from helpers import (
    blob_chain,
    boruvka_reference,
    bucketed_graph,
    kronecker_like,
    to_graph,
)


def check_forest(src, dst, n, el):
    fmask, labels = spanning_forest(el)
    fmask, labels = np.asarray(fmask), np.asarray(labels)
    emask = np.asarray(el.mask)
    fs = np.asarray(el.src)[fmask & emask]
    fd = np.asarray(el.dst)[fmask & emask]
    G = to_graph(src, dst, n)
    F = to_graph(fs, fd, n)
    assert nx.is_forest(F)
    assert nx.number_connected_components(F) == nx.number_connected_components(G)
    for comp in nx.connected_components(G):
        assert len({int(labels[v]) for v in comp}) == 1
    assert len(set(labels.tolist())) == nx.number_connected_components(G)


@given(st.integers(0, 10_000))
def test_forest_random(seed):
    src, dst, n, el = bucketed_graph(seed)
    check_forest(src, dst, n, el)


@given(st.integers(0, 10_000))
def test_forest_multigraph_selfloops(seed):
    """Duplicates + self loops must not break acyclicity/spanning."""
    src, dst, n, el = bucketed_graph(seed, simple=False)
    check_forest(src, dst, n, el)


def test_forest_tree_keeps_all_edges():
    src, dst = gen.tree_graph(60, seed=3)
    el = EdgeList.from_arrays(src, dst, 60)
    fmask, _ = spanning_forest(el)
    assert bool(np.asarray(fmask).all())


def test_forest_all_masked():
    el = EdgeList(
        np.zeros(4, np.int32), np.zeros(4, np.int32), np.zeros(4, bool), 5
    )
    fmask, labels = spanning_forest(el)
    assert not np.asarray(fmask).any()
    assert np.array_equal(np.asarray(labels), np.arange(5))


def test_connected_components_matches_networkx():
    src, dst = gen.random_graph(70, 60, seed=9)
    labels = np.asarray(connected_components(EdgeList.from_arrays(src, dst, 70)))
    G = to_graph(src, dst, 70)
    for comp in nx.connected_components(G):
        assert len({int(labels[v]) for v in comp}) == 1


# ----------------------------------------- scan-first search (BFS layers)
def check_sfs(src, dst, n, el):
    """The frontier-hooking invariants: a genuine BFS-layer forest."""
    fmask, parent, level = scan_first_forest(el)
    fmask = np.asarray(fmask) & np.asarray(el.mask)
    parent, level = np.asarray(parent), np.asarray(level)
    G = to_graph(src, dst, n)
    G.remove_edges_from(nx.selfloop_edges(G))

    # the forest is a forest and spans exactly G's components
    fs = np.asarray(el.src)[fmask]
    fd = np.asarray(el.dst)[fmask]
    F = to_graph(fs, fd, n)
    assert nx.is_forest(F)
    assert nx.number_connected_components(F) == nx.number_connected_components(G)

    # BFS-layer invariant: every tree edge joins adjacent layers,
    # parent level = child level - 1, and levels are true BFS distances
    # from the component's min-id root
    for comp in nx.connected_components(G):
        r = min(comp)
        dist = nx.single_source_shortest_path_length(G, r)
        for v in comp:
            assert level[v] == dist[v], (v, level[v], dist[v])
            if v != r:
                assert level[parent[v]] == level[v] - 1
                assert G.has_edge(int(parent[v]), int(v))
    for u, w in zip(fs.tolist(), fd.tolist()):
        assert abs(int(level[u]) - int(level[w])) == 1


@given(st.integers(0, 10_000))
def test_sfs_layer_invariant_random(seed):
    src, dst, n, el = bucketed_graph(seed)
    check_sfs(src, dst, n, el)


@given(st.integers(0, 10_000))
def test_sfs_multigraph_selfloops(seed):
    src, dst, n, el = bucketed_graph(seed, simple=False)
    check_sfs(src, dst, n, el)


@given(st.integers(0, 10_000))
def test_sfs_labels_equal_boruvka_components(seed):
    """The SFS root labels induce exactly the Borůvka hooking partition
    (canonicalized to min member id)."""
    src, dst, n, el = bucketed_graph(seed, simple=(seed % 2 == 0))
    _, _, _, root, _ = scan_first_forest_ex(el)
    root = np.asarray(root)
    labels = np.asarray(connected_components(el))
    # same partition...
    vs = np.arange(n)
    canon = np.array([vs[labels == labels[v]].min() for v in range(n)])
    assert np.array_equal(root, canon)


def test_sfs_on_failure_scenarios():
    """Planted scenarios: BFS layers + component labels on ground truth."""
    for sc in gen.failure_scenarios():
        src, dst, n = sc["src"], sc["dst"], sc["n"]
        el = EdgeList.from_arrays(src, dst, n)
        check_sfs(src, dst, n, el)
        _, _, _, root, _ = scan_first_forest_ex(el)
        root = np.asarray(root)
        labels = np.asarray(connected_components(el))
        G = to_graph(src, dst, n)
        for comp in nx.connected_components(G):
            assert len({int(root[v]) for v in comp}) == 1
            assert int(root[min(comp)]) == min(comp)
        assert len(set(root.tolist())) == len(set(labels.tolist()))


def test_sfs_isolated_and_masked():
    el = EdgeList(
        np.zeros(4, np.int32), np.zeros(4, np.int32), np.zeros(4, bool), 5
    )
    fmask, parent, level = scan_first_forest(el)
    assert not np.asarray(fmask).any()
    assert np.array_equal(np.asarray(parent), np.arange(5))
    assert np.array_equal(np.asarray(level), np.zeros(5))  # all roots


def test_sfs_path_graph_levels():
    """A path rooted at 0 must produce levels 0..n-1 (depth = diameter)."""
    n = 12
    src = np.arange(n - 1, dtype=np.int32)
    dst = np.arange(1, n, dtype=np.int32)
    el = EdgeList.from_arrays(src, dst, n)
    fmask, parent, level = scan_first_forest(el)
    assert bool(np.asarray(fmask).all())
    assert np.array_equal(np.asarray(level), np.arange(n))
    assert np.array_equal(np.asarray(parent)[1:], np.arange(n - 1))


@given(st.integers(0, 10_000))
def test_warm_start_forest_extends_to_union(seed):
    """Incremental primitive: forest(B | init_labels=labels(F_A)) joined
    with F_A must be a spanning forest of A ∪ B (the invariant the
    warm-start merge rests on)."""
    from repro.core.forest import spanning_forest_ex

    src_a, dst_a, n, el_a = bucketed_graph(seed)
    src_b, dst_b = gen.random_graph(n, max(len(src_a) // 2, 1), seed=seed + 3)
    el_b = EdgeList.from_arrays(src_b, dst_b, n)

    fa, labels_a, _, _ = spanning_forest_ex(el_a)
    fd, labels_u, _, _ = spanning_forest_ex(el_b, init_labels=labels_a)
    fa, fd = np.asarray(fa), np.asarray(fd)

    fs = np.concatenate([src_a[fa[: len(src_a)] & np.asarray(el_a.mask)[: len(src_a)]],
                         src_b[fd[: len(src_b)]]])
    fdst = np.concatenate([dst_a[fa[: len(src_a)] & np.asarray(el_a.mask)[: len(src_a)]],
                           dst_b[fd[: len(src_b)]]])
    U = to_graph(np.concatenate([src_a, src_b]), np.concatenate([dst_a, dst_b]), n)
    F = to_graph(fs, fdst, n)
    assert nx.is_forest(F)
    assert nx.number_connected_components(F) == nx.number_connected_components(U)
    # labels after the warm-started pass = components of the union
    labels_u = np.asarray(labels_u)
    for comp in nx.connected_components(U):
        assert len({int(labels_u[v]) for v in comp}) == 1


# ------------------------------------- filtered loop against the reference
def _filtered_case(case):
    """(src, dst, mask, n, init_labels) of one case, in a 2^16-slot buffer
    (``narrow_slots`` 64: room for the compaction to engage)."""
    cap = 1 << 16
    if case == "blob_chain_compacts":
        s, d, n = blob_chain(8, 64, 4000, seed=0)
    elif case == "skewed_stops_at_zero":
        s, d, n = kronecker_like(12, cap, seed=1)
    elif case == "all_masked":
        s, d, n = blob_chain(4, 32, 500, seed=2)
    else:  # warm_start: a second edge set hooked onto a first's partition
        s0, d0, n = blob_chain(8, 64, 200, seed=3)
        s, d, _ = blob_chain(8, 64, 4000, seed=4)
        _, init, _, _ = boruvka_reference(s0, d0, np.ones(len(s0), bool), n)
    el = EdgeList.from_arrays(s, d, n, capacity=cap)
    mask = np.asarray(el.mask) & (case != "all_masked")
    return (np.asarray(el.src), np.asarray(el.dst), mask, n,
            init if case == "warm_start" else None)


@pytest.mark.parametrize("case", ["blob_chain_compacts",
                                  "skewed_stops_at_zero", "all_masked",
                                  "warm_start", "vmapped_below_threshold",
                                  "vmapped_would_compact", "plain_asked_for"])
def test_filtered_loop_matches_reference_bit_for_bit(case):
    """The filtered Borůvka loop returns the (forest, labels, rounds) of a
    plain numpy min-index Borůvka, bit for bit, and scans the whole buffer
    in exactly the rounds before the first that finds at most
    ``narrow_slots(E)`` cross edges. Under ``vmap`` the plain loop runs,
    which scans the whole buffer in every round: on buffers with no narrow
    slots (below 2^10) and on rows the filtered loop would compact. So it
    does where the caller asks for it (``filtered=False``)."""
    if case == "plain_asked_for":
        src, dst, mask, n, _ = _filtered_case("blob_chain_compacts")
        f, lab, r, full = spanning_forest_ex(EdgeList(src, dst, mask, n),
                                             filtered=False)
        want_f, want_l, want_r, _ = boruvka_reference(src, dst, mask, n)
        assert np.array_equal(np.asarray(f), want_f)
        assert np.array_equal(np.asarray(lab), want_l)
        assert int(r) == int(full) == want_r
        return
    if case.startswith("vmapped"):
        if case == "vmapped_below_threshold":
            graphs = [bucketed_graph(seed)[3] for seed in (0, 3, 6)]
            rows = [(np.asarray(g.src), np.asarray(g.dst), np.asarray(g.mask))
                    for g in graphs]  # 64 slots
            n = graphs[0].n_nodes
        else:
            cases = [_filtered_case(c) for c in
                     ("blob_chain_compacts", "skewed_stops_at_zero",
                      "all_masked")]
            rows = [c[:3] for c in cases]
            n = max(c[3] for c in cases)
        src, dst, mask = (np.stack(col) for col in zip(*rows))
        got = jax.vmap(lambda s, d, m: spanning_forest_ex(
            EdgeList(s, d, m, n)))(src, dst, mask)
        for i in range(len(rows)):
            f, lab, r, full = (np.asarray(x[i]) for x in got)
            want_f, want_l, want_r, _ = boruvka_reference(src[i], dst[i],
                                                          mask[i], n)
            assert np.array_equal(f, want_f)
            assert np.array_equal(lab, want_l)
            assert int(r) == int(full) == want_r
        if case == "vmapped_would_compact":  # unbatched, row 0 compacts
            _, _, r0, full0 = spanning_forest_ex(EdgeList(*rows[0], n))
            assert int(full0) < int(r0)
        return
    src, dst, mask, n, init = _filtered_case(case)
    el = EdgeList(src, dst, mask, n)
    f, lab, r, full = spanning_forest_ex(el, init_labels=init)
    want_f, want_l, want_r, live = boruvka_reference(src, dst, mask, n,
                                                     init_labels=init)
    assert np.array_equal(np.asarray(f), want_f)
    assert np.array_equal(np.asarray(lab), want_l)
    assert int(r) == want_r
    k = narrow_slots(len(src))
    narrow_at = next(i for i, x in enumerate(live) if x <= k)
    assert int(full) == narrow_at
    if case == "blob_chain_compacts":  # narrow rounds that still hook
        assert 0 < live[narrow_at] and narrow_at <= want_r - 2
    elif case == "skewed_stops_at_zero":  # only the check is spared
        assert live[narrow_at] == 0 and narrow_at == want_r - 1
    elif case == "all_masked":
        assert (want_r, int(full)) == (1, 0)


# ------------------------------------------ pointer doubling to convergence
def _parents(case, n, seed=0):
    """Parent pointers of one rooted forest over ``n`` vertices (roots
    point at themselves), ids permuted."""
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    if case == "random_forest":  # each vertex under an earlier one, or a root
        up = np.array([rng.integers(0, i) if i and rng.random() < 0.97
                       else i for i in range(n)])
    elif case == "path":  # depth n - 1
        up = np.maximum(np.arange(n) - 1, 0)
    elif case == "star":
        up = np.zeros(n, np.int64)
    else:  # identity
        up = np.arange(n)
    p = np.empty(n, np.int32)
    p[order] = order[up]
    return p


def _fixed_steps(p, steps):
    for _ in range(steps):
        p = p[p]
    return p


@pytest.mark.parametrize("case", ["random_forest", "path", "star",
                                  "identity", "vmapped"])
def test_shortcut_matches_fixed_step_compression(case):
    """``_shortcut`` stops once a step moves no label, and returns what
    ``ceil(log2 n)`` fixed pointer-doubling steps return, bit for bit: on
    a path of depth n − 1, which needs every one of those steps, and under
    ``vmap`` on rows that converge after different numbers of steps."""
    n = 1 << 10
    steps = _ceil_log2(n)
    if case == "vmapped":
        rows = np.stack([_parents(c, n, seed=i) for i, c in enumerate(
            ("random_forest", "path", "star", "identity"))])
        got = np.asarray(jax.vmap(lambda p: _shortcut(p, steps))(rows))
        for row, out in zip(rows, got):
            assert np.array_equal(out, _fixed_steps(row, steps))
        return
    p = _parents(case, n)
    got = np.asarray(_shortcut(p, steps))
    want = _fixed_steps(p, steps)
    assert got.dtype == want.dtype
    assert np.array_equal(got, want)
    assert np.array_equal(want, want[want])  # a full compression
    if case == "path":  # one step fewer leaves it unfinished
        short = _fixed_steps(p, steps - 1)
        assert not np.array_equal(short, short[short])
