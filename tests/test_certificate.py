"""Sparse-certificate properties (paper Lemma 1 + the certificate theorem)."""
import numpy as np

from repro.core.bridges_host import bridges_dfs, bridges_from_edgelist
from repro.core.certificate import (
    certificate_capacity,
    merge_certificates,
    merge_certificates_incremental,
    sparse_certificate,
    sparse_certificate_ex,
)
from repro.graph import generators as gen
from repro.graph.datastructs import EdgeList

from _hyp import given, st
from helpers import SHAPE_BUCKETS, bucketed_graph, nx_bridges


@given(st.integers(0, 10_000))
def test_certificate_size_bound(seed):
    """|S| <= 2(n-1) — paper Lemma 1."""
    src, dst, n, el = bucketed_graph(seed)
    cert = sparse_certificate(el)
    assert int(cert.num_edges()) <= 2 * (n - 1)
    assert cert.capacity == certificate_capacity(n)


@given(st.integers(0, 10_000))
def test_certificate_preserves_bridges(seed):
    """bridges(G) == bridges(V, S) — the property the algorithm rests on."""
    src, dst, n, el = bucketed_graph(seed)
    cert = sparse_certificate(el)
    assert bridges_from_edgelist(cert) == nx_bridges(src, dst, n)


@given(st.integers(0, 10_000))
def test_certificate_union_property(seed):
    """bridges(G(V, E ∪ Y)) == bridges(G(V, S ∪ Y)) for random extra sets Y."""
    src, dst, n, el = bucketed_graph(seed)
    rng = np.random.default_rng(seed + 1)
    ysrc, ydst = gen.random_graph(n, int(rng.integers(1, n)), seed=seed + 1)
    if len(ysrc) == 0:
        return
    cert = sparse_certificate(el)
    cs, cd = cert.to_numpy()
    full = bridges_dfs(np.concatenate([src, ysrc]), np.concatenate([dst, ydst]), n)
    via_cert = bridges_dfs(np.concatenate([cs, ysrc]), np.concatenate([cd, ydst]), n)
    assert full == via_cert


@given(st.integers(0, 10_000))
def test_merge_step_is_a_certificate(seed):
    """One paper merge phase: cert(A) ∪ cert(B) re-certified still preserves
    the bridges of A ∪ B — the inductive invariant of the phase loop."""
    src_a, dst_a, n, el_a = bucketed_graph(seed)
    # same bucket => same n for the second graph
    src_b, dst_b, n_b, el_b = bucketed_graph(seed + len(SHAPE_BUCKETS))
    if n_b != n:
        src_b, dst_b = gen.random_graph(n, max(len(src_b), 1), seed=seed + 7)
        el_b = EdgeList.from_arrays(src_b, dst_b, n, capacity=el_a.capacity)
    ca = sparse_certificate(el_a)
    cb = sparse_certificate(el_b)
    merged = merge_certificates(ca, cb)
    assert int(merged.num_edges()) <= 2 * (n - 1)
    want = bridges_dfs(
        np.concatenate([src_a, src_b]), np.concatenate([dst_a, dst_b]), n
    )
    assert bridges_from_edgelist(merged) == want


@given(st.integers(0, 10_000))
def test_incremental_merge_matches_recertify(seed):
    """Warm-start merge (beyond-paper) preserves the same inductive
    invariant as the paper's re-certify step, and chains across phases."""
    src_a, dst_a, n, el_a = bucketed_graph(seed)
    src_b, dst_b, n_b, el_b = bucketed_graph(seed + len(SHAPE_BUCKETS))
    if n_b != n:
        src_b, dst_b = gen.random_graph(n, max(len(src_b), 1), seed=seed + 7)
        el_b = EdgeList.from_arrays(src_b, dst_b, n, capacity=el_a.capacity)
    cap = certificate_capacity(n)
    ca, lab1, lab2, _, _ = sparse_certificate_ex(el_a, capacity=cap)
    cb = sparse_certificate(el_b, capacity=cap)
    merged, lab1, lab2, rounds = merge_certificates_incremental(
        ca, lab1, lab2, cb
    )
    assert int(merged.num_edges()) <= 2 * (n - 1)
    want = bridges_dfs(
        np.concatenate([src_a, src_b]), np.concatenate([dst_a, dst_b]), n
    )
    assert bridges_from_edgelist(merged) == want
    # chain a second phase: merge a third certificate into the result
    src_c, dst_c = gen.random_graph(n, max(len(src_a) // 2, 1), seed=seed + 13)
    cc = sparse_certificate(
        EdgeList.from_arrays(src_c, dst_c, n, capacity=cap), capacity=cap
    )
    merged2, _, _, _ = merge_certificates_incremental(merged, lab1, lab2, cc)
    want2 = bridges_dfs(
        np.concatenate([src_a, src_b, src_c]),
        np.concatenate([dst_a, dst_b, dst_c]), n,
    )
    assert bridges_from_edgelist(merged2) == want2


def test_certificate_idempotent():
    src, dst = gen.random_graph(50, 200, seed=1)
    el = EdgeList.from_arrays(src, dst, 50)
    c1 = sparse_certificate(el)
    c2 = sparse_certificate(c1)
    s1, d1 = c1.to_numpy()
    s2, d2 = c2.to_numpy()
    key = lambda s, d: set(zip(np.minimum(s, d).tolist(), np.maximum(s, d).tolist()))
    assert key(s1, d1) == key(s2, d2)
