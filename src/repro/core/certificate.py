"""Sparse 2-edge-connectivity certificates (paper §III, Lemma 1).

``S = F1 ∪ F2`` where F1 is a spanning forest of G and F2 a spanning forest
of G − F1 (Nagamochi–Ibaraki / Cheriyan–Kao–Thurimella, k = 2).
|S| ≤ 2(n−1), and for any extra edge set Y,
bridges(G(V, E ∪ Y)) == bridges(G(V, S ∪ Y)).

The output lives in a fixed ``2(n−1)``-slot buffer so certificates from
different machines/phases always have identical shapes.
"""
from __future__ import annotations

import jax

from repro.core.forest import scan_first_forest_ex, spanning_forest_ex
from repro.graph.datastructs import INT, EdgeList, compact_edges, concat_edges


def certificate_capacity(n_nodes: int) -> int:
    return max(2 * (n_nodes - 1), 1)


def sparse_certificate(edges: EdgeList, capacity: int | None = None) -> EdgeList:
    """Compute the certificate and compact it into a 2(n−1)-slot buffer."""
    cert, *_ = sparse_certificate_ex(edges, capacity=capacity)
    return cert


def merge_certificates(a: EdgeList, b: EdgeList) -> EdgeList:
    """One paper merge step: union two certificates, re-certify to 2(n−1)."""
    both = concat_edges(a, b)
    return sparse_certificate(both, capacity=certificate_capacity(a.n_nodes))


def sparse_certificate_ex(edges: EdgeList, capacity: int | None = None):
    """Certificate + the component labels of its two forests (+ rounds,
    and of those the full-width ones, per forest).

    The labels seed the INCREMENTAL merge below: they are the state that
    lets later phases skip re-certifying edges they already know about.
    The passes run under the phase scopes ``cert/forest1``,
    ``cert/forest2`` and ``cert/compact`` (DESIGN.md §Observability).
    """
    cap = certificate_capacity(edges.n_nodes) if capacity is None else capacity
    with jax.named_scope("cert/forest1"):
        f1, lab1, r1, w1 = spanning_forest_ex(edges)
    with jax.named_scope("cert/forest2"):
        rest = EdgeList(edges.src, edges.dst, edges.mask & ~f1, edges.n_nodes)
        f2, lab2, r2, w2 = spanning_forest_ex(rest)
    with jax.named_scope("cert/compact"):
        cert = compact_edges(edges, cap, keep=f1 | f2)
    return cert, lab1, lab2, (r1, r2), (w1, w2)


def sfs_certificate(edges: EdgeList, capacity: int | None = None) -> EdgeList:
    """Scan-first-search certificate: S = F1 ∪ F2 with F1 a BFS-layer
    (scan-first) forest of G and F2 one of G − F1 (Cheriyan–Kao–Thurimella,
    k = 2). Same 2(n−1) size bound as the Borůvka certificate, but the
    layered forests additionally preserve VERTEX connectivity up to 2 —
    articulation points and biconnected blocks of S match G, which the
    arbitrary-forest pair provably does not (DESIGN.md §Connectivity).

    Like the 2-edge certificate it composes under union: re-certifying the
    union of two SFS certificates yields an SFS certificate of the union,
    so the same merge schedules serve the vertex-connectivity kinds.
    """
    cert, *_ = sfs_certificate_ex(edges, capacity=capacity)
    return cert


def sfs_certificate_ex(edges: EdgeList, capacity: int | None = None):
    """SFS certificate + F1's (parent, level) pair (+ BFS rounds per pass,
    twice: every BFS round scans the whole buffer, so all are full-width).

    parent/level are the live SFS forest state the engine keeps for
    incremental vertex-cut serving (DESIGN.md §Analysis registry)."""
    cap = certificate_capacity(edges.n_nodes) if capacity is None else capacity
    f1, parent, level, _, r1 = scan_first_forest_ex(edges)
    # F2 scans the SIMPLE complement of F1: a slot duplicating an F1 pair
    # {v, parent(v)} adds nothing to vertex connectivity (unlike the 2-edge
    # case, where the parallel copy is what protects the pair) and would
    # waste an F2 forest slot that a genuinely new edge needs.
    dup = (parent[edges.src] == edges.dst) | (parent[edges.dst] == edges.src)
    rest = EdgeList(edges.src, edges.dst, edges.mask & ~f1 & ~dup,
                    edges.n_nodes)
    f2, _, _, _, r2 = scan_first_forest_ex(rest)
    cert = compact_edges(edges, cap, keep=f1 | f2)
    return cert, parent, level, (r1, r2), (r1, r2)


def hybrid_certificate(edges: EdgeList, capacity: int | None = None) -> EdgeList:
    """Hybrid Borůvka⊕SFS certificate for sparse, path-like worlds.

    The plain SFS certificate pays one BFS round per layer — O(diameter),
    which is exactly wrong on long induced paths. The hybrid bounds the
    scanned diameter by handling degree-≤2 chains combinatorially first:

      1. **Chain edges** — every edge incident to a vertex of (masked,
         multiplicity-counted) degree ≤ 2 goes into the certificate
         verbatim. Such edges are ≤ 2 per low-degree vertex, so this part
         never exceeds 2·|{deg ≤ 2}| slots.
      2. **Contract** — the edges whose BOTH endpoints have degree ≤ 2 (the
         chain interiors) are Borůvka-hooked (``spanning_forest_ex``,
         O(log n) rounds) and each chain component collapses to one label;
         high-degree vertices keep their own labels. A maximal chain thus
         becomes a length-2 virtual path u–c–v between its attachment
         vertices — subdivision, not smoothing, so parallel attachments
         stay distinguishable.
      3. **Scan** — the scan-first pair F1 ∪ F2 is built on the RELABELED
         buffer (same slots, contracted endpoints, interiors masked off).
         Its BFS rounds are O(diameter of the contracted graph): chains of
         any length cost one hop.
      4. **Re-expand** — selection maps back slot-for-slot; the output is
         chain ∪ F1 ∪ F2 compacted into the usual 2(n−1)-slot buffer
         (|chain| ≤ 2s and |Fi ∩ non-chain| ≤ h−1 for s low-degree and h
         high-degree vertices, so the bound is safe).

    Validity (DESIGN.md §Certificate registry for the sketch): the
    certificate keeps every chain edge, and its contracted image contains
    an SFS pair of the contracted graph, so cut/block/bridge structure is
    preserved on the contraction and lifts through the subdivision
    equivalence. Same contract as ``sfs_certificate``: vertex connectivity
    up to 2 always, edge connectivity up to 2 on simple inputs; composes
    under union-then-recertify, so it rides every merge schedule.
    """
    cert, *_ = hybrid_certificate_ex(edges, capacity=capacity)
    return cert


def hybrid_certificate_ex(edges: EdgeList, capacity: int | None = None):
    """Hybrid certificate + per-pass round counts.

    Returns ``(cert, (rounds_chain, rounds_f1, rounds_f2), full)`` where
    ``rounds_chain`` counts the Borůvka hooking rounds of the chain
    contraction and ``rounds_f1``/``rounds_f2`` the BFS rounds of the two
    scan passes on the contracted buffer — the observable for "hybrid
    bounds SFS depth on path-like worlds" (benchmarks/fig7
    ``path_world_rounds``); ``full`` the same three counts of full-width
    rounds."""
    cap = certificate_capacity(edges.n_nodes) if capacity is None else capacity
    n = edges.n_nodes
    src, dst, mask = edges.src, edges.dst, edges.mask
    valid = mask & (src != dst)
    ones = valid.astype(INT)
    deg = (jax.ops.segment_sum(ones, src, num_segments=n)
           + jax.ops.segment_sum(ones, dst, num_segments=n))
    low = deg <= 2
    interior = valid & low[src] & low[dst]
    chain = valid & (low[src] | low[dst])
    _, labels, r_chain, w_chain = spanning_forest_ex(
        EdgeList(src, dst, interior, n))
    csrc, cdst = labels[src], labels[dst]
    contracted = valid & ~interior
    f1, parent, _, _, r1 = scan_first_forest_ex(
        EdgeList(csrc, cdst, contracted, n))
    # F2 scans the simple complement of F1 in the CONTRACTED graph — the
    # same multigraph rule as sfs_certificate_ex (parallel copies of an F1
    # pair would waste forest slots F2 needs for real connectivity).
    dup = (parent[csrc] == cdst) | (parent[cdst] == csrc)
    f2, _, _, _, r2 = scan_first_forest_ex(
        EdgeList(csrc, cdst, contracted & ~f1 & ~dup, n))
    cert = compact_edges(edges, cap, keep=chain | f1 | f2)
    return cert, (r_chain, r1, r2), (w_chain, r1, r2)


# NOTE: the certificate-type table lives in the certificate registry
# (repro.core.certs) — builders here are plain functions the registry's
# descriptors reference; resolve by name via certs.certificate_builder.


def merge_certificates_incremental(own: EdgeList, f1_labels, f2_labels,
                                   recv: EdgeList):
    """Warm-start merge (beyond-paper SPerf iteration for the merge phases).

    The paper re-certifies the 4(n-1)-slot union from scratch every phase
    (2 forest passes x O(log V) Borůvka rounds over the full concat). But
    ``own`` is EXACTLY F1_a ∪ F2_a, and we already hold both forests'
    component labels, so:

      F1_new = F1_a ∪ forest(recv edges          | warm-start labels_1)
      F2_new = F2_a ∪ forest(recv − F1_delta     | warm-start labels_2)

    Each delta pass scans only recv's 2(n-1) slots (half the union), and
    hooking starts from the existing partition so the convergence-tested
    while loop pays only rounds ~ log(new merges), not log(V). Correctness:
    F1_a spans every A-component, and a forest of the label-contracted
    multigraph extends it to a spanning forest of the union (same argument
    for F2 on the F1-complement, using S_a − F1_a = F2_a exactly).

    Returns (merged_cert, f1_labels', f2_labels', (rounds_f1, rounds_f2)).
    """
    cap = certificate_capacity(own.n_nodes)
    with jax.named_scope("cert/forest1"):
        f1d, f1_labels, r1, _ = spanning_forest_ex(recv,
                                                   init_labels=f1_labels)
    with jax.named_scope("cert/forest2"):
        rest = EdgeList(recv.src, recv.dst, recv.mask & ~f1d, recv.n_nodes)
        f2d, f2_labels, r2, _ = spanning_forest_ex(rest,
                                                   init_labels=f2_labels)
    with jax.named_scope("cert/compact"):
        keep_recv = EdgeList(recv.src, recv.dst, recv.mask & (f1d | f2d),
                             recv.n_nodes)
        cert = compact_edges(concat_edges(own, keep_recv), cap)
    return cert, f1_labels, f2_labels, (r1, r2)
