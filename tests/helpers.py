import networkx as nx
import numpy as np

# Shape buckets: property tests draw (n, edge-capacity) from this fixed set so
# jit caches hit instead of recompiling per hypothesis example (1-core box).
SHAPE_BUCKETS = [(16, 64), (48, 192), (96, 384)]


def bucketed_graph(seed: int, simple: bool = True):
    """Random graph with shapes drawn from SHAPE_BUCKETS (padded capacity)."""
    from repro.graph import generators as gen
    from repro.graph.datastructs import EdgeList

    rng = np.random.default_rng(seed)
    n, cap = SHAPE_BUCKETS[seed % len(SHAPE_BUCKETS)]
    m = int(rng.integers(1, cap))
    if simple:
        src, dst = gen.random_graph(n, m, seed=seed)
    else:
        m = max(m, 2)
        src = rng.integers(0, n, m).astype(np.int32)
        dst = rng.integers(0, n, m).astype(np.int32)
    if len(src) == 0:
        src = np.array([0], np.int32)
        dst = np.array([1 % n], np.int32)
    el = EdgeList.from_arrays(src, dst, n, capacity=cap)
    return src, dst, n, el


def nx_bridges(src, dst, n) -> set[tuple[int, int]]:
    G = nx.Graph()
    G.add_nodes_from(range(n))
    G.add_edges_from(zip(np.asarray(src).tolist(), np.asarray(dst).tolist()))
    return set((min(u, v), max(u, v)) for u, v in nx.bridges(G))


def to_pair_set(edgelist) -> set[tuple[int, int]]:
    s, d = edgelist.to_numpy()
    return set((int(min(a, b)), int(max(a, b))) for a, b in zip(s, d))


def to_graph(src, dst, n) -> nx.Graph:
    G = nx.Graph()
    G.add_nodes_from(range(n))
    G.add_edges_from(zip(np.asarray(src).tolist(), np.asarray(dst).tolist()))
    return G


def blob_chain(blobs: int, size: int, m: int, seed: int):
    """``m`` random links inside ``blobs`` blobs of ``size`` vertices, the
    blobs chained by one link each, sorted by (src, dst): the edge order
    of a sorted edge list, under which the min-index Borůvka leaves a
    handful of cross edges for its late rounds. Returns (src, dst, n)."""
    rng = np.random.default_rng(seed)
    b = rng.integers(0, blobs, m)
    src = np.concatenate([b * size + rng.integers(0, size, m),
                          np.arange(blobs - 1) * size + size - 1])
    dst = np.concatenate([b * size + rng.integers(0, size, m),
                          np.arange(1, blobs) * size + size - 2])
    order = np.lexsort((dst, src))
    return (src[order].astype(np.int32), dst[order].astype(np.int32),
            blobs * size)


def kronecker_like(scale: int, m: int, seed: int):
    """``m`` Graph500-style Kronecker tuples (A = 0.57, B = C = 0.19) on
    2^scale permuted vertices, loops and repeats kept: skewed degrees, so
    hub components keep many cross edges until the last hooking round.
    Returns (src, dst, n)."""
    rng = np.random.default_rng(seed)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for _ in range(scale):
        r = rng.random(m)
        src = 2 * src + (r > 0.76)
        dst = 2 * dst + (((r > 0.57) & (r <= 0.76)) | (r > 0.95))
    perm = rng.permutation(1 << scale)
    return (perm[src].astype(np.int32), perm[dst].astype(np.int32),
            1 << scale)


def boruvka_reference(src, dst, mask, n, init_labels=None):
    """Plain numpy min-index Borůvka, round for round as ``core/forest.py``
    specifies it: every component picks its minimum-index incident cross
    edge, mutual picks hook the smaller label under the larger, labels are
    fully compressed, and the loop ends after the first round that hooks
    nothing (counted) or at ``ceil(log2 n) + 2`` rounds.

    Returns ``(forest bool[E], labels int32[n], rounds, live)``, ``live``
    the cross edges each round found."""
    src, dst = np.asarray(src), np.asarray(dst)
    valid = np.asarray(mask) & (src != dst)
    labels = (np.arange(n) if init_labels is None
              else np.asarray(init_labels).astype(np.int64))
    forest = np.zeros(len(src), bool)
    log_n = max(1, int(np.ceil(np.log2(max(n, 2)))))
    comp, eidx = np.arange(n), np.arange(len(src))
    big = np.iinfo(np.int64).max
    live = []
    while len(live) < log_n + 2:
        lu, lv = labels[src], labels[dst]
        cross = valid & (lu != lv)
        live.append(int(cross.sum()))
        best = np.full(n, big)
        np.minimum.at(best, lu[cross], eidx[cross])
        np.minimum.at(best, lv[cross], eidx[cross])
        has = best < big
        e = np.where(has, best, 0)
        cu, cv = labels[src[e]], labels[dst[e]]
        prop = np.where(has, np.where(cu == comp, cv, cu), comp)
        hook = has & ((prop[prop] != comp) | (comp < prop))
        forest[e[hook]] = True
        parent = np.where(hook, prop, comp)
        for _ in range(log_n):
            parent = parent[parent]
        labels = parent[labels]
        if not hook.any():
            break
    return forest, labels.astype(np.int32), len(live), live
