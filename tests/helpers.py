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
