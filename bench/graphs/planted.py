"""The paper's dense test graph: a chain of dense random blobs joined by
single links, the planted bridges (a copy of the program's
``repro.graph.generators.planted_bridge_graph``, which the tests check it
against). Config keys: ``n_nodes``, ``n_edges``, ``n_bridges``."""
from __future__ import annotations

import numpy as np

from bench.graph import Graph


def planted_bridge_graph(n: int, m: int, n_bridges: int, seed: int = 0):
    """Connected simple graph = chain of (n_bridges+1) dense random blobs
    joined by single edges (the planted bridges). Returns
    (src, dst, bridges_set)."""
    rng = np.random.default_rng(seed)
    k = n_bridges + 1
    sizes = np.full(k, n // k)
    sizes[: n % k] += 1
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    srcs, dsts = [], []
    m_inner = max(m - n_bridges, 0)
    for b in range(k):
        nb, s0 = int(sizes[b]), int(starts[b])
        mb = m_inner // k
        if nb >= 2:
            # a cycle through the blob keeps it connected and bridgeless
            perm = rng.permutation(nb) + s0
            srcs.append(perm[:-1]); dsts.append(perm[1:])
            srcs.append(perm[-1:]); dsts.append(perm[:1])
            if nb >= 3 and mb > 0:
                u = rng.integers(0, nb, mb) + s0
                v = rng.integers(0, nb, mb) + s0
                keep = u != v
                srcs.append(u[keep]); dsts.append(v[keep])
    bridges = set()
    for b in range(k - 1):
        u = int(starts[b] + rng.integers(0, sizes[b]))
        v = int(starts[b + 1] + rng.integers(0, sizes[b + 1]))
        srcs.append(np.array([u])); dsts.append(np.array([v]))
        bridges.add((min(u, v), max(u, v)))
    src = np.concatenate(srcs).astype(np.int32)
    dst = np.concatenate(dsts).astype(np.int32)
    # dedup to a simple graph (the planted bridges are unique by
    # construction)
    key = np.minimum(src, dst).astype(np.int64) * n + np.maximum(src, dst)
    _, idx = np.unique(key, return_index=True)
    return src[idx], dst[idx], bridges


def generate(cfg: dict, seed: int) -> Graph:
    n = int(cfg["n_nodes"])
    src, dst, planted = planted_bridge_graph(n, int(cfg["n_edges"]),
                                             int(cfg["n_bridges"]), seed=seed)
    critical = np.array(sorted(planted), np.int32).reshape(-1, 2)
    return Graph(n, src, dst, critical)
