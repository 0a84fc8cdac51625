"""Plain references the benchmark compares the engine's answers with.

They import nothing of the program under test and take nothing it made.

``Multigraph(src, dst, n).bridges(drop)`` is the one the runs use: the
bridges of the multigraph minus every copy of the pairs in ``drop``. Its
symmetric adjacency (a CSR with one entry a pair, holding the pair's
multiplicity) is built once, so the answers of one run, whose graphs differ
from one another by a few pairs, share it. Each answer takes a spanning
forest by breadth-first search (scipy), numbers it in preorder, and applies
the subtree rule: a tree edge ``(parent(v), v)`` is a bridge iff it has
multiplicity one and no other edge joins ``v``'s subtree, the preorder
interval ``[pre(v), pre(v) + size(v))``, to a vertex outside it. Every step
is a vectorised pass over the edges or over a level of the tree, so the
paper's 1e7-edge graphs take a second or two on a host.

``bridges_tarjan`` is the sequential low-link DFS (a copy of the program's
host oracle, kept so that the tests check ``Multigraph`` against an
independent algorithm on multigraphs).
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy.sparse import coo_array, csr_array
from scipy.sparse.csgraph import breadth_first_order, connected_components


def pair_keys(src, dst, n: int) -> np.ndarray:
    """One int64 key per unordered endpoint pair."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    return np.minimum(src, dst) * n + np.maximum(src, dst)


class Multigraph:
    """An undirected multigraph on ``n`` vertices; self-loops are dropped,
    since they are never bridges and cover nothing."""

    def __init__(self, src, dst, n: int):
        src = np.asarray(src, np.int32)
        dst = np.asarray(dst, np.int32)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        adj = coo_array((np.ones(2 * len(src)),
                         (np.concatenate([src, dst]),
                          np.concatenate([dst, src]))), shape=(n, n)).tocsr()
        adj.sum_duplicates()  # sorted rows, one entry a pair
        self.n = n
        self.indptr = adj.indptr.astype(np.int32)
        self.indices = adj.indices.astype(np.int32)
        self.mult = adj.data
        # one vertex of each component: a search from the vertex of most
        # links spans the largest component of a skewed graph, and what it
        # leaves is spanned apart
        hub = np.array([np.argmax(np.diff(self.indptr))], np.int32)
        parent = self._forest(self.indptr, self.indices, hub)
        self.reps = np.flatnonzero(parent[:n] == n).astype(np.int32)

    def _without(self, drop):
        """(indptr, indices, mult) with the entries of the pairs in
        ``drop`` removed, both ways round."""
        indptr, indices, mult = self.indptr, self.indices, self.mult
        if drop is None:
            return indptr, indices, mult
        a = np.asarray(drop[0], np.int64)
        b = np.asarray(drop[1], np.int64)
        gone = set()
        for x, y in zip(np.concatenate([a, b]).tolist(),
                        np.concatenate([b, a]).tolist()):
            lo, hi = indptr[x], indptr[x + 1]
            p = lo + int(np.searchsorted(indices[lo:hi], y))
            if p < hi and indices[p] == y:
                gone.add(p)
        if not gone:
            return indptr, indices, mult
        gone = np.array(sorted(gone))
        keep = np.ones(len(indices), bool)
        keep[gone] = False
        rows = np.searchsorted(indptr, gone, side="right") - 1
        lost = np.bincount(rows, minlength=self.n)
        indptr = indptr - np.concatenate([[0], np.cumsum(lost)])
        return indptr.astype(np.int32), indices[keep], mult[keep]

    def _forest(self, indptr, indices, reps) -> np.ndarray:
        """Parents in a spanning forest of the graph (indptr, indices),
        with a virtual root ``n`` above one vertex of each component; the
        root is its own parent. The search starts from ``reps``, vertices
        of distinct components."""
        n = self.n
        parent = _bfs_parents(indptr, indices, reps)
        cut = np.flatnonzero(parent[:n] < 0)
        if len(cut):
            # vertices no search from ``reps`` reached (cut off from them
            # by a drop): whole components, spanned apart
            deg = indptr[cut + 1] - indptr[cut]
            ends = np.cumsum(deg)
            take = np.repeat(indptr[cut] - (ends - deg), deg) + np.arange(
                ends[-1] if len(ends) else 0)
            local = np.full(n, -1, np.int32)
            local[cut] = np.arange(len(cut))
            sub_ptr = np.concatenate([[0], ends]).astype(np.int32)
            sub_ix = local[indices[take]]
            _, comp = connected_components(
                csr_array((np.ones(len(sub_ix)), sub_ix, sub_ptr),
                          shape=(len(cut), len(cut))),
                directed=True, connection="strong")
            sub = _bfs_parents(sub_ptr, sub_ix, _least_of_each(comp))
            parent[cut] = np.where(sub[:-1] == len(cut), n,
                                   cut[np.minimum(sub[:-1], len(cut) - 1)])
        return parent

    def bridges(self, drop=None) -> set[tuple[int, int]]:
        """Bridges, as (min, max) pairs, of the multigraph minus every copy
        of the unordered pairs ``drop = (xs, ys)``. A doubled link is no
        bridge."""
        n = self.n
        root = n
        indptr, indices, mult = self._without(drop)
        parent = self._forest(indptr, indices, self.reps)

        # depth by pointer jumping, then the tree's levels
        depth = (np.arange(n + 1) != root).astype(np.int32)
        jump = parent
        while (jump != root).any():
            depth = depth + depth[jump]
            jump = jump[jump]
        rest = np.argsort(depth, kind="stable")[1:]
        levels = np.split(rest, np.flatnonzero(np.diff(depth[rest])) + 1)

        size = np.ones(n + 1, np.int32)
        for level in reversed(levels):
            np.add.at(size, parent[level], size[level])
        # preorder: a child follows its parent and the subtrees of the
        # siblings ranked before it
        pre = np.zeros(n + 1, np.int32)
        for level in levels:
            level = level[np.argsort(pre[parent[level]], kind="stable")]
            p, s = parent[level], size[level]
            before = np.cumsum(s) - s
            new = np.concatenate([[True], p[1:] != p[:-1]])
            block_start = np.flatnonzero(new)[np.cumsum(new) - 1]
            pre[level] = pre[p] + 1 + before - before[block_start]

        # least and greatest preorder number reached from each vertex by
        # an edge other than its parent link, then over each subtree
        row = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
        to_parent = indices == parent[row]
        reach = pre[indices]
        low = pre.copy()
        high = pre.copy()
        rows = np.flatnonzero(np.diff(indptr))
        if len(rows):
            starts = indptr[rows]
            low[rows] = np.minimum(low[rows], np.minimum.reduceat(
                np.where(to_parent, n + 1, reach), starts))
            high[rows] = np.maximum(high[rows], np.maximum.reduceat(
                np.where(to_parent, -1, reach), starts))
        for level in reversed(levels):
            np.minimum.at(low, parent[level], low[level])
            np.maximum.at(high, parent[level], high[level])
        # each vertex's parent link: its multiplicity (0 for the root's)
        parent_mult = np.zeros(n + 1)
        parent_mult[row[to_parent]] = mult[to_parent]

        kids = rest[parent[rest] != root]
        cut = kids[(parent_mult[kids] == 1) & (low[kids] >= pre[kids])
                   & (high[kids] < pre[kids] + size[kids])]
        a = parent[cut]
        return {(int(min(x, y)), int(max(x, y)))
                for x, y in zip(a.tolist(), cut.tolist())}


    def bridges_each(self, drops) -> list[set[tuple[int, int]]]:
        """``bridges(drop)`` for each drop, in threads of their own: numpy
        and scipy release the interpreter lock for most of the work."""
        if not drops:
            return []
        with ThreadPoolExecutor(len(drops)) as pool:
            return list(pool.map(self.bridges, drops))


def _least_of_each(comp) -> np.ndarray:
    """The least vertex of each component, from per-vertex labels."""
    first = np.full(int(comp.max(initial=-1)) + 1, len(comp), np.int32)
    np.minimum.at(first, comp, np.arange(len(comp), dtype=np.int32))
    return first


def _bfs_parents(indptr, indices, reps) -> np.ndarray:
    """Breadth-first parents on ``n = len(indptr) - 1`` vertices from a
    virtual root ``n`` joined to ``reps``: the root is its own parent, and
    a vertex no search reached has -1."""
    n = len(indptr) - 1
    m = len(indices)
    aug = csr_array((np.ones(m + len(reps)),
                     np.concatenate([indices, reps]).astype(np.int32),
                     np.concatenate([indptr, [m + len(reps)]]).astype(
                         np.int32)),
                    shape=(n + 1, n + 1))
    _, pred = breadth_first_order(aug, n, directed=True,
                                  return_predecessors=True)
    parent = np.where(pred < 0, -1, pred).astype(np.int32)
    parent[n] = n
    return parent


def bridges(src, dst, n: int) -> set[tuple[int, int]]:
    """Bridges of the undirected multigraph (src[i], dst[i]) on n vertices,
    as (min, max) pairs. Self-loops are never bridges; a doubled link is
    not one either."""
    return Multigraph(src, dst, n).bridges()


def bridges_tarjan(src, dst, n: int) -> set[tuple[int, int]]:
    """Sequential low-link DFS over a CSR of the symmetrised edge list;
    skips only the entering edge's id, so a doubled link is no bridge."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    e = len(src)
    asrc = np.concatenate([src, dst])
    adst = np.concatenate([dst, src])
    eids = np.concatenate([np.arange(e), np.arange(e)])
    order = np.lexsort((adst, asrc))
    asrc, indices, eids = asrc[order], adst[order], eids[order]
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, asrc + 1, 1)
    indptr = np.cumsum(indptr)

    disc = np.full(n, -1, np.int64)
    low = np.zeros(n, np.int64)
    ptr = indptr[:-1].copy()
    out = set()
    timer = 0
    for root in range(n):
        if disc[root] != -1:
            continue
        stack = [(root, -1)]
        disc[root] = low[root] = timer
        timer += 1
        while stack:
            v, in_eid = stack[-1]
            if ptr[v] < indptr[v + 1]:
                w = int(indices[ptr[v]])
                eid = int(eids[ptr[v]])
                ptr[v] += 1
                if eid == in_eid:
                    continue
                if disc[w] == -1:
                    disc[w] = low[w] = timer
                    timer += 1
                    stack.append((w, eid))
                else:
                    low[v] = min(low[v], disc[w])
            else:
                stack.pop()
                if stack:
                    p, _ = stack[-1]
                    low[p] = min(low[p], low[v])
                    if low[v] > disc[p]:
                        out.add((min(p, v), max(p, v)))
    return out
