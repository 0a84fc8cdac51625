"""Spanning forest in pure JAX — the TPU-native replacement for the paper's
sequential DFS + union-find certificate pass.

Borůvka-style minimum-edge hooking with pointer-doubling contraction:

  repeat O(log V) times:
    1. every component picks its minimum-index incident cross edge
       (the ``boruvka_round`` reduction over both endpoints' component
       labels)
    2. components hook along the picked edge; mutual 2-cycles (the only
       possible cycles under distinct edge keys) are broken by id order
    3. labels are flattened by pointer doubling

Each selected edge that survives hooking joins the forest. Distinct edge
indices act as distinct weights, so the classic Borůvka argument gives an
acyclic, component-spanning edge set.

Both hooking loops dispatch their per-round edge scan through
``repro.kernels.boruvka_round`` (DESIGN.md §Kernels): the XLA path on
every platform, with ``use_pallas=True`` selecting the Pallas kernel
(compiled on a TPU, interpreted elsewhere) for parity checks. The knob
threads through every public entry point here, so certificates — and
through the certificate registry, every engine substrate — take the same
path.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.graph.datastructs import INF32, INT, EdgeList
from repro.kernels.boruvka_round.ops import boruvka_round, frontier_round
from repro.kernels.segment_min.ops import segment_min


def _ceil_log2(n: int) -> int:
    return max(1, math.ceil(math.log2(max(n, 2))))


def _shortcut(parent: jax.Array, steps: int) -> jax.Array:
    """Full pointer-doubling path compression."""
    def body(_, p):
        return p[p]
    return lax.fori_loop(0, steps, body, parent)


@partial(jax.jit, static_argnames=("n", "use_pallas"))
def _forest_impl(src, dst, mask, n: int, init_labels=None,
                 use_pallas: bool = False):
    """Borůvka hooking. ``init_labels`` warm-starts from an existing
    partition (path-compressed component labels): the returned forest then
    contains only edges that merge ACROSS the initial components — the
    incremental-merge primitive (see certificate.merge_certificates_
    incremental). Rounds are data-dependent (convergence-tested while loop,
    bounded by log2(n)+2); the round count is returned (the certificate
    builders report it as ``certificate/rounds``). ``use_pallas`` selects the per-round edge-scan backend
    (``kernels.kernel_path``)."""
    E = src.shape[0]
    log_n = _ceil_log2(n)
    # Self-loops are never cross edges; masked slots never participate.
    valid = mask & (src != dst)

    def cond(state):
        _, _, changed, rounds = state
        return changed & (rounds < log_n + 2)

    def body(state):
        labels, forest, _, rounds = state
        # one round: tombstone mask + both label gathers + dual-endpoint
        # segment-min over the edge buffer
        with jax.named_scope("kernel/round/boruvka"):
            best = boruvka_round(src, dst, valid, labels, n,
                                 use_pallas=use_pallas)
        has = best < INF32
        e = jnp.where(has, best, 0)
        # O(n) gathers of the chosen edges' endpoint labels — the only
        # post-reduction label reads (nothing E-sized after the round)
        cu = labels[src[e]]
        cv = labels[dst[e]]
        comp = jnp.arange(n, dtype=INT)
        other = jnp.where(cu == comp, cv, cu)
        prop = jnp.where(has, other, comp)
        # distinct edge keys => only 2-cycles possible; break them by id order
        mutual = prop[prop] == comp
        hook = has & (~mutual | (comp < prop))
        parent = jnp.where(hook, prop, comp)
        chosen = jnp.where(hook, e, E)  # E is an out-of-range sentinel
        forest = forest.at[chosen].set(True, mode="drop")
        parent = _shortcut(parent, log_n)
        labels = parent[labels]
        changed = jnp.any(hook)
        return labels, forest, changed, rounds + 1

    labels0 = (jnp.arange(n, dtype=INT) if init_labels is None
               else init_labels.astype(INT))
    forest0 = jnp.zeros((E,), bool)
    labels, forest, _, rounds = lax.while_loop(
        cond, body, (labels0, forest0, jnp.bool_(True), jnp.int32(0))
    )
    return forest, labels, rounds


def spanning_forest(edges: EdgeList, use_pallas: bool = False):
    """Returns (forest_mask bool[E], labels int32[n]).

    ``forest_mask`` selects a spanning forest of the masked subgraph;
    ``labels`` maps each vertex to its connected-component representative.
    """
    forest, labels, _ = spanning_forest_ex(edges, use_pallas=use_pallas)
    return forest, labels


def spanning_forest_ex(edges: EdgeList, init_labels=None,
                       use_pallas: bool = False):
    """(forest_mask, labels, rounds_used); optional warm-start labels.

    With ``init_labels`` the forest spans only the *contraction* of the
    initial partition by the edge set (edges internal to an initial
    component are never selected)."""
    return _forest_impl(edges.src, edges.dst, edges.mask, edges.n_nodes,
                        init_labels=init_labels, use_pallas=use_pallas)


def connected_components(edges: EdgeList, use_pallas: bool = False):
    """Component labels only (same hooking machinery)."""
    _, labels, _ = spanning_forest_ex(edges, use_pallas=use_pallas)
    return labels


# --------------------------------------------------------- scan-first search
@partial(jax.jit, static_argnames=("n", "use_pallas"))
def _sfs_impl(src, dst, mask, n: int, comp_labels,
              use_pallas: bool = False):
    """Level-synchronous frontier hooking: a scan-first-search (BFS-layer)
    spanning forest, rooted at each component's minimum vertex id.

    Per round every frontier vertex scans its incident edges at once and each
    newly reached vertex hooks to its MINIMUM-id frontier neighbor (ties on
    parallel edges broken by minimum edge slot). That parent choice is
    realizable by a sequential scan-first search that scans each BFS layer in
    increasing vertex id, so the result is a genuine SFS forest in the
    Cheriyan–Kao–Thurimella sense — the property that makes the F1 ∪ F2 pair
    a 2-VERTEX-connectivity certificate (DESIGN.md §Connectivity), which the
    arbitrary-forest Borůvka pair above provably is not.

    Rounds are data-dependent (one per BFS layer, O(diameter), convergence-
    tested while loop bounded by n); the round count is returned.
    Returns (forest bool[E], parent int[n], level int[n],
    root int[n], rounds).
    """
    E = src.shape[0]
    vs = jnp.arange(n, dtype=INT)
    valid = mask & (src != dst)

    # roots: each component's minimum vertex id (one scan origin per
    # component — a valid sequential scan order starts there)
    minid = segment_min(vs, comp_labels, n, use_pallas=use_pallas)
    root = minid[comp_labels]
    is_root = root == vs

    def cond(state):
        _, _, _, _, _, changed, rounds = state
        return changed & (rounds < n + 1)

    def body(state):
        visited, level, parent, forest, frontier, _, rounds = state
        # frontier round: candidate mask + both arc orientations + the
        # lexicographic (parent id, edge slot) reduction over the raw edge
        # buffer. best_p = minimum-id frontier neighbor per newly reached
        # vertex; best_e = minimum edge slot to that neighbor (ties on
        # parallel edges).
        with jax.named_scope("kernel/round/sfs"):
            best_p, best_e = frontier_round(src, dst, valid, frontier,
                                            visited, n,
                                            use_pallas=use_pallas)
        newly = best_p < INF32
        parent = jnp.where(newly, best_p.astype(INT), parent)
        level = jnp.where(newly, rounds + 1, level)
        forest = forest.at[jnp.where(newly, best_e, E)].set(True, mode="drop")
        return (visited | newly, level, parent, forest, newly,
                jnp.any(newly), rounds + 1)

    level0 = jnp.where(is_root, 0, INF32).astype(INT)
    state = (is_root, level0, vs, jnp.zeros((E,), bool), is_root,
             jnp.bool_(True), jnp.int32(0))
    visited, level, parent, forest, _, _, rounds = lax.while_loop(
        cond, body, state)
    return forest, parent, level, root, rounds


def scan_first_forest(edges: EdgeList, use_pallas: bool = False):
    """Returns (forest_mask bool[E], parent int[n], level int[n]).

    The level-synchronous frontier-hooking primitive: a BFS-layer scan-first
    search forest of the masked subgraph. `level[v]` is v's BFS layer (roots
    at 0), `parent[v]` the hooked predecessor (roots and isolated vertices
    point at themselves). Component structure matches `spanning_forest` —
    only the tree SHAPE differs (layered, which is what makes the forest
    pair a vertex-connectivity certificate)."""
    f, p, lvl, _, _ = scan_first_forest_ex(edges, use_pallas=use_pallas)
    return f, p, lvl


def scan_first_forest_ex(edges: EdgeList, use_pallas: bool = False):
    """(forest_mask, parent, level, root_labels, rounds_used).

    `root_labels[v]` is the component's canonical minimum vertex id — the
    same partition as `connected_components`, canonicalized."""
    _, labels, _ = spanning_forest_ex(edges, use_pallas=use_pallas)
    return _sfs_impl(edges.src, edges.dst, edges.mask, edges.n_nodes,
                     labels, use_pallas=use_pallas)
