"""Spanning forest in pure JAX — the TPU-native replacement for the paper's
sequential DFS + union-find certificate pass.

Borůvka-style minimum-edge hooking with pointer-doubling contraction:

  repeat O(log V) times:
    1. every component picks its minimum-index incident cross edge
       (the ``boruvka_round`` reduction over both endpoints' component
       labels)
    2. components hook along the picked edge; mutual 2-cycles (the only
       possible cycles under distinct edge keys) are broken by id order
    3. labels are flattened by pointer doubling, until no label moves
       (at most ceil(log2 V) steps)

Each selected edge that survives hooking joins the forest. Distinct edge
indices act as distinct weights, so the classic Borůvka argument gives an
acyclic, component-spanning edge set.

The XLA loop runs filtered (DESIGN.md §Kernels): each round counts its
cross edges before its scatter-mins, stops there when none is left, and
once few are left finishes on a compacted buffer of just those. Under
``vmap``, and where a caller asks for it (``filtered=False``), the plain
loop runs instead.

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
from repro.kernels.boruvka_round.ops import (
    boruvka_cross,
    boruvka_pick,
    boruvka_round,
    frontier_round,
)
from repro.kernels.segment_min.ops import segment_min


def _ceil_log2(n: int) -> int:
    return max(1, math.ceil(math.log2(max(n, 2))))


def _shortcut(parent: jax.Array, steps: int) -> jax.Array:
    """Full pointer-doubling path compression: ``p = p[p]`` until a step
    moves no label, at most ``steps`` times (enough for any path shorter
    than 2^steps). The answer is the fixed-step loop's, bit for bit: once
    a step moves nothing, every later one would move nothing either.
    Under the scope ``kernel/hook/shortcut``."""
    def cond(state):
        i, _ = state
        return i < steps

    def body(state):
        i, p = state
        q = p[p]
        return jnp.where(jnp.all(q == p), steps, i + 1), q

    with jax.named_scope("kernel/hook/shortcut"):
        _, parent = lax.while_loop(cond, body, (jnp.int32(0), parent))
    return parent


def narrow_slots(e: int) -> int:
    """Slots of the buffer the filtered loop compacts an ``e``-slot buffer
    into, once no more cross edges than that are left (none below 2^10
    slots: there the loop only stops at zero)."""
    return e >> 10


@partial(jax.jit, static_argnames=("n", "use_pallas", "filtered"))
def _forest_impl(src, dst, mask, n: int, init_labels=None,
                 use_pallas: bool = False, filtered: bool = True):
    """Borůvka hooking. ``init_labels`` warm-starts from an existing
    partition (path-compressed component labels): the returned forest then
    contains only edges that merge ACROSS the initial components — the
    incremental-merge primitive (see certificate.merge_certificates_
    incremental). Rounds are data-dependent (convergence-tested, bounded by
    log2(n)+2); the count is returned with the count of rounds whose
    scatter-min ran over the whole buffer (the certificate builders report
    them as ``certificate/rounds`` and ``certificate/full_rounds``).
    ``use_pallas`` selects the per-round edge-scan backend
    (``kernels.kernel_path``).

    The XLA path runs the filtered loop: a round that finds no cross edge
    stops before its scatter-min, and once at most ``narrow_slots(E)`` are
    left the rounds go on over a buffer of just those. Under ``vmap`` (the
    scheduler's batches), and where the caller asks for it
    (``filtered=False``), it runs the plain loop. The forest, labels and
    round count are the plain loop's, bit for bit."""
    limit = _ceil_log2(n) + 2
    # Self-loops are never cross edges; masked slots never participate.
    valid = mask & (src != dst)
    labels = (jnp.arange(n, dtype=INT) if init_labels is None
              else init_labels.astype(INT))
    if use_pallas or not filtered:
        forest, labels, rounds = _plain_loop(src, dst, valid, labels, n,
                                             limit, use_pallas)
        return forest, labels, rounds, rounds
    return _xla_loop(n, limit)(src, dst, valid, labels)


def _xla_loop(n: int, limit: int):
    """(src, dst, valid, labels) -> (forest, labels, rounds, full-width
    rounds): the filtered loop, or under ``vmap`` the plain loop, since
    there a data-dependent branch becomes a select that runs both of its
    sides for every row."""
    @jax.custom_batching.custom_vmap
    def loop(src, dst, valid, labels):
        return _filtered_loop(src, dst, valid, labels, n, limit)

    @loop.def_vmap
    def batched(axis_size, in_batched, *args):
        def plain(*args):
            forest, labels, rounds = _plain_loop(*args, n, limit, False)
            return forest, labels, rounds, rounds

        axes = tuple(0 if b else None for b in in_batched)
        return (jax.vmap(plain, in_axes=axes, axis_size=axis_size)(*args),
                (True,) * 4)

    return loop


def _hook(labels, forest, best, src, dst, n: int):
    """Hook every component along its picked edge (``best``, an edge index
    or INF32) and flatten the labels: (labels, forest, hooked any). The
    O(n) work of a round, under the scope ``kernel/hook/boruvka``."""
    with jax.named_scope("kernel/hook/boruvka"):
        E = forest.shape[0]
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
        parent = _shortcut(parent, _ceil_log2(n))
        return parent[labels], forest, jnp.any(hook)


def _plain_loop(src, dst, valid, labels, n: int, limit: int,
                use_pallas: bool):
    """Every round scans the whole buffer, the last one (which hooks
    nothing) included."""
    forest = jnp.zeros(src.shape, bool)

    def cond(state):
        _, _, changed, rounds = state
        return changed & (rounds < limit)

    def body(state):
        labels, forest, _, rounds = state
        # one round: tombstone mask + both label gathers + dual-endpoint
        # segment-min over the edge buffer
        with jax.named_scope("kernel/round/boruvka"):
            best = boruvka_round(src, dst, valid, labels, n,
                                 use_pallas=use_pallas)
        labels, forest, changed = _hook(labels, forest, best, src, dst, n)
        return labels, forest, changed, rounds + 1

    labels, forest, _, rounds = lax.while_loop(
        cond, body, (labels, forest, jnp.bool_(True), jnp.int32(0)))
    return forest, labels, rounds


def _rounds_over(slot_src, slot_dst, slot_valid, keys, src, dst, labels,
                 forest, rounds, n: int, limit: int, stop_at: int,
                 scope: str):
    """Rounds over one buffer of slots (the whole edge buffer, or the
    compacted one: ``keys`` are the slots' edge indices, ``src``/``dst``
    the whole buffer's endpoints). Each round's gathers, done at the end
    of the round before, count its cross edges; the loop stops, before
    the scatter-min, at no more than ``stop_at`` of them.
    Returns (labels, forest, rounds, cross, live)."""
    def scan(labels):
        with jax.named_scope(scope):
            lu, lv, cross = boruvka_cross(slot_src, slot_dst, slot_valid,
                                          labels)
            return lu, lv, cross, jnp.sum(cross, dtype=INT)

    def cond(state):
        rounds, live = state[2], state[-1]
        return (live > stop_at) & (rounds < limit)

    def body(state):
        labels, forest, rounds, lu, lv, cross, _ = state
        with jax.named_scope(scope):
            best = boruvka_pick(keys, cross, lu, lv, n)
        labels, forest, _ = _hook(labels, forest, best, src, dst, n)
        return (labels, forest, rounds + 1, *scan(labels))

    labels, forest, rounds, _, _, cross, live = lax.while_loop(
        cond, body, (labels, forest, rounds, *scan(labels)))
    return labels, forest, rounds, cross, live


def _compact(cross, slots: int):
    """The indices of the first ``slots`` cross slots, in order (one
    cumsum, then a binary search per output slot), and which output slots
    hold one."""
    pos = jnp.cumsum(cross, dtype=INT)
    at = jnp.searchsorted(pos, jnp.arange(1, slots + 1, dtype=INT))
    held = at < cross.shape[0]
    return jnp.where(held, at, 0), held


def _filtered_loop(src, dst, valid, labels, n: int, limit: int):
    """Full-width rounds while more than ``narrow_slots(E)`` cross edges
    are left, then narrow rounds over a compaction of those. An edge
    inside one component never crosses again (labels only merge), and the
    keys stay the edge indices, so every round picks what the plain loop
    picks. Returns (forest, labels, rounds, full-width rounds)."""
    E = src.shape[0]
    k = narrow_slots(E)
    labels, forest, full, cross, live = _rounds_over(
        src, dst, valid, jnp.arange(E, dtype=INT), src, dst, labels,
        jnp.zeros((E,), bool), jnp.int32(0), n, limit, k,
        "kernel/round/boruvka")

    def narrow(state):
        labels, forest, rounds, cross, _ = state
        with jax.named_scope("kernel/narrow/compact"):
            slots, held = _compact(cross, k)
            slot_src, slot_dst = src[slots], dst[slots]
        labels, forest, rounds, _, live = _rounds_over(
            slot_src, slot_dst, held, slots, src, dst, labels, forest,
            rounds, n, limit, 0, "kernel/narrow/boruvka")
        return labels, forest, rounds, live

    def done(state):
        labels, forest, rounds, _, live = state
        return labels, forest, rounds, live

    rounds = full
    if k:  # with no narrow slots the loop above ran to no cross edge
        labels, forest, rounds, live = lax.cond(
            (live > 0) & (full < limit), narrow, done,
            (labels, forest, full, cross, live))
    # the round that found no cross edge counts, as in the plain loop
    rounds = rounds + ((live == 0) & (rounds < limit)).astype(INT)
    return forest, labels, rounds, full


def spanning_forest(edges: EdgeList, use_pallas: bool = False,
                    filtered: bool = True):
    """Returns (forest_mask bool[E], labels int32[n]).

    ``forest_mask`` selects a spanning forest of the masked subgraph;
    ``labels`` maps each vertex to its connected-component representative.
    """
    forest, labels, _, _ = spanning_forest_ex(edges, use_pallas=use_pallas,
                                              filtered=filtered)
    return forest, labels


def spanning_forest_ex(edges: EdgeList, init_labels=None,
                       use_pallas: bool = False, filtered: bool = True):
    """(forest_mask, labels, rounds_used, full_rounds); optional
    warm-start labels. ``full_rounds`` counts the rounds whose scatter-min
    ran over the whole buffer (all of them on the plain loop, which
    ``filtered=False`` asks for).

    With ``init_labels`` the forest spans only the *contraction* of the
    initial partition by the edge set (edges internal to an initial
    component are never selected)."""
    return _forest_impl(edges.src, edges.dst, edges.mask, edges.n_nodes,
                        init_labels=init_labels, use_pallas=use_pallas,
                        filtered=filtered)


def connected_components(edges: EdgeList, use_pallas: bool = False):
    """Component labels only (same hooking machinery)."""
    _, labels, _, _ = spanning_forest_ex(edges, use_pallas=use_pallas)
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
    _, labels, _, _ = spanning_forest_ex(edges, use_pallas=use_pallas)
    return _sfs_impl(edges.src, edges.dst, edges.mask, edges.n_nodes,
                     labels, use_pallas=use_pallas)
