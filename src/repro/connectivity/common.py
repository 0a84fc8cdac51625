"""Shared tour/interval plumbing for every connectivity query kind.

This is the common layer the per-kind analyses (bridges, articulation
points, 2ECC, bridge tree) are built from — refactored out of
``core/bridges_device.py`` so one certificate/tour pass serves the whole
failure-point family:

  1. F1 = spanning forest (Borůvka hooking), rest = non-tree edges.
  2. Euler tour of F1 -> per-vertex discovery positions; every subtree is a
     contiguous position interval.
  3. ntmin/ntmax[v] = min/max discovery position reachable from v via a
     non-tree edge (or disc[v] itself), scattered into tour-position space
     and closed under subtree range-reduce via one sparse table per extreme.

Per tree edge (child side) the range reduce yields smin/smax — the classic
``low``/``high`` values of the child subtree — from which each analysis
derives its own test (see device.py). Everything is mask-aware fixed-shape
jnp, so the whole family stays jit/vmap-compatible (DESIGN.md §Buffers).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.euler import build_sparse_table, euler_tour, range_reduce
from repro.core.forest import spanning_forest
from repro.graph.datastructs import INF32, INT, EdgeList


def tour_state(src, dst, mask, n: int) -> dict:
    """Rooted-forest tour state shared by all connectivity analyses.

    Returns a dict of fixed-shape arrays (C = slot capacity of the input
    buffer, positions run over P = 2C + 1):

      tree_mask bool[C]  spanning-forest slots
      nt_mask   bool[C]  non-tree (and non-self-loop) slots
      labels    int[C]   component representative per vertex
      is_root   bool[n]  tour root of its component (labels[v] == v)
      disc      int[n]   discovery position (INF32 for isolated vertices)
      vhi       int[n]   inclusive end of v's subtree position interval
      parent    int[C]   tree edge's parent endpoint (0 where ~tree_mask)
      child     int[C]   tree edge's child endpoint  (0 where ~tree_mask)
      lo, hi    int[C]   child subtree = positions (lo, hi]
      smin,smax int[C]   min/max non-tree reach of the child subtree
                         (the low/high values of the child)
      bridge    bool[C]  tree edge whose child subtree no non-tree edge
                         escapes — the paper's bridge criterion
    """
    # three phases under their own named scopes, each outside the nested
    # jit it wraps (DESIGN.md §Observability): the forest, the tour, and
    # everything that derives low/high values from the tour
    with jax.named_scope("tour/forest"):
        # the plain loop: with the filtered one the TPU compiler schedules
        # the Euler tour behind it slower than the loop saves (PERF.md §6)
        tree_mask, labels = spanning_forest(EdgeList(src, dst, mask, n),
                                            filtered=False)

    with jax.named_scope("tour/euler"):
        tour = euler_tour(
            jnp.where(tree_mask, src, 0),
            jnp.where(tree_mask, dst, 0),
            tree_mask,
            labels,
            n,
        )

    with jax.named_scope("tour/lowhigh"):
        return _low_high(src, dst, mask, n, tree_mask, labels, tour)


def _low_high(src, dst, mask, n: int, tree_mask, labels, tour) -> dict:
    """The ``tour/lowhigh`` phase of ``tour_state``: non-tree reach, its
    subtree range reductions, the bridge test, orientation and ``vhi``."""
    nt_mask = mask & ~tree_mask & (src != dst)
    gpos, disc = tour["gpos"], tour["disc"]

    # non-tree reach per vertex (include own discovery position)
    ep_v = jnp.concatenate([jnp.where(nt_mask, src, 0), jnp.where(nt_mask, dst, 0)])
    ep_w = jnp.concatenate([jnp.where(nt_mask, dst, 0), jnp.where(nt_mask, src, 0)])
    nt2 = jnp.concatenate([nt_mask, nt_mask])
    reach = jnp.where(nt2, disc[ep_w], INF32)
    ntmin = jax.ops.segment_min(reach, jnp.where(nt2, ep_v, 0), num_segments=n)
    ntmin = jnp.minimum(ntmin, disc)
    reach_max = jnp.where(nt2, disc[ep_w], -1)
    ntmax = jax.ops.segment_max(reach_max, jnp.where(nt2, ep_v, 0), num_segments=n)
    ntmax = jnp.maximum(ntmax, jnp.where(disc == INF32, -1, disc))

    # scatter per-vertex values into tour-position space.
    # disc values run up to `total` (<= 2C), so allocate 2C+1 positions.
    P = gpos.shape[0] + 1
    pos_of_v = jnp.where(disc == INF32, P, disc)  # drop isolated
    Rmin = jnp.full((P,), INF32, INT).at[pos_of_v].set(ntmin, mode="drop")
    Rmax = jnp.full((P,), -1, INT).at[pos_of_v].set(ntmax, mode="drop")
    Tmin = build_sparse_table(Rmin, jnp.minimum, INF32)
    Tmax = build_sparse_table(Rmax, jnp.maximum, -1)

    # per tree-edge subtree interval: down-arc at lo, up-arc at hi
    # => subtree(child) = { w : lo < disc[w] <= hi }
    down = jnp.minimum(gpos[0::2], gpos[1::2])
    up = jnp.maximum(gpos[0::2], gpos[1::2])
    lo = jnp.where(tree_mask, down, 0)
    hi = jnp.where(tree_mask, up, 1)
    smin = range_reduce(Tmin, lo + 1, hi, jnp.minimum)
    smax = range_reduce(Tmax, lo + 1, hi, jnp.maximum)
    bridge = tree_mask & (smin > lo) & (smax <= hi)

    # rooted orientation: the earlier-discovered endpoint is the parent
    # (discovery positions are unique inside a component)
    src_first = disc[src] <= disc[dst]
    parent = jnp.where(tree_mask, jnp.where(src_first, src, dst), 0)
    child = jnp.where(tree_mask, jnp.where(src_first, dst, src), 0)

    # per-vertex subtree end: child vertices inherit their parent edge's up
    # position; roots span their whole component (max up over its tree edges)
    vs = jnp.arange(n, dtype=INT)
    is_root = labels == vs
    vhi = jnp.full((n,), -1, INT).at[
        jnp.where(tree_mask, child, n)
    ].set(hi, mode="drop")
    comp_end = jax.ops.segment_max(
        jnp.where(tree_mask, up, -1),
        jnp.where(tree_mask, labels[src], 0),
        num_segments=n,
    )
    vhi = jnp.where(is_root, comp_end[labels], vhi)

    return {
        "tree_mask": tree_mask,
        "nt_mask": nt_mask,
        "labels": labels,
        "is_root": is_root,
        "disc": disc,
        "vhi": vhi,
        "parent": parent,
        "child": child,
        "lo": lo,
        "hi": hi,
        "smin": smin,
        "smax": smax,
        "bridge": bridge,
    }
