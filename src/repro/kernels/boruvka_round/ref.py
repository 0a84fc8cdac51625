"""Pure-jnp oracles for the fused connectivity-round reductions.

These reproduce, op for op, the three-pass lax sequences the fused Pallas
kernels replace (core/forest.py pre-fusion): the Borůvka hooking round's
back-to-back ``segment_min`` over both endpoint labels, and the scan-first
search round's frontier-candidate mask + lexicographic (parent, edge-slot)
pair of ``segment_min`` passes. The fused kernels are property-tested for
bit-identical outputs against these functions (tests/test_kernels.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.graph.datastructs import INF32, INT


def boruvka_round_ref(src, dst, mask, labels, num_segments: int):
    """Per-component minimum cross-edge slot, both endpoints at once.

    src, dst: int32[E]; mask: bool[E]; labels: int32[n].
    Returns int32[num_segments]: for each component label, the minimum edge
    index whose endpoints live in different components and at least one of
    them in this component (INF32 where no such edge exists). This is the
    Borůvka hooking reduction — distinct edge indices act as distinct
    weights.
    """
    lu, lv, cross = boruvka_cross(src, dst, mask, labels)
    eidx = jnp.arange(src.shape[0], dtype=INT)
    return boruvka_pick(eidx, cross, lu, lv, num_segments)


def boruvka_cross(src, dst, mask, labels):
    """The gather half of a Borůvka round: both endpoint labels of every
    slot, ``(lu, lv)``, and which slots hold a cross edge (masked in, no
    self-loop, endpoints in different components)."""
    lu = labels[src]
    lv = labels[dst]
    return lu, lv, mask & (src != dst) & (lu != lv)


def boruvka_pick(keys, cross, lu, lv, num_segments: int):
    """The scatter-min half of a Borůvka round: per component label, the
    minimum key among the cross slots with an endpoint in it (INF32 where
    none). ``keys`` are the slots' edge indices: ``arange(E)`` over a whole
    buffer, the original indices over a compacted one."""
    key = jnp.where(cross, keys, INF32)
    best_u = jax.ops.segment_min(key, lu, num_segments=num_segments)
    best_v = jax.ops.segment_min(key, lv, num_segments=num_segments)
    return jnp.minimum(best_u, best_v).astype(INT)


def frontier_round_ref(src, dst, mask, frontier, visited, num_segments: int):
    """One scan-first-search (BFS-layer) hooking round, fused.

    src, dst: int32[E]; mask: bool[E]; frontier, visited: bool[n].
    Returns ``(best_p, best_e)`` int32[num_segments] pairs: for each newly
    reachable vertex w (unvisited, adjacent to the frontier), ``best_p[w]``
    is its minimum-id frontier neighbor and ``best_e[w]`` the minimum edge
    slot connecting w to that neighbor (ties on parallel edges). Both INF32
    where w is not newly reached. The lexicographic (parent, slot) choice is
    what makes the hooked forest a genuine scan-first-search forest
    (DESIGN.md §Connectivity).
    """
    e = src.shape[0]
    eidx = jnp.arange(e, dtype=INT)
    valid = mask & (src != dst)
    us = jnp.concatenate([src, dst])
    ws = jnp.concatenate([dst, src])
    e2 = jnp.concatenate([eidx, eidx])
    v2 = jnp.concatenate([valid, valid])
    cand = v2 & frontier[us] & ~visited[ws]
    best_p = jax.ops.segment_min(
        jnp.where(cand, us, INF32), jnp.where(cand, ws, 0),
        num_segments=num_segments)
    sel = cand & (us == best_p[ws])
    best_e = jax.ops.segment_min(
        jnp.where(sel, e2, INF32), jnp.where(sel, ws, 0),
        num_segments=num_segments)
    return best_p.astype(INT), best_e.astype(INT)
