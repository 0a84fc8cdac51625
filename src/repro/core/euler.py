"""Parallel Euler tour machinery (pure JAX, fixed shapes).

The paper ends with a sequential DFS on machine C0. On TPU we replace it with
the classic PRAM pipeline, entirely in vectorized jnp ops:

  tree edges -> directed arcs -> circular adjacency successor -> Euler circuit
  -> cut at per-component roots -> Wyllie pointer-doubling list ranking
  -> discovery positions -> subtree = contiguous interval.

Everything below is O(A log A) work with A = 2 * tree_capacity arcs and lowers
to gathers/scatters/sorts that XLA maps onto TPU vector units.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.graph.datastructs import INF32, INT
from repro.kernels.segment_min.ops import segment_min


def _ceil_log2(n: int) -> int:
    return max(1, math.ceil(math.log2(max(n, 2))))


@partial(jax.jit, static_argnames=("n",))
def euler_tour(tsrc, tdst, tmask, labels, n: int):
    """Euler-tour positions for a rooted spanning forest.

    Args:
      tsrc, tdst, tmask: tree edge buffer [C] (must be a forest).
      labels: [n] component representative per vertex (roots: labels[v]==v).
      n: vertex count.

    Returns dict with:
      gpos:  [2C] global tour position per arc (arc 2i = src->dst of slot i,
             arc 2i+1 = reverse). Invalid arcs get INF32.
      disc:  [n] global discovery position per vertex (INF32 for isolated).
      total: [] total number of arc positions (== 2 * #tree edges).
    """
    C = tsrc.shape[0]
    A = 2 * C
    arc_src = jnp.stack([tsrc, tdst], axis=1).reshape(A)
    arc_dst = jnp.stack([tdst, tsrc], axis=1).reshape(A)
    amask = jnp.repeat(tmask, 2)
    # masked arcs sort last
    s_key = jnp.where(amask, arc_src, n)
    d_key = jnp.where(amask, arc_dst, n)
    order = jnp.lexsort((d_key, s_key))  # arc ids grouped by src, sorted by dst
    rank = jnp.zeros((A,), INT).at[order].set(jnp.arange(A, dtype=INT))

    sorted_src = s_key[order]
    vs = jnp.arange(n, dtype=INT)
    start = jnp.searchsorted(sorted_src, vs, side="left").astype(INT)
    end = jnp.searchsorted(sorted_src, vs, side="right").astype(INT)
    deg = end - start

    # successor in the Euler circuit: next(a=(u->v)) = next arc out of v after (v->u)
    rev = jnp.arange(A, dtype=INT) ^ 1
    v = arc_dst
    vd = jnp.maximum(deg[v], 1)
    r = rank[rev]
    nxt_pos = start[v] + (r - start[v] + 1) % vd
    SENT = jnp.int32(A)
    nxt = jnp.where(amask, order[nxt_pos], SENT)

    # cut each component's circuit at its root's first outgoing arc
    is_root = (labels == vs) & (deg > 0)
    head_arc = order[jnp.clip(start, 0, A - 1)]  # first arc out of each vertex
    is_head = jnp.zeros((A + 1,), bool)
    is_head = is_head.at[jnp.where(is_root, head_arc, A)].set(True, mode="drop")
    is_head = is_head.at[A].set(False)
    nxt = jnp.where(is_head[nxt], SENT, nxt)

    # Wyllie list ranking: dist[a] = #arcs after a in its list
    nxt_p = jnp.concatenate([nxt, jnp.array([SENT], INT)])
    dist = jnp.where(nxt_p != SENT, 1, 0).astype(INT)
    dist = dist.at[A].set(0)

    def body(_, state):
        d, nx = state
        d = d + d[nx]
        nx = nx[nx]
        return d, nx

    dist, _ = lax.fori_loop(0, _ceil_log2(A) + 1, body, (dist, nxt_p))
    dist = dist[:A]

    comp = labels[arc_src]  # component (root id) of each arc
    # list length per component root
    L = jnp.zeros((n,), INT).at[
        jnp.where(is_root, vs, n)
    ].set(jnp.where(is_root, dist[jnp.clip(head_arc, 0, A - 1)] + 1, 0), mode="drop")
    offset = jnp.concatenate([jnp.zeros((1,), INT), jnp.cumsum(L)[:-1]])
    tourpos = L[comp] - 1 - dist
    gpos = jnp.where(amask, tourpos + offset[comp], INF32)

    # discovery: an arc at tour position p *enters* its head at time p+1,
    # so disc[v] = 1 + min entering-arc position. Roots are discovered at the
    # position of their first outgoing arc (their component offset). This keeps
    # discovery times unique: root=offset, first child=offset+1, ...
    # repro.kernels.segment_min: the XLA scatter-min (its Pallas kernel
    # only where a caller asks) — INF32 for empty segments
    disc = segment_min(
        jnp.where(amask, gpos, INF32), jnp.where(amask, arc_dst, 0), n
    )
    disc = jnp.where(disc < INF32, disc + 1, disc)
    disc = jnp.where(is_root, offset, disc)
    disc = jnp.where(deg > 0, disc, INF32)  # isolated vertices
    total = jnp.sum(L)
    return {"gpos": gpos, "disc": disc, "total": total}


def build_sparse_table(values: jax.Array, reduce_fn, identity):
    """[K, P] sparse table for range reduce; fixed K = ceil_log2(P)+1 levels."""
    P = values.shape[0]
    K = _ceil_log2(P) + 1
    rows = [values]
    cur = values
    for k in range(1, K):
        half = 1 << (k - 1)
        shifted_idx = jnp.minimum(jnp.arange(P) + half, P - 1)
        cur = reduce_fn(cur, cur[shifted_idx])
        rows.append(cur)
    return jnp.stack(rows)  # [K, P]


def _floor_log2(x: jax.Array, max_bits: int) -> jax.Array:
    """Exact integer floor(log2(x)) for x >= 1, via power comparisons."""
    pows = (jnp.int32(1) << jnp.arange(max_bits, dtype=INT))  # [K]
    return jnp.sum(x[..., None] >= pows[None, :], axis=-1).astype(INT) - 1


def range_reduce(table: jax.Array, lo: jax.Array, hi: jax.Array, reduce_fn):
    """Reduce values over inclusive position range [lo, hi] per query."""
    K, P = table.shape
    length = jnp.maximum(hi - lo + 1, 1)
    k = jnp.clip(_floor_log2(length, K), 0, K - 1)
    left = table[k, jnp.clip(lo, 0, P - 1)]
    right_pos = jnp.clip(hi - (jnp.int32(1) << k) + 1, 0, P - 1)
    right = table[k, right_pos]
    return reduce_fn(left, right)
