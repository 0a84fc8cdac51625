"""Pallas TPU kernels: the connectivity-round reductions as one masked min.

The Borůvka hooking loop (core/forest.py) runs O(log V) rounds per
certificate pass, and each round reduces over the whole edge buffer: every
component picks its minimum incident cross edge, over BOTH endpoints'
labels. The kernels here do that reduction as one dense masked min over
(edge tile x segment tile) blocks, the ``kernels/segment_min`` scheme:

    grid = (num_segment_tiles, num_edge_tiles)        # output-stationary
    block (j, i): acc[s] = min(acc[s], min over edges t of
                              where(lu[t] == s  OR  lv[t] == s, key[t], INF))

Both endpoints' reductions happen in the SAME (edge × segment) compare on
the VPU, so the two back-to-back ``segment_min`` passes of the oracle
collapse into one masked min, and the tombstone/validity mask is applied
in-register. The output tile stays resident in VMEM across the inner edge
loop; every segment tile re-reads the edge arrays, so one round streams
``⌈n / SEG_BLOCK⌉`` passes over them.

What the kernels read: Mosaic lowers only 2-D gathers, so the per-edge
gathers of the vertex arrays (``labels[src]``, ``labels[dst]``; the
frontier and visited bits of both endpoints) run in XLA ahead of the
``pallas_call``, and the kernels stream the gathered per-edge int32 arrays:

  * ``boruvka_round``: ``lu = labels[src]``, ``lv = labels[dst]`` and the
    mask. A self-loop has ``lu == lv``, so ``lu != lv`` is the whole cross
    test;
  * ``frontier_round``: ``src``, ``dst`` and a 2-bit candidate code per
    edge (bit 0: the forward arc src→dst leaves the frontier for an
    unvisited vertex; bit 1: the reverse arc). The reduction is
    LEXICOGRAPHIC on (parent id, edge slot) — two accumulators merged per
    block — so the parent choice and the tie-broken tree slot come out of
    one pass instead of two dependent segment-mins.

Block shapes: XLA tiles a 1-D int32 array in HBM as ``T(1024)``, and
Mosaic refuses a 1-D block whose tiling differs, so edge and segment
blocks are 1024 long (shared with ``kernels/segment_min``).

Dtype contract: everything is ``datastructs.INT`` (int32) with INF32 as
the empty/invalid sentinel; wrappers reject key spaces that could collide
with the sentinel (see ``check_key_space``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.graph.datastructs import INF32, INT
from repro.kernels.segment_min.kernel import (
    EDGE_BLOCK,
    SEG_BLOCK,
    check_key_space,
)


def _pad_edges(arrs, e: int):
    """Pad each [e] array to a multiple of EDGE_BLOCK (zeros: masked)."""
    e_pad = pl.cdiv(max(e, 1), EDGE_BLOCK) * EDGE_BLOCK
    if e_pad == e:
        return arrs, e_pad
    return [jnp.pad(a, (0, e_pad - e)) for a in arrs], e_pad


def _edge_pass(kernel, edge_arrays, num_segments: int, n_out: int,
               interpret: bool):
    """Run ``kernel`` over every (segment tile, edge tile) block of the
    per-edge int32 ``edge_arrays``; ``n_out`` int32[num_segments] outputs,
    each resident per segment tile across the inner edge loop."""
    e = edge_arrays[0].shape[0]
    check_key_space(e, num_segments)
    arrs, e_pad = _pad_edges([a.astype(INT) for a in edge_arrays], e)
    n_pad = pl.cdiv(num_segments, SEG_BLOCK) * SEG_BLOCK
    edge_spec = pl.BlockSpec((EDGE_BLOCK,), lambda j, i: (i,))
    seg_spec = pl.BlockSpec((SEG_BLOCK,), lambda j, i: (j,))
    outs = pl.pallas_call(
        kernel,
        grid=(n_pad // SEG_BLOCK, e_pad // EDGE_BLOCK),
        in_specs=[edge_spec] * len(arrs),
        out_specs=[seg_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((n_pad,), INT)] * n_out,
        interpret=interpret,
    )(*arrs)
    return tuple(o[:num_segments] for o in outs)


def _tile_ids():
    """(segment ids [1, SEG_BLOCK], edge slots [EDGE_BLOCK, 1]) of the
    current (segment tile, edge tile) block."""
    j, i = pl.program_id(0), pl.program_id(1)
    seg_ids = j * SEG_BLOCK + jax.lax.broadcasted_iota(INT, (1, SEG_BLOCK), 1)
    eidx = i * EDGE_BLOCK + jax.lax.broadcasted_iota(INT, (EDGE_BLOCK, 1), 0)
    return seg_ids, eidx


def _boruvka_round_kernel(lu_ref, lv_ref, mask_ref, out_ref):
    seg_ids, eidx = _tile_ids()
    # edges down the sublanes: [EDGE_BLOCK, 1] columns (Mosaic reshapes
    # 1-D int32 vectors, not 1-D masks, so the masks are built as columns)
    lu, lv, msk = (r[...][:, None] for r in (lu_ref, lv_ref, mask_ref))
    # tombstone/validity mask + cross test, in-register (self-loops have
    # lu == lv)
    key = jnp.where((msk != 0) & (lu != lv), eidx, INF32)
    hit = (lu == seg_ids) | (lv == seg_ids)
    partial = jnp.min(jnp.where(hit, key, INF32), axis=0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full((SEG_BLOCK,), INF32, INT)

    out_ref[...] = jnp.minimum(out_ref[...], partial)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def boruvka_round_pallas(src, dst, mask, labels, num_segments: int,
                         interpret: bool = False):
    """Borůvka round: see ``ref.boruvka_round_ref`` for the contract.

    XLA gathers both endpoints' labels; the kernel streams (lu, lv, mask)
    and reduces both endpoints in one masked min per block.
    """
    labels = labels.astype(INT)
    (out,) = _edge_pass(_boruvka_round_kernel,
                        [labels[src], labels[dst], mask], num_segments, 1,
                        interpret)
    return out


def _frontier_round_kernel(src_ref, dst_ref, cand_ref, p_ref, e_ref):
    seg_ids, eidx = _tile_ids()
    src, dst, cand = (r[...][:, None] for r in (src_ref, dst_ref, cand_ref))
    cand_f = (cand & 1) != 0  # arc src -> dst reaches dst
    cand_r = (cand & 2) != 0  # arc dst -> src reaches src
    hit_f = cand_f & (dst == seg_ids)
    hit_r = cand_r & (src == seg_ids)
    p_blk = jnp.minimum(
        jnp.min(jnp.where(hit_f, src, INF32), axis=0),
        jnp.min(jnp.where(hit_r, dst, INF32), axis=0))
    sel_f = hit_f & (src == p_blk[None, :])
    sel_r = hit_r & (dst == p_blk[None, :])
    e_blk = jnp.minimum(
        jnp.min(jnp.where(sel_f, eidx, INF32), axis=0),
        jnp.min(jnp.where(sel_r, eidx, INF32), axis=0))

    @pl.when(pl.program_id(1) == 0)
    def _init():
        p_ref[...] = jnp.full((SEG_BLOCK,), INF32, INT)
        e_ref[...] = jnp.full((SEG_BLOCK,), INF32, INT)

    # lexicographic merge with the accumulators: parent id first, then
    # minimum edge slot among edges to that parent
    acc_p, acc_e = p_ref[...], e_ref[...]
    e_ref[...] = jnp.where(
        p_blk < acc_p, e_blk,
        jnp.where(p_blk == acc_p, jnp.minimum(acc_e, e_blk), acc_e))
    p_ref[...] = jnp.minimum(acc_p, p_blk)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def frontier_round_pallas(src, dst, mask, frontier, visited,
                          num_segments: int, interpret: bool = False):
    """Scan-first-search round: contract in ``ref.frontier_round_ref``.

    Returns ``(best_p, best_e)`` int32[num_segments]. XLA gathers the
    frontier/visited bits of both endpoints into a per-edge candidate code;
    the kernel streams (src, dst, code) and reduces both arc orientations.
    """
    frontier = frontier.astype(bool)
    visited = visited.astype(bool)
    valid = mask.astype(bool) & (src != dst)
    fwd = valid & frontier[src] & ~visited[dst]
    rev = valid & frontier[dst] & ~visited[src]
    cand = fwd.astype(INT) | (rev.astype(INT) << 1)
    return _edge_pass(_frontier_round_kernel, [src, dst, cand],
                      num_segments, 2, interpret)
