"""Pallas TPU kernel: unsorted segment-min over a bounded id space.

This is the Borůvka hooking reduction — the inner loop of the paper's
certificate pass (each component picks its minimum incident cross edge).

TPU adaptation: there are no scatter atomics on the VPU, and the certificate
phases have E = O(n), so instead of a scattered reduction we run a dense
masked min over (edge-tile x segment-tile) blocks:

    grid = (num_segment_tiles, num_edge_tiles)        # segment-major
    block (j, i):  partial[s] = min over t of
                   where(ids[t] == seg_base_j + s, keys[t], INF)

The output block j stays resident in VMEM across the inner edge-tile loop
(revisited-accumulator pattern), so HBM traffic is E·(keys+ids) reads per
segment tile + N writes, and the work is E·N masked ops.

Block shapes: XLA tiles a 1-D int32 array in HBM as ``T(1024)``, and Mosaic
refuses a 1-D block whose tiling differs, so both the edge and the segment
blocks are 1024 long.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.graph.datastructs import INF32, INT

# 1-D blocks: multiples of XLA's T(1024) tiling of an int32 vector
EDGE_BLOCK = 1024
SEG_BLOCK = 1024


def check_key_space(e: int, num_segments: int, *, edge_block: int = EDGE_BLOCK,
                    seg_block: int = SEG_BLOCK) -> None:
    """Reject shapes whose int32 keys/ids could collide with the INF32
    sentinel or wrap int32.

    The kernels generate ids as ``tile_base + iota`` (and the fused round
    kernels generate edge keys as ``chunk_base + iota``), so the PADDED
    index space must stay strictly below INF32: at ``num_segments`` (or
    edge counts) approaching 2^31 buckets the packed key would alias the
    empty-segment sentinel or overflow. Shared by kernels/segment_min and
    kernels/boruvka_round (tests/test_kernels.py pins both failure modes).
    """
    if e > INF32 - edge_block:
        raise ValueError(
            f"edge buffer of {e} slots overflows the int32 edge-key space "
            f"(limit {INF32 - edge_block}); shard the buffer first")
    if num_segments > INF32 - seg_block:
        raise ValueError(
            f"{num_segments} segments overflows the int32 segment-id space "
            f"(limit {INF32 - seg_block})")


def _segment_min_kernel(keys_ref, ids_ref, out_ref):
    j = pl.program_id(0)  # segment tile (outer)
    i = pl.program_id(1)  # edge tile (inner, sequential on TPU)
    keys = keys_ref[...]  # [EDGE_BLOCK]
    ids = ids_ref[...]  # [EDGE_BLOCK]
    seg_base = j * SEG_BLOCK
    # [EDGE_BLOCK, SEG_BLOCK] masked compare on the VPU
    seg_ids = seg_base + jax.lax.broadcasted_iota(INT, (1, SEG_BLOCK), 1)
    masked = jnp.where(ids[:, None] == seg_ids, keys[:, None], INF32)
    partial = jnp.min(masked, axis=0)  # [SEG_BLOCK]

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full((SEG_BLOCK,), INF32, INT)

    out_ref[...] = jnp.minimum(out_ref[...], partial)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def segment_min_pallas(
    keys: jax.Array, ids: jax.Array, num_segments: int, interpret: bool = False
) -> jax.Array:
    """keys, ids: int32[E] -> int32[num_segments] (INF32 for empty segments).

    Invalid/masked edges should carry keys == INF32 (they then never win) or
    ids pointing at a dump segment. Inputs are cast to ``datastructs.INT``
    (int32, the repo-wide index dtype); shapes that could alias the INF32
    sentinel are rejected by ``check_key_space``.
    """
    e = keys.shape[0]
    check_key_space(e, num_segments)
    keys = keys.astype(INT)
    ids = ids.astype(INT)
    e_pad = pl.cdiv(max(e, 1), EDGE_BLOCK) * EDGE_BLOCK
    n_pad = pl.cdiv(num_segments, SEG_BLOCK) * SEG_BLOCK
    if e_pad != e:
        keys = jnp.pad(keys, (0, e_pad - e), constant_values=INF32)
        # padded ids point inside range but their keys are INF -> harmless
        ids = jnp.pad(ids, (0, e_pad - e), constant_values=0)

    grid = (n_pad // SEG_BLOCK, e_pad // EDGE_BLOCK)
    out = pl.pallas_call(
        _segment_min_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((EDGE_BLOCK,), lambda j, i: (i,)),
            pl.BlockSpec((EDGE_BLOCK,), lambda j, i: (i,)),
        ],
        out_specs=pl.BlockSpec((SEG_BLOCK,), lambda j, i: (j,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), INT),
        interpret=interpret,
    )(keys, ids)
    return out[:num_segments]
