"""Pallas TPU embedding-bag: gather rows from a big HBM table + pooled reduce.

The recsys hot path (taxonomy B.6): tables are 10^6-10^9 rows and live in
HBM; only the gathered rows should ever touch VMEM. The kernel keeps the
table in ANY/HBM memory space and issues per-index dynamic-slice loads
(scalar-prefetch pattern: the index tile is staged in SMEM so the DMA
addresses are known ahead of the compute), accumulating the pooled result
for a batch tile in VMEM.

grid = (num_batch_tiles,); each step pools B_TILE bags of fixed length L.
HBM traffic: B*L rows of D floats read + B rows written — the roofline
optimum for this op (it is memory-bound by construction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

B_TILE = 8


def _bag_kernel(idx_ref, mask_ref, table_ref, out_ref, *, mode):
    # idx_ref: [B_TILE, L] (SMEM); table_ref: [V, D] (ANY/HBM); out: [B_TILE, D]
    L = idx_ref.shape[1]
    D = out_ref.shape[1]

    def pool_one(b, _):
        def body(l, acc):
            row = table_ref[idx_ref[b, l]]  # dynamic-slice load from HBM
            valid = mask_ref[b, l]
            rowf = row.astype(jnp.float32)
            if mode == "max":
                acc_v, cnt = acc
                acc_v = jnp.where(valid, jnp.maximum(acc_v, rowf), acc_v)
                return acc_v, cnt
            acc_v, cnt = acc
            acc_v = acc_v + jnp.where(valid, rowf, 0.0)
            return acc_v, cnt + valid.astype(jnp.float32)

        init = (
            jnp.full((D,), -jnp.inf if mode == "max" else 0.0, jnp.float32),
            jnp.zeros((), jnp.float32),
        )
        acc_v, cnt = jax.lax.fori_loop(0, L, body, init)
        if mode == "mean":
            acc_v = acc_v / jnp.maximum(cnt, 1.0)
        if mode == "max":
            acc_v = jnp.where(jnp.isfinite(acc_v), acc_v, 0.0)
        out_ref[b, :] = acc_v.astype(out_ref.dtype)
        return ()

    jax.lax.fori_loop(0, idx_ref.shape[0], pool_one, ())


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def embedding_bag_pallas(table, indices, mask=None, mode: str = "sum",
                         interpret: bool = False):
    """table: [V, D]; indices: int32[B, L]; mask: bool[B, L] -> f32[B, D]."""
    b, l = indices.shape
    v, d = table.shape
    if mask is None:
        mask = jnp.ones((b, l), bool)
    b_pad = pl.cdiv(b, B_TILE) * B_TILE
    if b_pad != b:
        indices = jnp.pad(indices, ((0, b_pad - b), (0, 0)))
        mask = jnp.pad(mask, ((0, b_pad - b), (0, 0)))

    grid = (b_pad // B_TILE,)
    out = pl.pallas_call(
        functools.partial(_bag_kernel, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B_TILE, l), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((B_TILE, l), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),  # table stays in HBM
        ],
        out_specs=pl.BlockSpec((B_TILE, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, d), jnp.float32),
        interpret=interpret,
    )(indices, mask, table)
    return out[:b]
