"""Public connectivity-round ops: the XLA path by default, the Pallas kernel
when a caller sets ``use_pallas`` — the same dispatch as kernels/segment_min,
so `core/forest.py` (and through it every certificate, hence every engine
substrate) picks its round implementation in one place.

``use_pallas`` on every op:
  * ``False`` (default, every platform) — the XLA path: the ``ref.py``
    gathers + ``segment_min`` scatter-mins. On one TPU v5e chip at the
    paper's size (n_bucket = 2^17, E = 2^24) a warm Borůvka round of it
    took 0.54 s against the kernel's 4.9 s: the kernel re-reads the edge
    arrays once per 1024-segment tile;
  * ``True`` — the Pallas kernel: compiled on a TPU, run by the Pallas
    interpreter elsewhere (how the parity tests drive it on CPU).

``boruvka_cross`` and ``boruvka_pick`` are the XLA round's two halves, the
gathers and the scatter-mins, for a loop that keeps the gathered labels
from one round to the next (``core/forest.py``'s filtered loop); the
Pallas kernel has no such split.

``kernel_path(use_pallas)`` names what a setting runs (``oracle`` |
``pallas`` | ``interpret``); the ops dispatch through it, and serving
reports and benchmark records carry it.
"""
from __future__ import annotations

from repro.kernels import kernel_path
from repro.kernels.boruvka_round.kernel import (
    boruvka_round_pallas,
    frontier_round_pallas,
)
from repro.kernels.boruvka_round.ref import (
    boruvka_cross,
    boruvka_pick,
    boruvka_round_ref,
    frontier_round_ref,
)

__all__ = ["boruvka_cross", "boruvka_pick", "boruvka_round",
           "boruvka_round_bytes", "frontier_round", "frontier_round_bytes",
           "kernel_path"]


def boruvka_round(src, dst, mask, labels, num_segments: int,
                  use_pallas: bool = False):
    """Borůvka hooking round (contract: ``ref.boruvka_round_ref``)."""
    path = kernel_path(use_pallas)
    if path == "oracle":
        return boruvka_round_ref(src, dst, mask, labels, num_segments)
    return boruvka_round_pallas(src, dst, mask, labels, num_segments,
                                interpret=path == "interpret")


def frontier_round(src, dst, mask, frontier, visited, num_segments: int,
                   use_pallas: bool = False):
    """SFS frontier round (contract: ``ref.frontier_round_ref``)."""
    path = kernel_path(use_pallas)
    if path == "oracle":
        return frontier_round_ref(src, dst, mask, frontier, visited,
                                  num_segments)
    return frontier_round_pallas(src, dst, mask, frontier, visited,
                                 num_segments, interpret=path == "interpret")


# ------------------------------------------------- HBM byte-traffic model
# The analytic edge-buffer traffic per round that benchmarks/fig9_kernels.py
# pins as exact counters (DESIGN.md §Kernels has the derivation). It models
# a kernel that gathers in VMEM and streams the raw buffer once per round.
# The kernels no longer do either: XLA gathers the per-edge arrays ahead of
# them, and they re-read those arrays once per segment tile. So the
# ``fused`` figures are a model, not what the kernels read (ROADMAP.md).

#: bytes per edge slot of the raw buffer: src int32 + dst int32 + mask byte
EDGE_SLOT_BYTES = 9


def boruvka_round_bytes(e: int, fused: bool) -> int:
    """Modelled edge-buffer bytes of one Borůvka round.

    fused: one pass over (src, dst, mask) — 9 bytes/edge. lax: three trips —
    the key/cross build reads the raw buffer (9), then each of the two
    ``segment_min`` passes re-reads its (key, label-ids) pair (8 + 8).
    """
    return e * EDGE_SLOT_BYTES if fused else e * (9 + 8 + 8)


def frontier_round_bytes(e: int, fused: bool) -> int:
    """Modelled edge-buffer bytes of one SFS frontier round.

    fused: one pass over the RAW E-slot buffer — 9 bytes/edge. lax: the
    candidate-mask build reads the materialized 2E arc arrays (us, ws, v2:
    9 bytes/arc), then the parent and edge-slot ``segment_min`` passes each
    re-read a (key, ids) pair over 2E arcs (8 + 8) — 50 bytes/edge in total.
    """
    return e * EDGE_SLOT_BYTES if fused else 2 * e * (9 + 8 + 8)
