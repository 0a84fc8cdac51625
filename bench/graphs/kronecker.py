"""Graph500 Kronecker generator (graph500.org specification, section
"Graph Generation"; the reference code's ``kronecker_generator.m``).

``edgefactor * 2^scale`` edge tuples; each picks its quadrant once per bit
of the scale with the initiator probabilities A, B, C and D = 1 - A - B - C.
Vertex labels are then permuted, and so is the order of the tuples. As in
the specification, self-loops and repeated tuples are kept: a doubled link
is no bridge. Config keys: ``scale``, ``edgefactor``, ``A``, ``B``, ``C``.

The links it knows to be bridges (``Graph.critical``) are up to
``CRITICAL`` links at vertices that hold a single tuple.
"""
from __future__ import annotations

import numpy as np

from bench.graph import Graph

CRITICAL = 64


def quadrant_bits(scale: int, m: int, a: float, b: float, c: float, rng):
    """Unpermuted (i, j) int32 endpoints of ``m`` tuples: one uniform draw
    per tuple and bit picks the quadrant, the same joint law as the
    specification's two draws (i with probability C + D, then j given i)."""
    ab, abc = a + b, a + b + c
    i = np.zeros(m, np.int32)
    j = np.zeros(m, np.int32)
    for lo in range(0, scale, 8):
        # eight bits at a time in bytes, then into the int32 labels
        bi = np.zeros(m, np.uint8)
        bj = np.zeros(m, np.uint8)
        for bit in range(lo, min(lo + 8, scale)):
            u = rng.random(m, dtype=np.float32)
            x1, ii, x3 = u >= a, u >= ab, u >= abc
            bi |= ii.view(np.uint8) << (bit - lo)
            bj |= (x1 ^ ii ^ x3).view(np.uint8) << (bit - lo)
        i |= bi.astype(np.int32) << lo
        j |= bj.astype(np.int32) << lo
    return i, j


def kronecker_edges(scale: int, edgefactor: int, a: float, b: float,
                    c: float, seed: int):
    """(src, dst) int32 arrays of ``edgefactor * 2^scale`` tuples, vertex
    labels and tuple order permuted."""
    n = 1 << scale
    m = edgefactor * n
    rng = np.random.default_rng(seed)
    i, j = quadrant_bits(scale, m, a, b, c, rng)
    label = rng.permutation(n).astype(np.int32)
    order = rng.permutation(m)
    return label[i[order]], label[j[order]]


def generate(cfg: dict, seed: int) -> Graph:
    scale = int(cfg["scale"])
    src, dst = kronecker_edges(scale, int(cfg["edgefactor"]),
                               float(cfg["A"]), float(cfg["B"]),
                               float(cfg["C"]), seed)
    n = 1 << scale
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    leaf = np.flatnonzero(((deg[src] == 1) | (deg[dst] == 1)) & (src != dst))
    pick = np.random.default_rng([seed, 1]).permutation(leaf)[:CRITICAL]
    return Graph(n, src, dst, np.stack([src[pick], dst[pick]], 1))
