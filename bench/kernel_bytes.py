"""Bytes a kernel must move, computed from shapes alone.

The count belongs to the algorithm, not to an implementation: whatever
computes a round (the XLA scatter or a Pallas kernel), it has to read this
much, so a roofline share built on it compares implementations fairly.
"""
from __future__ import annotations


def bucket(m: int, minimum: int = 16) -> int:
    """The power-of-two slot count the engine pads ``m`` items to."""
    m = max(int(m), minimum, 1)
    return 1 << (m - 1).bit_length()


def boruvka_round_bytes(edge_slots: int, n_vertices: int) -> int:
    """One Borůvka round over a padded edge buffer: read both endpoints
    (int32) and the mask (bool) of every slot, gather both endpoint labels
    (int32), and write one int32 minimum per vertex segment."""
    return edge_slots * (4 + 4 + 1 + 4 + 4) + n_vertices * 4
