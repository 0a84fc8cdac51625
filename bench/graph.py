"""What a generator hands the harness."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """An undirected multigraph on ``n`` vertices.

    ``critical`` holds links the generator knows to be bridges (the planted
    ones), as a ``[k, 2]`` array; empty where it knows none.
    """

    n: int
    src: np.ndarray
    dst: np.ndarray
    critical: np.ndarray

    @property
    def n_edges(self) -> int:
        return len(self.src)
