"""A square road grid (the 9th DIMACS Implementation Challenge's
``Square-n`` family, a sqrt(n) x sqrt(n) grid from Cherkassky, Goldberg and
Radzik's SPGRID generator), undirected and without weights, cut into
districts joined by single links, the planted bridges (a copy of the
program's ``repro.graph.generators.tiled_grid_graph``, which the tests check
it against). Config keys: ``side``, ``tiles``."""
from __future__ import annotations

import numpy as np

from bench.graph import Graph


def tiled_grid_graph(side: int, tiles: int, seed: int = 0):
    """A ``side`` x ``side`` 4-neighbour grid cut into ``tiles`` x ``tiles``
    districts: every link between districts is dropped, then consecutive
    districts of a boustrophedon (snake) order are joined by one link each
    (the planted bridges), at a position along their shared boundary drawn
    from the seed. Junction ids and link order are permuted from the seed.
    Returns (src, dst, bridges_set)."""
    if not 1 <= tiles <= side // 2:
        raise ValueError(f"{tiles} x {tiles} districts of a {side}-side grid "
                         f"leave one narrower than 2 junctions")
    rng = np.random.default_rng(seed)
    band = np.arange(side) * tiles // side  # district of each row / column
    start = np.searchsorted(band, np.arange(tiles + 1))
    ids = np.arange(side * side, dtype=np.int64).reshape(side, side)
    inner_h = band[:-1] == band[1:]  # column c to c + 1 stays in a district
    srcs = [ids[:, :-1][:, inner_h].ravel(), ids[:-1][inner_h].ravel()]
    dsts = [ids[:, 1:][:, inner_h].ravel(), ids[1:][inner_h].ravel()]
    snake = [(i, j if i % 2 == 0 else tiles - 1 - j)
             for i in range(tiles) for j in range(tiles)]
    planted = []
    for (i, j), (i2, j2) in zip(snake[:-1], snake[1:]):
        if i == i2:  # across the column boundary in front of district max(j)
            col = start[max(j, j2)]
            row = start[i] + rng.integers(0, start[i + 1] - start[i])
            planted.append((ids[row, col - 1], ids[row, col]))
        else:        # across the row boundary below district row i
            row = start[i2]
            col = start[j] + rng.integers(0, start[j + 1] - start[j])
            planted.append((ids[row - 1, col], ids[row, col]))
    planted = np.array(planted, np.int64).reshape(-1, 2)
    src = np.concatenate(srcs + [planted[:, 0]])
    dst = np.concatenate(dsts + [planted[:, 1]])
    label = rng.permutation(side * side).astype(np.int32)
    order = rng.permutation(len(src))
    src, dst = label[src[order]], label[dst[order]]
    ps, pd = label[planted[:, 0]], label[planted[:, 1]]
    bridges = {(int(min(u, v)), int(max(u, v))) for u, v in zip(ps, pd)}
    return src, dst, bridges


def generate(cfg: dict, seed: int) -> Graph:
    side = int(cfg["side"])
    src, dst, planted = tiled_grid_graph(side, int(cfg["tiles"]), seed=seed)
    critical = np.array(sorted(planted), np.int32).reshape(-1, 2)
    return Graph(side * side, src, dst, critical)
