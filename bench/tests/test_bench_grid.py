"""The road-grid configuration's generator: a copy of the program's, at the
size the configuration states (CPU only; no chip is described or
touched)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, reference  # noqa: E402
from bench.graphs import grid  # noqa: E402


@pytest.mark.parametrize("side,tiles,seed", [(16, 2, 0), (64, 4, 2**31 + 3),
                                             (101, 5, 2006)])
def test_grid_copy_matches_the_program_generator(side, tiles, seed):
    from repro.graph.generators import tiled_grid_graph

    want = tiled_grid_graph(side, tiles, seed=seed)
    got = grid.tiled_grid_graph(side, tiles, seed=seed)
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])
    assert got[2] == want[2]
    g = grid.generate({"side": side, "tiles": tiles}, seed)
    assert g.n == side * side
    assert {tuple(e) for e in g.critical.tolist()} == want[2]


def test_configuration_size_links_and_critical_links():
    cfg = json.loads((harness.BENCH / "configs" /
                      "grid-square-1024.json").read_text())
    g = grid.generate(cfg, int(cfg["graph_seed"]))
    assert (cfg["side"], cfg["tiles"]) == (1024, 4)
    assert g.n == 2**20
    assert g.n_edges == 2_088_975
    assert g.critical.shape == (15, 2)
    assert g.src.dtype == g.dst.dtype == np.int32
    # the planted links are simple links of the graph
    keys = reference.pair_keys(g.src, g.dst, g.n)
    crit = reference.pair_keys(g.critical[:, 0], g.critical[:, 1], g.n)
    assert np.isin(crit, keys).all()
    assert len(np.unique(keys)) == len(keys)
