"""The what-if bridges path on the tiled road grid (``tiled_grid_graph``),
a sparse, high-diameter graph whose certificate keeps nearly every link,
against the benchmark's plain reference and the host DFS."""
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.bridges_host import bridges_dfs
from repro.engine import BridgeEngine
from repro.graph.generators import tiled_grid_graph

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import reference  # noqa: E402

ENGINE = BridgeEngine()


def grid_links(side: int, tiles: int) -> int:
    """Links of the grid, less those between districts, plus the snake's."""
    return 2 * side * (side - 1) - 2 * (tiles - 1) * side + tiles**2 - 1


def fail_set(src, dst, planted, seed: int):
    """8 failed links, 2 of them planted bridges, in a seeded order."""
    rng = np.random.default_rng(seed)
    idx = rng.choice(len(src), 6, replace=False)
    cut = sorted(planted)
    two = rng.choice(len(cut), 2, replace=False)
    ks = np.concatenate([src[idx], [cut[i][0] for i in two]])
    kd = np.concatenate([dst[idx], [cut[i][1] for i in two]])
    order = rng.permutation(8)
    return ks[order].astype(np.int32), kd[order].astype(np.int32)


@pytest.mark.parametrize("failures", [0, 8])
@pytest.mark.parametrize("side,tiles", [(32, 2), (64, 4)])
def test_whatif_bridges_match_the_reference_and_the_host_dfs(side, tiles,
                                                             failures):
    src, dst, planted = tiled_grid_graph(side, tiles, seed=side + tiles)
    n = side * side
    delete = fail_set(src, dst, planted, seed=side) if failures else None
    got = ENGINE.analyze(src, dst, n, kind="bridges", final="device",
                         delete=delete)
    want = reference.Multigraph(src, dst, n).bridges(drop=delete)
    assert got == want
    if delete is None:
        assert got == planted
        keep = np.ones(len(src), bool)
    else:
        gone = np.isin(reference.pair_keys(src, dst, n),
                       reference.pair_keys(*delete, n))
        keep = ~gone
        # the two failed planted links are gone; the other planted ones stay
        assert planted - got == {tuple(sorted(p)) for p in zip(*delete)
                                 if tuple(sorted(p)) in planted}
    assert got == bridges_dfs(src[keep], dst[keep], n)


@pytest.mark.parametrize("side,tiles", [(8, 2), (9, 4), (32, 2), (64, 4),
                                        (1024, 4)])
def test_link_count_formula(side, tiles):
    src, dst, planted = tiled_grid_graph(side, tiles, seed=3)
    assert len(src) == len(dst) == grid_links(side, tiles)
    assert len(planted) == tiles**2 - 1
    keys = reference.pair_keys(src, dst, side * side)
    assert len(np.unique(keys)) == len(keys)  # a simple graph
    assert (src != dst).all()


@pytest.mark.parametrize("side,tiles", [(12, 3), (40, 4)])
def test_planted_links_are_the_bridges_of_the_base_graph(side, tiles):
    src, dst, planted = tiled_grid_graph(side, tiles, seed=11)
    n = side * side
    assert bridges_dfs(src, dst, n) == planted
    assert reference.bridges(src, dst, n) == planted


def test_ids_and_link_order_are_permuted_from_the_seed():
    side, tiles = 32, 4
    src, dst, planted = tiled_grid_graph(side, tiles, seed=5)
    again = tiled_grid_graph(side, tiles, seed=5)
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1])
    assert planted == again[2]
    other = tiled_grid_graph(side, tiles, seed=6)
    assert not np.array_equal(src, other[0])
    # in row-major ids every link joins junctions 1 or ``side`` apart, and
    # the links come in order; after the permutation neither holds
    gap = np.abs(src.astype(np.int64) - dst)
    assert np.mean((gap == 1) | (gap == side)) < 0.05
    assert np.mean(np.diff(np.minimum(src, dst)) >= 0) < 0.6
    assert sorted(np.unique(np.concatenate([src, dst]))) == list(
        range(side * side))


def test_too_many_districts_for_the_side_are_refused():
    with pytest.raises(ValueError, match="narrower than 2"):
        tiled_grid_graph(6, 4)
