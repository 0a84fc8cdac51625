"""The benchmark's graph generators and its plain reference (CPU only)."""
from __future__ import annotations

import sys
from pathlib import Path

import networkx as nx
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import reference  # noqa: E402
from bench.graphs import kronecker, planted  # noqa: E402

A, B, C = 0.57, 0.19, 0.19


def test_kronecker_is_deterministic_and_sized_by_the_spec():
    s1, d1 = kronecker.kronecker_edges(10, 16, A, B, C, seed=2**31 + 5)
    s2, d2 = kronecker.kronecker_edges(10, 16, A, B, C, seed=2**31 + 5)
    assert len(s1) == len(d1) == 16 * 2**10
    assert np.array_equal(s1, s2) and np.array_equal(d1, d2)
    s3, _ = kronecker.kronecker_edges(10, 16, A, B, C, seed=2**31 + 6)
    assert not np.array_equal(s1, s3)
    assert s1.min() >= 0 and max(s1.max(), d1.max()) < 2**10
    assert s1.dtype == np.int32


def test_kronecker_quadrant_shares_match_the_initiator():
    m = 200_000
    i, j = kronecker.quadrant_bits(4, m, A, B, C, np.random.default_rng(1))
    for bit in range(4):
        bi, bj = (i >> bit) & 1, (j >> bit) & 1
        for (x, y), p in {(0, 0): A, (0, 1): B, (1, 0): C,
                          (1, 1): 1 - A - B - C}.items():
            share = np.mean((bi == x) & (bj == y))
            sigma = np.sqrt(p * (1 - p) / m)
            assert abs(share - p) < 5 * sigma, (bit, x, y, share, p)


def test_kronecker_config_keeps_loops_and_repeats():
    g = kronecker.generate({"scale": 8, "edgefactor": 16, "A": A, "B": B,
                            "C": C}, seed=3)
    assert g.n == 256 and g.n_edges == 4096
    keys = reference.pair_keys(g.src, g.dst, g.n)
    assert len(np.unique(keys)) < len(keys)  # repeated tuples are kept
    # the links it names as bridges are bridges
    assert 0 < len(g.critical) <= kronecker.CRITICAL
    assert {tuple(sorted(e)) for e in g.critical.tolist()} <= \
        reference.bridges(g.src, g.dst, g.n)


def test_planted_copy_matches_the_program_generator():
    from repro.graph.generators import planted_bridge_graph

    for n, m, k, seed in ((300, 4000, 5, 0), (1000, 20000, 3, 2**31 + 1)):
        want = planted_bridge_graph(n, m, k, seed=seed)
        got = planted.planted_bridge_graph(n, m, k, seed=seed)
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])
        assert got[2] == want[2]
        g = planted.generate({"n_nodes": n, "n_edges": m, "n_bridges": k},
                             seed)
        assert {tuple(e) for e in g.critical.tolist()} == want[2]


@pytest.mark.parametrize("seed", range(6))
def test_reference_agrees_with_tarjan_on_multigraphs(seed):
    rng = np.random.default_rng(seed)
    for _ in range(40):
        n = int(rng.integers(2, 80))
        m = int(rng.integers(0, 3 * n))
        s, d = rng.integers(0, n, m), rng.integers(0, n, m)
        assert reference.bridges(s, d, n) == \
            reference.bridges_tarjan(s, d, n)


@pytest.mark.parametrize("seed", range(3))
def test_reference_drop_agrees_with_tarjan_on_the_graph_left(seed):
    rng = np.random.default_rng(100 + seed)
    for _ in range(40):
        n = int(rng.integers(2, 80))
        m = int(rng.integers(1, 3 * n))
        s, d = rng.integers(0, n, m), rng.integers(0, n, m)
        pick = rng.integers(0, m, int(rng.integers(0, 6)))
        xs = np.concatenate([s[pick], rng.integers(0, n, 2)])
        ys = np.concatenate([d[pick], rng.integers(0, n, 2)])
        gone = np.isin(reference.pair_keys(s, d, n),
                       reference.pair_keys(xs, ys, n))
        assert reference.Multigraph(s, d, n).bridges(drop=(xs, ys)) == \
            reference.bridges_tarjan(s[~gone], d[~gone], n)


def test_reference_agrees_with_networkx_on_simple_graphs():
    rng = np.random.default_rng(7)
    for _ in range(30):
        n = int(rng.integers(3, 60))
        g = nx.gnm_random_graph(n, int(rng.integers(n // 2, 2 * n)),
                                seed=int(rng.integers(1 << 30)))
        e = np.array(list(g.edges()) or np.zeros((0, 2)), np.int64)
        e = e.reshape(-1, 2)
        want = {tuple(sorted(x)) for x in nx.bridges(g)}
        assert reference.bridges(e[:, 0], e[:, 1], n) == want


def test_reference_on_planted_and_deleted_graphs():
    s, d, planted_set = planted.planted_bridge_graph(3000, 60000, 5, seed=4)
    assert reference.bridges(s, d, 3000) == planted_set
    cut = sorted(planted_set)[:2]
    ks = np.array([c[0] for c in cut])
    kd = np.array([c[1] for c in cut])
    g = reference.Multigraph(s, d, 3000)
    assert g.bridges(drop=(kd, ks)) == planted_set - set(cut)  # either order
    assert g.bridges() == planted_set  # the graph is left as it was
    assert g.bridges_each([(ks, kd), None]) == [planted_set - set(cut),
                                                planted_set]
    # a doubled link is no bridge; a self-loop never is
    assert reference.bridges([0, 0, 1], [1, 1, 1], 2) == set()
    assert reference.bridges([0, 1], [1, 1], 2) == {(0, 1)}
