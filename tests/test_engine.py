"""BridgeEngine: compile-once caching, batched dispatch, incremental updates,
and the shape-bucketing contract (DESIGN.md §Engine)."""
import numpy as np
import pytest

from repro.core import find_bridges
from repro.core.bridges_host import bridges_dfs
from repro.engine import BatchedEdgeList, BridgeEngine, find_bridges_batch
from repro.graph import generators as gen
from repro.graph.datastructs import EdgeList, bucket_capacity, pad_edges

from helpers import to_pair_set

# One (n, E) operating point so the whole module shares a few compiled
# programs on the 1-core box: n in (32, 64] -> bucket 64, E -> bucket 512.
N_A, N_B, E_N = 50, 60, 400


def graph(seed, n=N_A, e=E_N):
    src, dst, _ = gen.planted_bridge_graph(n, e, n_bridges=3, seed=seed)
    return src, dst


def test_bucket_capacity_powers_of_two():
    assert bucket_capacity(1) == 16  # minimum floor
    assert bucket_capacity(16) == 16
    assert bucket_capacity(17) == 32
    assert bucket_capacity(500) == 512
    assert bucket_capacity(512) == 512
    assert bucket_capacity(3, minimum=1) == 4


def test_pad_edges_shrink_refuses_to_drop_real_edges():
    src, dst = gen.random_graph(20, 10, seed=0)
    el = EdgeList.from_arrays(src, dst, 20)
    with pytest.raises(ValueError, match="drop"):
        pad_edges(el, len(src) - 2)


def test_pad_edges_shrink_keeps_all_real_edges():
    src, dst = gen.random_graph(20, 10, seed=0)
    el = pad_edges(EdgeList.from_arrays(src, dst, 20), 64)  # grow first
    small = pad_edges(el, len(src))  # shrink back to exactly the real count
    assert small.capacity == len(src)
    assert to_pair_set(small) == to_pair_set(el)


def test_second_call_same_bucket_no_retrace():
    """Acceptance: cached-program second call shows no retrace."""
    eng = BridgeEngine()
    # different n and E, same (64, 512) shape bucket
    s1, d1 = gen.random_graph(N_A, 300, seed=1)
    s2, d2 = gen.random_graph(N_B, 400, seed=2)
    r1 = eng.find_bridges(s1, d1, N_A)
    traces_after_first = eng.stats.traces
    r2 = eng.find_bridges(s2, d2, N_B)
    assert r1 == bridges_dfs(s1, d1, N_A)
    assert r2 == bridges_dfs(s2, d2, N_B)
    assert eng.stats.misses == 1
    assert eng.stats.hits == 1
    assert eng.stats.traces == traces_after_first == 1  # no retrace on hit
    assert eng.cache_info()["programs"] == 1


def test_batch_matches_per_graph_results():
    """Acceptance: B=8 batched == the per-graph find_bridges results."""
    eng = BridgeEngine()
    graphs = [graph(seed) for seed in range(8)]
    got = eng.find_bridges_batch(graphs, N_A)
    want = [find_bridges(s, d, N_A, final="device") for s, d in graphs]
    assert got == want
    # one batched program, one dispatch; smaller batch reuses it (B-bucket)
    assert eng.cache_info()["programs"] == 1
    traces = eng.stats.traces
    got5 = eng.find_bridges_batch(graphs[:5], N_A)
    assert got5 == want[:5]
    assert eng.stats.traces == traces


def test_batch_mixed_vertex_counts():
    graphs = [graph(3, n=N_A), graph(4, n=N_B)]
    got = find_bridges_batch(graphs, [N_A, N_B])
    assert got[0] == bridges_dfs(*graphs[0], N_A)
    assert got[1] == bridges_dfs(*graphs[1], N_B)


def test_insert_edges_matches_from_scratch():
    """Acceptance: incremental answers == from-scratch recompute per delta."""
    eng = BridgeEngine()
    src, dst = graph(7)
    eng.load(src, dst, N_A)
    assert eng.current_bridges() == bridges_dfs(src, dst, N_A)
    all_s, all_d = src, dst
    for step in range(3):
        ds, dd = gen.random_graph(N_A, 30, seed=100 + step)
        got = eng.insert_edges(ds, dd)
        all_s = np.concatenate([all_s, ds])
        all_d = np.concatenate([all_d, dd])
        want = find_bridges(all_s, all_d, N_A, final="device")
        assert got == want, step
    # certificate invariant survives the delta chain
    assert eng.num_live_edges <= 2 * (eng._live["n_bucket"] - 1)


def test_insert_bridge_then_cover_it():
    """A delta that adds a bridge, then a delta that cycles it away."""
    src, dst, n = np.array([0, 1], np.int32), np.array([1, 2], np.int32), 40
    eng = BridgeEngine()
    eng.load(src, dst, n)
    assert eng.current_bridges() == {(0, 1), (1, 2)}
    got = eng.insert_edges(np.array([2], np.int32), np.array([3], np.int32))
    assert got == {(0, 1), (1, 2), (2, 3)}
    got = eng.insert_edges(np.array([3], np.int32), np.array([0], np.int32))
    assert got == set()  # 0-1-2-3-0 is now a cycle


def test_engine_host_final_matches_device():
    eng = BridgeEngine()
    src, dst = graph(9)
    assert (eng.find_bridges(src, dst, N_A, final="host")
            == eng.find_bridges(src, dst, N_A, final="device"))


def test_batch_rejects_mismatched_vertex_counts():
    graphs = [graph(1), graph(2), graph(3)]
    with pytest.raises(ValueError, match="3 graphs but 2"):
        BridgeEngine().find_bridges_batch(graphs, [N_A, N_A])


def test_insert_requires_load():
    eng = BridgeEngine()
    with pytest.raises(RuntimeError, match="load"):
        eng.insert_edges([0], [1])


def test_registry_out_struct_matches_traced_shapes():
    """Each Analysis.out_struct declaration is exactly the pytree of
    shapes/dtypes the traced final stage produces (the §Buffers contract
    extended to result buffers)."""
    import jax

    from repro.connectivity.registry import ANALYSIS_KINDS, get_analysis
    from repro.core.certificate import certificate_capacity
    from repro.engine import make_analysis_fn

    n, cap = 64, 256
    cert_cap = certificate_capacity(n)
    in_structs = (jax.ShapeDtypeStruct((cap,), np.int32),
                  jax.ShapeDtypeStruct((cap,), np.int32),
                  jax.ShapeDtypeStruct((cap,), np.bool_))
    for kind in ANALYSIS_KINDS:
        analysis = get_analysis(kind)
        got, rounds, full = jax.eval_shape(
            make_analysis_fn(n, kind, "device"), *in_structs)
        for counts in (rounds, full):
            assert counts.shape == (2,) and counts.dtype == np.int32, kind
        # out_struct's capacity = the buffer the final stage ran on
        ran_on = cert_cap if analysis.device_input == "certificate" else cap
        want = analysis.out_struct(n, ran_on)
        got_l = jax.tree_util.tree_leaves(got)
        want_l = jax.tree_util.tree_leaves(want)
        assert len(got_l) == len(want_l), kind
        for g, w in zip(got_l, want_l):
            assert g.shape == w.shape and g.dtype == w.dtype, (kind, g, w)


def test_batched_edgelist_roundtrip():
    graphs = [graph(11), graph(12)]
    bel = BatchedEdgeList.from_graphs(graphs, N_A, capacity=512, batch_pad=4)
    assert bel.batch_size == 4 and bel.capacity == 512
    for i, (s, d) in enumerate(graphs):
        assert to_pair_set(bel[i]) == to_pair_set(
            EdgeList.from_arrays(s, d, N_A))
    assert int(np.asarray(bel.mask[2]).sum()) == 0  # padding rows are empty

    with pytest.raises(ValueError, match="exceeds"):
        BatchedEdgeList.from_graphs(graphs, N_A, capacity=4)
