"""Per-kernel shape/dtype sweeps: Pallas interpret=True vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.forest import scan_first_forest_ex, spanning_forest_ex
from repro.graph import generators as gen
from repro.graph.datastructs import INF32, EdgeList
from repro.kernels.boruvka_round.kernel import (
    boruvka_round_pallas,
    frontier_round_pallas,
)
from repro.kernels.boruvka_round.ops import (
    boruvka_round_bytes,
    frontier_round_bytes,
    kernel_path,
)
from repro.kernels.boruvka_round.ref import boruvka_round_ref, frontier_round_ref
from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.segment_min.kernel import check_key_space, segment_min_pallas
from repro.kernels.segment_min.ref import segment_min_ref

from _hyp import given, st


# ---------------------------------------------------------------- segment_min
@pytest.mark.parametrize(
    "e,n", [(7, 3), (100, 30), (1024, 512), (1500, 513), (4096, 1024), (33, 1)]
)
def test_segment_min_shapes(e, n):
    rng = np.random.default_rng(e * 31 + n)
    keys = jnp.asarray(rng.integers(0, 1 << 20, e), jnp.int32)
    ids = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    got = segment_min_pallas(keys, ids, n, interpret=True)
    want = segment_min_ref(keys, ids, n)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_segment_min_empty_segments_inf():
    keys = jnp.asarray([5, 3], jnp.int32)
    ids = jnp.asarray([0, 0], jnp.int32)
    out = np.asarray(segment_min_pallas(keys, ids, 4, interpret=True))
    assert out[0] == 3 and (out[1:] == np.iinfo(np.int32).max).all()


@given(st.integers(0, 1000))
def test_segment_min_property(seed):
    rng = np.random.default_rng(seed)
    e, n = 512, 128  # fixed shapes: avoid per-example recompiles
    keys = jnp.asarray(rng.integers(0, 1 << 15, e), jnp.int32)
    ids = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    got = segment_min_pallas(keys, ids, n, interpret=True)
    want = segment_min_ref(keys, ids, n)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------ flash attention
CASES = [
    # b, sq, skv, hq, hkv, d
    (2, 64, 64, 4, 2, 32),     # GQA group 2
    (1, 128, 128, 8, 1, 64),   # MQA
    (1, 1, 96, 4, 2, 32),      # decode: one query vs cache
    (2, 17, 63, 2, 2, 16),     # ragged, non-block-aligned
    (1, 256, 256, 2, 2, 128),  # MXU-aligned d_head=128
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(case, causal):
    b, sq, skv, hq, hkv, d = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % (1 << 31)), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, hkv, d), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, interpret=True,
                                 q_block=32, kv_block=32)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 64, 2, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 64, 2, 32), jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, interpret=True, q_block=32, kv_block=32)
    want = attention_ref(q, k, v)
    # bf16 storage, fp32 accumulation in both paths
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2, rtol=3e-2
    )


def test_flash_attention_block_shape_invariance():
    """Result must not depend on the tiling."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (1, 96, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 96, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 96, 2, 32), jnp.float32)
    a = flash_attention_pallas(q, k, v, interpret=True, q_block=32, kv_block=32)
    b = flash_attention_pallas(q, k, v, interpret=True, q_block=96, kv_block=48)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


# -------------------------------------------------------------- embedding bag
@pytest.mark.parametrize("mode", ["sum", "mean", "max"])
@pytest.mark.parametrize("b,l,v,d", [(13, 7, 1000, 32), (8, 1, 64, 16), (3, 50, 4096, 64)])
def test_embedding_bag_matches_ref(mode, b, l, v, d):
    rng = np.random.default_rng(b * l)
    table = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, v, (b, l)), jnp.int32)
    mask = jnp.asarray(rng.random((b, l)) > 0.3)
    got = embedding_bag_pallas(table, idx, mask, mode=mode, interpret=True)
    want = embedding_bag_ref(table, idx, mask, mode=mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_embedding_bag_all_masked_bag():
    table = jnp.ones((10, 4), jnp.float32)
    idx = jnp.zeros((2, 3), jnp.int32)
    mask = jnp.asarray([[True, True, False], [False, False, False]])
    for mode in ("sum", "mean", "max"):
        out = np.asarray(embedding_bag_pallas(table, idx, mask, mode=mode, interpret=True))
        assert np.isfinite(out).all(), mode
        assert out[1].sum() == 0.0  # empty bag pools to zero


# ----------------------------------------------- fused connectivity rounds
def _edge_buffer(e, n, seed, self_loop_frac=0.1, mask_frac=0.2):
    """Random masked multigraph buffer: duplicates, self-loops, tombstones."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    loops = rng.random(e) < self_loop_frac
    dst = np.where(loops, src, dst)
    # force duplicate (multi-)edges: copy a block of slots over another
    if e >= 8:
        src[e // 2 : e // 2 + e // 4] = src[: e // 4]
        dst[e // 2 : e // 2 + e // 4] = dst[: e // 4]
    mask = rng.random(e) >= mask_frac
    return jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask)


@pytest.mark.parametrize(
    "e,n", [(7, 5), (100, 30), (1024, 512), (1500, 513), (2048, 1024), (33, 1)]
)
def test_boruvka_round_shapes(e, n):
    rng = np.random.default_rng(e * 17 + n)
    src, dst, mask = _edge_buffer(e, n, seed=e + n)
    labels = jnp.asarray(rng.integers(0, n, n), jnp.int32)
    got = boruvka_round_pallas(src, dst, mask, labels, n, interpret=True)
    want = boruvka_round_ref(src, dst, mask, labels, n)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize(
    "e,n", [(7, 5), (100, 30), (1024, 512), (1500, 513), (2048, 1024)]
)
def test_frontier_round_shapes(e, n):
    rng = np.random.default_rng(e * 13 + n)
    src, dst, mask = _edge_buffer(e, n, seed=e * 3 + n)
    frontier = jnp.asarray(rng.random(n) < 0.4)
    visited = jnp.asarray(rng.random(n) < 0.5) | frontier
    got_p, got_e = frontier_round_pallas(src, dst, mask, frontier, visited, n,
                                         interpret=True)
    want_p, want_e = frontier_round_ref(src, dst, mask, frontier, visited, n)
    assert np.array_equal(np.asarray(got_p), np.asarray(want_p))
    assert np.array_equal(np.asarray(got_e), np.asarray(want_e))


def test_boruvka_round_all_masked_or_loops():
    """Tombstoned + self-loop-only buffers reduce to all-INF32."""
    src = jnp.asarray([0, 1, 2, 3], jnp.int32)
    dst = jnp.asarray([1, 1, 3, 0], jnp.int32)  # slot 1 is a self-loop
    mask = jnp.asarray([False, True, False, False])
    labels = jnp.arange(5, dtype=jnp.int32)
    out = np.asarray(
        boruvka_round_pallas(src, dst, mask, labels, 5, interpret=True))
    assert (out == INF32).all()


@given(st.integers(0, 1000))
def test_boruvka_round_property(seed):
    rng = np.random.default_rng(seed)
    e, n = 512, 128  # fixed shapes: avoid per-example recompiles
    src, dst, mask = _edge_buffer(e, n, seed=seed)
    labels = jnp.asarray(rng.integers(0, n, n), jnp.int32)
    got = boruvka_round_pallas(src, dst, mask, labels, n, interpret=True)
    want = boruvka_round_ref(src, dst, mask, labels, n)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@given(st.integers(0, 1000))
def test_frontier_round_property(seed):
    rng = np.random.default_rng(seed ^ 0x5F5F)
    e, n = 512, 128
    src, dst, mask = _edge_buffer(e, n, seed=seed + 7)
    frontier = jnp.asarray(rng.random(n) < 0.3)
    visited = jnp.asarray(rng.random(n) < 0.5) | frontier
    got_p, got_e = frontier_round_pallas(src, dst, mask, frontier, visited, n,
                                         interpret=True)
    want_p, want_e = frontier_round_ref(src, dst, mask, frontier, visited, n)
    assert np.array_equal(np.asarray(got_p), np.asarray(want_p))
    assert np.array_equal(np.asarray(got_e), np.asarray(want_e))


@pytest.mark.parametrize("idx", [0, 1, 2])
def test_boruvka_round_parity_on_failure_scenarios(idx):
    """Round-level interpret-mode parity on every planted failure world."""
    sc = gen.failure_scenarios()[idx]
    el = EdgeList.from_arrays(sc["src"], sc["dst"], sc["n"])
    n = el.n_nodes
    rng = np.random.default_rng(idx)
    for labels in (jnp.arange(n, dtype=jnp.int32),
                   jnp.asarray(rng.integers(0, n, n), jnp.int32)):
        got = boruvka_round_pallas(el.src, el.dst, el.mask, labels, n,
                                   interpret=True)
        want = boruvka_round_ref(el.src, el.dst, el.mask, labels, n)
        assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------- forest equivalence (end-to-end)
@pytest.mark.parametrize("idx", [0, 1, 2])
def test_forest_pallas_equals_lax_on_failure_scenarios(idx):
    """`use_pallas=True` must produce the IDENTICAL forest, labels and round
    count as the jnp-oracle path on every planted failure scenario — the
    fused kernel is a drop-in for the three-pass lax sequence, bit for bit."""
    sc = gen.failure_scenarios()[idx]
    el = EdgeList.from_arrays(sc["src"], sc["dst"], sc["n"])
    f_lax, l_lax, r_lax, _ = spanning_forest_ex(el, use_pallas=False)
    f_pal, l_pal, r_pal, _ = spanning_forest_ex(el, use_pallas=True)
    assert np.array_equal(np.asarray(f_lax), np.asarray(f_pal))
    assert np.array_equal(np.asarray(l_lax), np.asarray(l_pal))
    assert int(r_lax) == int(r_pal)


@pytest.mark.parametrize("idx", [0, 1, 2])
def test_sfs_pallas_equals_lax_on_failure_scenarios(idx):
    sc = gen.failure_scenarios()[idx]
    el = EdgeList.from_arrays(sc["src"], sc["dst"], sc["n"])
    lax_out = scan_first_forest_ex(el, use_pallas=False)
    pal_out = scan_first_forest_ex(el, use_pallas=True)
    for a, b in zip(lax_out, pal_out):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- int32 key-space guard
def test_key_space_guard_rejects_overflow():
    ok_keys = jnp.asarray([1, 2], jnp.int32)
    ok_ids = jnp.asarray([0, 0], jnp.int32)
    with pytest.raises(ValueError, match="segment-id space"):
        segment_min_pallas(ok_keys, ok_ids, num_segments=(1 << 31) - 10)
    with pytest.raises(ValueError, match="segment-id space"):
        boruvka_round_pallas(ok_keys, ok_ids, jnp.asarray([True, True]),
                             jnp.asarray([0], jnp.int32),
                             num_segments=(1 << 31) - 10)
    # edge-key branch checked on the raw guard: no 2^31-slot array needed
    with pytest.raises(ValueError, match="edge-key space"):
        check_key_space((1 << 31) - 10, 4)
    check_key_space(1 << 20, 1 << 20)  # comfortably inside: no raise


# ---------------------------------------------------- byte-traffic invariants
def test_fused_round_halves_edge_bytes():
    """The acceptance bound: the fused path moves <= half the edge-buffer
    bytes per round of the three-pass lax baseline (fig9 pins the values)."""
    for e in (1, 1000, 1 << 20):
        assert 2 * boruvka_round_bytes(e, fused=True) <= boruvka_round_bytes(
            e, fused=False)
        assert 2 * frontier_round_bytes(e, fused=True) <= frontier_round_bytes(
            e, fused=False)


def test_kernel_path_names():
    assert kernel_path(False) == "oracle"
    assert kernel_path(True) in ("pallas", "interpret")
    assert kernel_path(None) in ("pallas", "oracle")


# ------------------------------------------------- kernel-backed ops dispatch
def test_ops_wrappers_run_on_cpu():
    from repro.kernels.boruvka_round import boruvka_round, frontier_round
    from repro.kernels.embedding_bag import embedding_bag
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.segment_min import segment_min

    out = segment_min(jnp.asarray([3, 1], jnp.int32), jnp.asarray([0, 0], jnp.int32), 2)
    assert int(out[0]) == 1
    src = jnp.asarray([0, 1], jnp.int32)
    dst = jnp.asarray([1, 2], jnp.int32)
    msk = jnp.asarray([True, True])
    best = boruvka_round(src, dst, msk, jnp.arange(3, dtype=jnp.int32), 3)
    assert np.asarray(best).tolist() == [0, 0, 1]
    p, e = frontier_round(src, dst, msk, jnp.asarray([True, False, False]),
                          jnp.asarray([True, False, False]), 3)
    assert int(p[1]) == 0 and int(e[1]) == 0
    q = jnp.ones((1, 8, 2, 16), jnp.float32)
    assert flash_attention(q, q[:, :, :2], q[:, :, :2]).shape == (1, 8, 2, 16)
    t = jnp.ones((5, 4), jnp.float32)
    assert embedding_bag(t, jnp.zeros((2, 3), jnp.int32)).shape == (2, 4)
