"""Distributed pipeline tests. These need >1 device, and jax locks the device
count at first init — so they run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests in this process
keep seeing 1 device, per the dry-run contract)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest


REPO = Path(__file__).resolve().parent.parent


def run_with_devices(code: str, n_devices: int = 8) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )


@pytest.mark.parametrize("schedule", ["paper", "xor"])
@pytest.mark.parametrize("final", ["host", "device"])
def test_distributed_matches_oracle(schedule, final):
    r = run_with_devices(f"""
        import jax
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,), ("machines",), axis_types=(AxisType.Auto,))
        from repro.core import find_bridges
        from repro.core.bridges_host import bridges_dfs
        from repro.graph import generators as gen
        for seed in range(3):
            src, dst, _ = gen.planted_bridge_graph(100, 2500, 3, seed=seed)
            want = bridges_dfs(src, dst, 100)
            got = find_bridges(src, dst, 100, mesh=mesh, machine_axes=("machines",),
                               schedule="{schedule}", final="{final}", seed=seed)
            assert got == want, (got - want, want - got)
        print("OK")
    """)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


@pytest.mark.parametrize("schedule", ["paper", "xor"])
def test_distributed_incremental_merge_matches_oracle(schedule):
    """Beyond-paper warm-start merge: same bridges as the oracle end-to-end."""
    r = run_with_devices(f"""
        import jax
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,), ("machines",), axis_types=(AxisType.Auto,))
        from repro.core import find_bridges
        from repro.core.bridges_host import bridges_dfs
        from repro.graph import generators as gen
        for seed in range(3):
            src, dst, _ = gen.planted_bridge_graph(100, 2500, 3, seed=seed)
            want = bridges_dfs(src, dst, 100)
            got = find_bridges(src, dst, 100, mesh=mesh, machine_axes=("machines",),
                               schedule="{schedule}", final="device",
                               merge="incremental", seed=seed)
            assert got == want, (got - want, want - got)
        print("OK")
    """)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_retrieval_score_then_combine_matches_gather():
    """Score-then-combine retrieval (shard_map over the row-sharded table)
    must equal the plain gathered-embedding dot."""
    r = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.models import recsys as rec
        from repro.models.transformer import Parallelism

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = rec.SASRecConfig(n_items=1024, d=16, seq_len=10)
        params = rec.init_sasrec(cfg, jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        hist = jax.random.randint(key, (2, 10), 1, cfg.n_items)
        mask = jnp.ones((2, 10), bool)
        cands = jax.random.randint(key, (64,), 0, cfg.n_items)
        want = rec.retrieval_scores(params, hist, mask, cands, cfg, None)
        par = Parallelism(mesh=mesh, dp_axes=("data",), tp_axis="model")
        with jax.set_mesh(mesh):
            got = jax.jit(lambda p, h, m, c: rec.retrieval_scores(
                p, h, m, c, cfg, par))(params, hist, mask, cands)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_hierarchical_2d_mesh():
    r = run_with_devices("""
        import jax
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        from repro.core import find_bridges
        from repro.core.bridges_host import bridges_dfs
        from repro.graph import generators as gen
        src, dst, _ = gen.planted_bridge_graph(120, 3000, 4, seed=9)
        want = bridges_dfs(src, dst, 120)
        got = find_bridges(src, dst, 120, mesh=mesh, machine_axes=("data", "model"),
                           schedule="hierarchical", final="device", seed=9)
        assert got == want
        print("OK")
    """)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_xor_schedule_gives_answer_on_every_machine():
    """Beyond-paper property: after recursive doubling, *any* machine can
    serve the result (fault-tolerance redundancy)."""
    r = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,), ("machines",), axis_types=(AxisType.Auto,))
        from repro.core.merge import build_distributed_bridges_fn
        from repro.core.partition import partition_edges
        from repro.core.bridges_host import bridges_dfs
        from repro.graph import generators as gen
        src, dst, _ = gen.planted_bridge_graph(80, 1500, 3, seed=4)
        want = bridges_dfs(src, dst, 80)
        psrc, pdst, pmask = partition_edges(src, dst, 80, 8, seed=0)
        fn = build_distributed_bridges_fn(mesh, ("machines",), 80, "xor", "device")
        with jax.set_mesh(mesh):
            osrc, odst, omask = jax.jit(fn)(jnp.asarray(psrc), jnp.asarray(pdst), jnp.asarray(pmask))
        osrc, odst, omask = map(np.asarray, (osrc, odst, omask))
        for machine in range(8):
            got = set((int(min(a,b)), int(max(a,b)))
                      for a, b in zip(osrc[machine][omask[machine]], odst[machine][omask[machine]]))
            assert got == want, machine
        print("OK")
    """)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_partition_preserves_edges():
    import numpy as np

    from repro.core.partition import partition_edges
    from repro.graph import generators as gen

    src, dst = gen.random_graph(50, 400, seed=1)
    psrc, pdst, pmask = partition_edges(src, dst, 50, 8, seed=2)
    key = lambda s, d: sorted(zip(np.minimum(s, d).tolist(), np.maximum(s, d).tolist()))
    assert key(psrc[pmask], pdst[pmask]) == key(src, dst)
    assert pmask.sum() == len(src)
