"""The paper's full distributed pipeline on a mesh of every device:
partition -> per-machine sparse certificates -> log-phase merge ->
bridge extraction, all one XLA program. On a TPU host the machines are
the chips; on the CPU, 8 virtual devices stand in for them.

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/distributed_bridges.py
    PYTHONPATH=src python examples/distributed_bridges.py   # TPU host
"""
import os

if os.environ.get("JAX_PLATFORMS") == "cpu":
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

import time

import jax
from jax.sharding import AxisType

from repro.core import find_bridges
from repro.core.bridges_host import bridges_dfs
from repro.graph import generators as gen


def main():
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("machines",), axis_types=(AxisType.Auto,))
    n, m = 3_000, 150_000
    src, dst, planted = gen.planted_bridge_graph(n, m, n_bridges=8, seed=7)
    print(f"|V|={n} |E|={len(src)} on M={mesh.devices.size} machines")

    want = bridges_dfs(src, dst, n)
    for schedule in ("paper", "xor", "hierarchical"):
        axes = ("machines",)
        if schedule == "hierarchical":
            mesh2 = jax.make_mesh((max(n_dev // 2, 1), min(n_dev, 2)),
                                  ("data", "model"),
                                  axis_types=(AxisType.Auto,) * 2)
            t0 = time.time()
            got = find_bridges(src, dst, n, mesh=mesh2,
                               machine_axes=("data", "model"),
                               schedule=schedule, final="device")
        else:
            t0 = time.time()
            got = find_bridges(src, dst, n, mesh=mesh, machine_axes=axes,
                               schedule=schedule, final="device")
        dt = time.time() - t0
        status = "OK" if got == want else f"MISMATCH {got ^ want}"
        print(f"  schedule={schedule:>12}: {len(got)} bridges in "
              f"{dt*1e3:.0f}ms (incl. compile) — {status}")
    assert planted <= want
    print("planted bridges all found:", sorted(planted)[:4], "...")


if __name__ == "__main__":
    main()
