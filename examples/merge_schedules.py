"""Compare the paper's merge schedule against the beyond-paper variants.

Runs the full distributed pipeline on a (2, M/2) mesh of every device for
every (schedule x merge) combination, verifies all six give IDENTICAL
bridges, and prints wall time per variant, compilation included. On the
CPU, 8 virtual devices stand in for the machines.

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/merge_schedules.py
    PYTHONPATH=src python examples/merge_schedules.py   # TPU host
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
    n, m = 1_500, 120_000
    src, dst, planted = gen.planted_bridge_graph(n, m, n_bridges=5, seed=7)
    want = bridges_dfs(src, dst, n)
    print(f"graph: |V|={n} |E|={len(src)}; oracle bridges: {len(want)}")

    n_dev = len(jax.devices())
    mesh = jax.make_mesh((min(n_dev, 2), max(n_dev // 2, 1)),
                         ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    for schedule in ("paper", "xor", "hierarchical"):
        for merge in ("recertify", "incremental"):
            t0 = time.time()
            got = find_bridges(
                src, dst, n, mesh=mesh, machine_axes=("data", "model"),
                schedule=schedule, merge=merge, final="device", seed=7,
            )
            dt = time.time() - t0
            assert got == want, f"{schedule}/{merge} mismatch!"
            print(f"  {schedule:>12} x {merge:<11} -> {len(got)} bridges "
                  f"({dt * 1e3:7.1f} ms, compile+run)")
    print("all six variants agree with the host Tarjan oracle: OK")
    print("""
schedule semantics (EXPERIMENTS.md SPerf C for the roofline deltas):
  paper        — faithful idle-half tree reduction (machine 2k+1 sends to 2k)
  xor          — recursive doubling: no idle machines; EVERY machine ends
                 with the global certificate (any machine can serve the
                 final stage — free fault-tolerance redundancy)
  hierarchical — multi-pod: merge intra-pod axes first so only one
                 certificate-sized message crosses the DCI per pod pair
merge semantics:
  recertify    — paper-faithful: re-certify the 4(n-1) union every phase
  incremental  — warm-start delta forests over the received buffer only
                 (measured 7.4x less merge memory traffic at the fig2 scale)
""")


if __name__ == "__main__":
    main()
