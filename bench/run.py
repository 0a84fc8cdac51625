"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell named in ``BENCHMARK.json`` from its configuration, traffic
mix and metric files, warms every shape its traffic uses (set-up, reported
as ``setup_s``), measures the traffic for ``--seconds``, checks sampled
answers against the plain reference once the window has closed, and prints
one JSON line: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` a ``breakdown``), then the compared
numbers under ``checks``. With ``--trace 0`` the metrics are the cell's
end-to-end ones, with ``--trace 1`` its per-layer ones, read from a
profiler trace of the window.

Exits non-zero, printing no result line, where JAX finds no TPU or fewer
chips than the cell asks for. ``--control 1`` answers with the reference's
control in the program's place (it must come out not correct); the
benchmark's own runs leave it off.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the benchmark's modules are imported as ``bench.*``, never bare
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != ROOT / "bench"]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import device, harness

    try:
        cell = harness.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        return harness.fail(str(e))
    try:
        devs = device.require_chips(cell.chips)
        device.peaks(devs[0].device_kind)
    except (device.NoChip, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(f"# device {device.describe(devs)}", flush=True)
    try:
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), t_process=T_PROCESS,
                                devs=devs, control=bool(args.control))
    except Exception as e:  # the run's boundary: report, print no result
        return harness.fail(f"{type(e).__name__}: {e}")
    harness.report(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
