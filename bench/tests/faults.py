"""Shared set-up of the fault tests: the harness's own run, on the CPU at a
test size, with the chip look skipped."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

E2E = [{"name": "setup_s", "unit": "s"}]

#: the dense configuration's shape (blobs joined by planted bridges) at a
#: size a test holds
DENSE = {"generator": "planted", "n_nodes": 300, "n_edges": 4000,
         "n_bridges": 5}
#: the Graph500 generator at scale 9
KRON = {"generator": "kronecker", "scale": 9, "edgefactor": 16,
        "A": 0.57, "B": 0.19, "C": 0.19}


def mix(name: str, **over) -> dict:
    m = json.loads((harness.BENCH / "mixes" / f"{name}.json").read_text())
    m.update(over)
    return m


def run(config: dict, mix_: dict, seed: int = 2**31 + 17,
        seconds: float = 2.0, control: bool = False) -> dict:
    cell = harness.Cell("test", 1, config, mix_, E2E, [])
    return harness.run_cell(cell, seed, seconds, False,
                            t_process=time.perf_counter(), control=control,
                            log=lambda *a: None)


def alter(answer):
    """An answer with one bridge changed where it is produced."""
    out = set(answer)
    if out:
        out.pop()
    out.add((0, 10**6))
    return out
