"""The harness refuses to measure without a chip, refuses a device it has
no peaks for, and finds every file ``BENCHMARK.json`` names (CPU only)."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import device, harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_cpu_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    w = SPEC["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", w,
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_unknown_device_kind_is_an_error():
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        device.peaks("TPU v99")


def test_cpu_devices_are_refused():
    with pytest.raises(device.NoChip):
        device.require_chips(1)


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(workload):
    cell = harness.load_cell(workload)
    assert cell.chips in (1, 4)
    harness.load_module(harness.BENCH / "graphs"
                        / f"{cell.config['generator']}.py")
    harness.load_module(harness.BENCH / "drivers"
                        / f"{cell.mix['driver']}.py")
    for m in cell.end_to_end + cell.per_layer:
        assert hasattr(harness.load_module(
            harness.BENCH / "metrics" / f"{m['name']}.py"), "read")
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_benchmark_json_names_and_units():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "workloads" in m
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
    for w in SPEC["workloads"]:
        assert (harness.BENCH / "mixes" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200
