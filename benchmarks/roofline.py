"""Roofline report: reads artifacts/dryrun/*.json, emits the per-cell table
(three terms in seconds, dominant bottleneck, MODEL_FLOPS/HLO_FLOPs ratio,
one-line improvement note) as markdown for EXPERIMENTS.md §Roofline.

    PYTHONPATH=src python -m benchmarks.roofline [--dir artifacts/dryrun]
"""
from __future__ import annotations

import argparse
import json
import math
from pathlib import Path

from benchmarks.model_flops import bridges_model, model_flops_for
from repro.configs import get
from repro.launch.hlo_analysis import HW

ART = Path(__file__).resolve().parent.parent / "artifacts" / "dryrun"

IMPROVE_NOTES = {
    "compute": "compute-bound: reduce remat recompute / raise MXU utilization "
    "(larger per-device microbatch, fused kernels)",
    "memory": "memory-bound: fuse elementwise chains + cast activations bf16; "
    "HLO bytes are an unfused upper bound (see methodology note)",
    "collective": "collective-bound: re-shard to cut resharding collectives / "
    "overlap collectives with compute (latency-hiding scheduler)",
}


def load_records(art_dir: Path):
    recs = []
    for p in sorted(art_dir.glob("*.json")):
        recs.append(json.loads(p.read_text()))
    return recs


def fmt_s(x):
    return f"{x:.2e}" if x is not None else "-"


def wire_collective_s(rec) -> float | None:
    """Ring-wire refinement of the collective term: all-reduce moves ~2x its
    payload on the wire (reduce-scatter + all-gather); every other kind ~1x
    ((g-1)/g ~ 1 at g=16). The per-kind mix comes from the direct-HLO
    counts recorded in every artifact; the factor scales the (possibly
    probe-extrapolated) t_collective_s consistently."""
    coll = rec.get("collectives")
    r = rec.get("roofline")
    if not coll or not r:
        return None
    b = coll["bytes"]
    total = sum(b.values())
    factor = ((total + b.get("all-reduce", 0)) / total) if total else 1.0
    return r["t_collective_s"] * factor


def build_table(recs, mesh_kind: str = "single"):
    rows = []
    for rec in recs:
        if rec.get("mesh") != mesh_kind:
            continue
        arch, shape = rec["arch"], rec["shape"]
        if rec["status"] == "skipped":
            rows.append(
                f"| {arch} | {shape} | skipped | - | - | - | - | - | - | "
                f"{rec['reason'][:70]} |"
            )
            continue
        if rec["status"] != "ok":
            rows.append(
                f"| {arch} | {shape} | ERROR | - | - | - | - | - | - | "
                f"{rec['error'][:70]} |"
            )
            continue
        spec = get(arch)
        n_chips = rec["n_chips"]
        r = rec["roofline"]
        if spec.family == "graph":
            # analytic model supplies the honest terms (HLO counts loop
            # bodies once for the data-dependent Borůvka rounds); memory
            # term is the "fused" byte model (9 B/edge/round), which no
            # longer matches the kernels or the XLA path the chip runs
            # (DESIGN.md §Kernels); the lax 25 B/edge term is kept for
            # the delta note
            am = bridges_model(spec.shapes[shape], n_chips, fused=True)
            am_lax = bridges_model(spec.shapes[shape], n_chips, fused=False)
            t_c = am["model_ops"] / (HW["peak_flops"] / 2)  # int ops on VPU
            t_m = am["memory_bytes_per_device"] / HW["hbm_bw"]
            t_n = am["collective_bytes_per_device"] / HW["ici_bw"]
            t_m_lax = am_lax["memory_bytes_per_device"] / HW["hbm_bw"]
            dom = max([("compute", t_c), ("memory", t_m), ("collective", t_n)],
                      key=lambda kv: kv[1])[0]
            ratio = 1.0
            note = ("analytic model, fused boruvka_round path (lax 3-pass "
                    f"t_mem {t_m_lax:.2e}s, {t_m_lax / max(t_m, 1e-30):.1f}x);"
                    f" HLO sched: {rec['collectives']['counts']}")
            rows.append(
                f"| {arch} | {shape} | {dom} | {fmt_s(t_c)} | {fmt_s(t_m)} |"
                f" {fmt_s(t_n)} | {fmt_s(t_n)} | {ratio:.2f} | "
                f"{min(t_c / max(t_c, t_m, t_n), 1):.2f} | {note[:90]} |"
            )
            continue
        mf = model_flops_for(spec, shape, n_chips)
        hlo_global = r["hlo_flops_per_device"] * n_chips
        ratio = (mf / hlo_global) if (mf and hlo_global) else float("nan")
        frac = r["roofline_fraction"]
        note = IMPROVE_NOTES[r["dominant"]]
        rows.append(
            f"| {arch} | {shape} | {r['dominant']} |"
            f" {fmt_s(r['t_compute_s'])} | {fmt_s(r['t_memory_s'])} |"
            f" {fmt_s(r['t_collective_s'])} | {fmt_s(wire_collective_s(rec))} |"
            f" {ratio:.2f} | {frac:.2f} |"
            f" {note[:90]} |"
        )
    header = (
        f"| arch | shape | bottleneck | t_compute (s) | t_memory (s) |"
        f" t_collective (s) | t_coll wire (s) | MODEL/HLO | roofline frac | note |\n"
        f"|---|---|---|---|---|---|---|---|---|---|"
    )
    return header + "\n" + "\n".join(rows)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default=str(ART))
    ap.add_argument("--mesh", default="single")
    args = ap.parse_args()
    recs = load_records(Path(args.dir))
    print(f"## Roofline ({args.mesh}-pod mesh)\n")
    print(f"HW: {HW['peak_flops']/1e12:.0f} TF/s bf16, "
          f"{HW['hbm_bw']/1e9:.0f} GB/s HBM, {HW['ici_bw']/1e9:.0f} GB/s ICI\n")
    print(build_table(recs, args.mesh))


if __name__ == "__main__":
    main()
