# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness:

    PYTHONPATH=src python -m benchmarks.run             # all paper figures
    PYTHONPATH=src python -m benchmarks.run --only fig2
    PYTHONPATH=src python -m benchmarks.run --only fig5 --smoke
    PYTHONPATH=src python -m benchmarks.run --json BENCH_fig5.json --only fig5

``--smoke`` shrinks problem sizes for CI-on-CPU sanity runs (numbers are not
comparable across modes). ``--json PATH`` additionally writes the rows as
``[{name, us_per_call, derived}, ...]`` records so PRs can check in
``BENCH_*.json`` trajectory files.

``--trace`` runs every selected figure under the span tracer
(``repro.obs``): each figure gets a ``bench/<fig>`` root span, and
afterwards its per-stage rollup — certificate-build / merge / final-stage /
kernel-round span totals — is written as a JSON artifact (``--trace-json``,
default ``BENCH_trace_rollup.json``) together with a ``<fig>/trace`` CSV
record carrying the span/stage counts (deterministic for the figures' fixed
operating sequences, so ``scripts/check_bench.py`` gates them EXACTLY
against ``BENCH_baseline_trace.json``) and the staged-time coverage of the
figure's wall clock. Tracing must not perturb the non-trace records: spans
wrap host dispatch only, and the figures' trace-only extras are gated on
``tracer.enabled``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def rows_to_records(rows: list[str]) -> list[dict]:
    """CSV rows (after the header) -> {name, us_per_call, derived} records."""
    records = []
    for row in rows:
        name, us, derived = row.split(",", 2)
        records.append(
            {"name": name, "us_per_call": float(us), "derived": derived})
    return records


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig2,fig3,fig4,fig5,fig6,fig7,fig8,"
                         "fig9,fig10,fig11,fig12")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problem sizes (CI sanity, not for comparison)")
    ap.add_argument("--json", dest="json_path", default=None, metavar="PATH",
                    help="also write records as JSON to PATH")
    ap.add_argument("--trace", action="store_true",
                    help="run each figure under the span tracer and emit "
                         "per-stage rollups + <fig>/trace records")
    ap.add_argument("--trace-json", default="BENCH_trace_rollup.json",
                    metavar="PATH",
                    help="with --trace: stage-rollup artifact path")
    args = ap.parse_args()
    which = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        fig10_serving,
        fig11_failover,
        fig12_streaming,
        fig2_machines,
        fig3_vertices,
        fig4_edges,
        fig5_baseline,
        fig6_engine,
        fig7_connectivity,
        fig8_distributed_kinds,
        fig9_kernels,
    )

    benches = {
        "fig2": fig2_machines.run,
        "fig3": fig3_vertices.run,
        "fig4": fig4_edges.run,
        "fig5": fig5_baseline.run,
        "fig6": fig6_engine.run,
        "fig7": fig7_connectivity.run,
        "fig8": fig8_distributed_kinds.run,
        "fig9": fig9_kernels.run,
        "fig10": fig10_serving.run,
        "fig11": fig11_failover.run,
        "fig12": fig12_streaming.run,
    }
    if which and not which <= set(benches):
        ap.error(f"unknown figure(s) {sorted(which - set(benches))}; "
                 f"choose from {sorted(benches)}")
    if args.json_path:
        try:  # fail on an unwritable path now, not after minutes of timing
            existed = os.path.exists(args.json_path)
            open(args.json_path, "a").close()
            if not existed:  # don't leave a bogus empty BENCH_*.json behind
                os.unlink(args.json_path)
        except OSError as e:
            ap.error(f"--json {args.json_path}: {e}")
    tracer = None
    rollups: dict = {}
    if args.trace:
        from repro import obs
        tracer = obs.enable_tracing()

    out: list[str] = ["name,us_per_call,derived"]
    for name, fn in benches.items():
        if which and name not in which:
            continue
        print(f"# running {name} ...", file=sys.stderr, flush=True)
        if tracer is None:
            fn(out, smoke=args.smoke)
            continue
        from benchmarks.common import csv_row
        tracer.reset()
        with tracer.span(f"bench/{name}") as root:
            fn(out, smoke=args.smoke)
        stages = tracer.stage_rollup()
        staged = sum(r["total_s"] for r in stages.values())
        coverage = staged / max(root.dur, 1e-9)
        rollups[name] = {"wall_s": root.dur, "staged_s": staged,
                         "coverage": coverage, "stages": stages}
        # spans/stages counts are deterministic for the figures' fixed
        # operating sequences -> EXACT-gated vs BENCH_baseline_trace.json;
        # coverage_pct is wall-clock-dependent and deliberately written as
        # a float so the counter gate ignores it
        out.append(csv_row(
            f"{name}/trace", root.dur,
            f"spans={len(tracer.spans())} stages={len(stages)} "
            f"coverage_pct={coverage * 100:.1f}"))
        print(f"# {name}: {len(tracer.spans())} spans, {len(stages)} "
              f"stages, staged {staged:.3f}s / wall {root.dur:.3f}s "
              f"({coverage * 100:.1f}%)", file=sys.stderr, flush=True)
    print("\n".join(out), flush=True)
    if tracer is not None:
        from repro import obs
        obs.disable_tracing()
        with open(args.trace_json, "w") as f:
            json.dump(rollups, f, indent=2)
            f.write("\n")
        print(f"# wrote stage rollups to {args.trace_json}",
              file=sys.stderr, flush=True)
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(rows_to_records(out[1:]), f, indent=2)
            f.write("\n")
        print(f"# wrote {len(out) - 1} records to {args.json_path}",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
