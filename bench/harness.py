"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to a configuration, a traffic mix or a metric is
found by name (see ``bench/__init__.py``); nothing here names a cell.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path):
    """Import one plugin file by path (names may hold '.' and '-')."""
    name = "bench_plugin_" + "".join(c if c.isalnum() else "_"
                                     for c in str(path.relative_to(BENCH)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with everything its names point at."""

    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(workload: str, spec_path: Path = ROOT / "BENCHMARK.json"):
    spec = json.loads(spec_path.read_text())
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in {spec_path.name}; "
                       f"known: {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    mix = json.loads((BENCH / "mixes" / f"{w['traffic']}.json").read_text())

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(workload, int(w["chips"]), config, mix,
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)])


@dataclasses.dataclass
class Op:
    """One request or operation of the measured traffic."""

    kind: str
    due: float     # host clock: when it was due (closed loop: sent)
    end: float     # host clock: when its answer was in hand
    ok: bool
    answer: object = None
    arg: object = None   # what the check needs to recompute the answer
    edges: int = 0       # edges of the graph it analysed


@dataclasses.dataclass
class Check:
    """A number compared with the reference, and its limit (<=)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""

    cell: Cell
    ops: list[Op]
    window: tuple[float, float]
    setup_s: float
    driver: object
    trace: object = None      # tracefile.Trace of a --trace 1 run
    peaks: dict | None = None


def _annotating_tracer():
    """The engine's own span tracer, writing each span into the profiler
    trace as a host annotation, so that idle gaps can be named by what the
    host was doing (pad and upload, dispatch, readback and convert)."""
    import jax
    from repro.obs.tracer import Span, Tracer

    class AnnotatedSpan(Span):
        __slots__ = ("_ann",)

        def __enter__(self):
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
            return super().__enter__()

        def __exit__(self, exc_type, exc, tb):
            try:
                return super().__exit__(exc_type, exc, tb)
            finally:
                self._ann.__exit__(exc_type, exc, tb)

    class AnnotatingTracer(Tracer):
        def span(self, name: str, **attrs):
            return AnnotatedSpan(self, name, attrs)

        __call__ = span

        def _reserve(self) -> int:
            return -1  # spans live in the profile, not in memory

        def _commit(self, sp) -> None:
            pass

    return AnnotatingTracer()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, devs=None, control: bool = False,
             log=print) -> dict:
    """Set up, measure, check; returns the result line as a dict.

    ``devs`` are the chips (None: the test path, which skips the chip and
    reports no device numbers). ``control`` puts the reference's control
    in the program's place.
    """
    import jax

    from bench import device as device_mod
    from bench import tracefile

    peaks = device_mod.peaks(devs[0].device_kind) if devs else None
    if devs:
        from repro.launch.compile_cache import use_compile_cache

        # small programs too: every run after the first compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        log(f"# compile cache: {use_compile_cache()}")
    gen = load_module(BENCH / "graphs" / f"{cell.config['generator']}.py")
    t_graph = time.perf_counter()
    # a deployment serves one graph: where the configuration fixes its seed,
    # the run's seed draws only the traffic
    graph = gen.generate(cell.config,
                         int(cell.config.get("graph_seed", seed)))
    t_driver = time.perf_counter()
    drv = load_module(BENCH / "drivers" / f"{cell.mix['driver']}.py").Driver(
        cell.config, cell.mix, graph, seed, devs=devs, control=control)
    drv.setup(seconds)
    traces0 = drv.traces()
    setup_s = time.perf_counter() - t_process
    log(f"# setup_s={setup_s:.3f} (to the graph {t_graph - t_process:.1f}s, "
        f"graph {t_driver - t_graph:.1f}s, driver set-up "
        f"{t_process + setup_s - t_driver:.1f}s) graph n={graph.n} "
        f"E={graph.n_edges}")

    tmp = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None
    try:
        with (tracefile.capture(tmp) if trace
              else contextlib.nullcontext()):
            if trace:
                from repro import obs
                obs.enable_tracing(_annotating_tracer())
            try:
                with jax.profiler.TraceAnnotation(tracefile.WINDOW_SPAN):
                    t0 = time.perf_counter()
                    ops = drv.window(t0, seconds)
                    t1 = max([t0] + [o.end for o in ops])
            finally:
                if trace:
                    from repro import obs
                    obs.disable_tracing()
        compiled = drv.traces() - traces0
        if compiled:
            raise RuntimeError(f"{compiled} programs were traced inside the "
                               f"measured window")
        mem = device_mod.memory_peak_bytes(devs) if devs else None
        summary = drv.summary(ops)
        drv.release()
        t_check = time.perf_counter()
        checks = drv.check(ops)
        log(f"# check took {time.perf_counter() - t_check:.1f}s")
        tr = tracefile.read(tmp) if trace else None
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)

    run = Run(cell, ops, (t0, t1), setup_s, drv, tr, peaks)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {
        "correct": bool(checks) and all(c.ok for c in checks),
        "attempted": len(ops),
        "failed": sum(not o.ok for o in ops),
        "metrics": metrics,
        "device": dict(device_mod.describe(devs) if devs else {},
                       memory_peak_bytes=mem),
        "summary": summary,
    }
    if trace:
        line["device"]["busy_s"] = tr.busy_s()
        line["device"]["window_s"] = tr.window_s
        line["breakdown"] = {"device_ops": tr.top_ops(10),
                             "idle_gaps": tr.idle_gaps(10)}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    return line


def report(line: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout."""
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})", file=err,
              flush=True)
    print(json.dumps(line), file=out, flush=True)


def fail(msg: str) -> int:
    traceback.print_exc()
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 1
