"""Smoke run of the bridges engine on TPU at the paper's size.

    python chip_smoke.py [--seed S]       # one chip: phases (a)-(g)
    python chip_smoke.py --four-chips     # the M = 4 mesh path only (4 chips)

Drives the main path once, through the entry points users call —
``core.find_bridges``, ``BridgeEngine`` and ``BridgeScheduler`` — on the
``configs/bridges_dense`` graph (|V| = 1e5, |E| = 1e7, the paper's Fig 2
point, with planted bridges), and checks every answer against the host
references (``core/bridges_host.py``, ``connectivity/host.py``). Each phase
prints one line: its name, ``ok``, its wall seconds (compilation included)
and the ``kernel_path`` it ran. The last line of stdout is one JSON object
naming the device. The script exits non-zero, and prints no JSON line,
when JAX finds no TPU or a phase fails: it never falls back to the CPU.

Everything runs in this one process, because a chip belongs to one process
at a time. Host references run on a worker thread while the device phases
compile and run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: planted bridges in the generated graph
N_BRIDGES = 5
#: streamed-ingest chunk (edges); 2^20 is one chunk bucket
CHUNK_EDGES = 1 << 20
#: edges of the vertex-kind graph (phase g). The one-shot cuts and bcc
#: programs run on the full edge buffer; at the 2^24 bucket the cuts program
#: asks for 15.7 GB of temporaries, at 2^23 for 7.6 GB (bcc: 7.4 GB). This
#: count lands in 2^23, the largest bucket that leaves one 16 GB chip
#: headroom.
VERTEX_KIND_EDGES = 8_000_000
#: width of the Pallas-kernel parity check (phase kernels)
KERNEL_PARITY_EDGES = 1 << 20


class SmokeFailure(Exception):
    """A phase's answer disagreed with its reference, or a device check
    failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def phase(name: str, path: str, fn):
    """Run one phase and print its line; returns what ``fn`` returns
    besides its detail string."""
    t0 = time.perf_counter()
    detail, out = fn()
    dt = time.perf_counter() - t0
    print(f"[{name}] ok {dt:.1f}s kernel_path={path} {detail}", flush=True)
    return out


def require_tpu(count: int):
    """Phase (a): the devices JAX found, or SmokeFailure naming what is
    missing."""
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU chip: jax.devices()[0].platform is "
          f"{devs[0].platform!r}")
    check(len(devs) >= count,
          f"{count} TPU chips needed, JAX found {len(devs)}")
    return devs


def pair_delete(s, d, ks, kd):
    """Host edge set minus every copy of the unordered pairs (ks, kd)."""
    n_key = int(max(s.max(initial=0), d.max(initial=0),
                    ks.max(initial=0), kd.max(initial=0))) + 1
    key = np.minimum(s, d).astype(np.int64) * n_key + np.maximum(s, d)
    dead = np.minimum(ks, kd).astype(np.int64) * n_key + np.maximum(ks, kd)
    keep = ~np.isin(key, dead)
    return s[keep], d[keep]


def generate(seed: int):
    """Phase (b): the bridges_dense graph at full size."""
    from repro.configs.bridges_dense import CONFIG
    from repro.graph.generators import planted_bridge_graph

    n = CONFIG.n_nodes
    t0 = time.perf_counter()
    src, dst, planted = planted_bridge_graph(n, CONFIG.n_edges, N_BRIDGES,
                                             seed=seed)
    dt = time.perf_counter() - t0
    check(dt < 60.0, f"generating the graph took {dt:.0f}s, not seconds")
    return f"|V|={n} |E|={len(src)} planted={len(planted)}", (
        n, src, dst, planted)


def round_timings(n, src, dst):
    """One warm Borůvka round of each path at full width (smoke timings,
    not a claim), and the kernel's answer against the XLA path's."""
    import jax
    import jax.numpy as jnp

    from repro.graph.datastructs import EdgeList, admission_capacity
    from repro.kernels.boruvka_round import boruvka_round

    nb = admission_capacity(n)
    el = EdgeList.from_arrays(src, dst, nb,
                              capacity=admission_capacity(len(src)))
    labels = jnp.arange(nb, dtype=jnp.int32)
    times, outs = {}, {}
    for use_pallas in (False, True):
        fn = jax.jit(lambda s, d, m, lb, up=use_pallas: boruvka_round(
            s, d, m, lb, nb, use_pallas=up))
        fn(el.src, el.dst, el.mask, labels).block_until_ready()  # compile
        t0 = time.perf_counter()
        outs[use_pallas] = fn(el.src, el.dst, el.mask,
                              labels).block_until_ready()
        times[use_pallas] = time.perf_counter() - t0
    check(np.array_equal(np.asarray(outs[True]), np.asarray(outs[False])),
          "Pallas Borůvka round differs from the XLA round at full width")
    return (f"n_bucket={nb} E_bucket={el.capacity} "
            f"xla_round_s={times[False]:.6f} "
            f"pallas_round_s={times[True]:.6f} pallas_matches_xla=True"), None


def kernel_parity(n, seed):
    """The path the chip does not run by default — the three Pallas
    kernels, compiled — against their ref.py oracles."""
    import jax.numpy as jnp

    from repro.kernels.boruvka_round.kernel import (
        boruvka_round_pallas,
        frontier_round_pallas,
    )
    from repro.kernels.boruvka_round.ref import (
        boruvka_round_ref,
        frontier_round_ref,
    )
    from repro.kernels.segment_min.kernel import segment_min_pallas
    from repro.kernels.segment_min.ref import segment_min_ref

    rng = np.random.default_rng(seed + 3)
    e = KERNEL_PARITY_EDGES
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    mask = jnp.asarray(rng.random(e) < 0.9)
    labels = jnp.asarray(rng.integers(0, n, n), jnp.int32)
    frontier = jnp.asarray(rng.random(n) < 0.05)
    visited = jnp.asarray(rng.random(n) < 0.3) | frontier

    def same(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b))

    check(same([boruvka_round_pallas(src, dst, mask, labels, n)],
               [boruvka_round_ref(src, dst, mask, labels, n)]),
          "boruvka_round_pallas differs from boruvka_round_ref")
    check(same(frontier_round_pallas(src, dst, mask, frontier, visited, n),
               frontier_round_ref(src, dst, mask, frontier, visited, n)),
          "frontier_round_pallas differs from frontier_round_ref")
    check(same([segment_min_pallas(src, labels[dst], n)],
               [segment_min_ref(src, labels[dst], n)]),
          "segment_min_pallas differs from segment_min_ref")
    return f"n={n} E={e} boruvka,frontier,segment_min == ref.py", None


def one_chip(args, pool) -> None:
    """Phases (b)-(g) on one chip; host references run on ``pool``."""
    from repro.connectivity.host import (
        articulation_points_dfs,
        host_bcc_labels,
        two_ecc_labels_dfs,
    )
    from repro.core import find_bridges
    from repro.core.bridges_host import bridges_dfs
    from repro.engine import BridgeScheduler, get_default_engine
    from repro.graph.generators import planted_bridge_graph
    from repro.kernels import kernel_path
    from repro.obs import MetricsRegistry

    path = kernel_path()
    n, src, dst, planted = phase("b/generate", "-",
                                 lambda: generate(args.seed))

    # (d)'s steps and edge sets, fixed up front so that their host
    # references run while the device works: 64 links inside the first
    # blob, then every link of one vertex v but one (that one becomes a
    # bridge), then the planted bridges (certificate edges: a rebuild)
    rng = np.random.default_rng(args.seed + 1)
    blob = n // (N_BRIDGES + 1)  # vertices [0, blob) lie in the first blob
    ins = (rng.integers(0, blob, 64).astype(np.int32),
           rng.integers(0, blob, 64).astype(np.int32))
    s1, d1 = np.concatenate([src, ins[0]]), np.concatenate([dst, ins[1]])
    ends = {v for e in planted for v in e}
    v = next(int(x) for x in rng.permutation(blob) if int(x) not in ends)
    links = np.flatnonzero((s1 == v) | (d1 == v))[1:]
    s2, d2 = pair_delete(s1, d1, s1[links], d1[links])
    planted_arr = np.array(sorted(planted), np.int32)
    s3, d3 = pair_delete(s2, d2, planted_arr[:, 0], planted_arr[:, 1])
    steps = [("insert_edges", ins),
             ("delete_edges", (s1[links], d1[links])),
             ("delete_edges", (planted_arr[:, 0], planted_arr[:, 1]))]
    sets = [(s1, d1), (s2, d2), (s3, d3)]
    want = {"bridges": pool.submit(bridges_dfs, src, dst, n),
            "2ecc": pool.submit(two_ecc_labels_dfs, src, dst, n)}
    want_steps = [pool.submit(bridges_dfs, s, d, n) for s, d in sets]

    phase("rounds", "oracle+pallas", lambda: round_timings(n, src, dst))
    phase("kernels", "pallas", lambda: kernel_parity(n, args.seed))

    eng = get_default_engine()  # the engine behind core.find_bridges

    def one_shot():
        got = find_bridges(src, dst, n)  # certificate on chip, Tarjan on host
        eng.load(src, dst, n)
        ref = {k: eng.current_analysis(kind=k) for k in want}
        wb = want["bridges"].result()
        check(got == wb, "find_bridges differs from bridges_dfs")
        check(ref["bridges"] == wb, "bridges differ from bridges_dfs")
        check(planted <= wb, "a planted bridge is missing")
        check(np.array_equal(ref["2ecc"], want["2ecc"].result()),
              "2ecc labels differ from two_ecc_labels_dfs")
        return (f"bridges={len(wb)} 2ecc_classes="
                f"{len(np.unique(ref['2ecc']))} == host"), ref

    ref = phase("c/one-shot", path, one_shot)

    def churn():
        before = eng.live_rebuilds["2ec"]
        got = []
        for (op, (ks, kd)), w in zip(steps, want_steps):
            got.append(getattr(eng, op)(ks, kd))
            check(got[-1] == w.result(),
                  f"{op} differs from the host recompute")
        check(any(v in e for e in got[1]),
              f"v={v}'s last link did not become a bridge")
        hits = eng.live_rebuilds["2ec"] - before
        check(hits >= 1, "deleting certificate edges rebuilt nothing")
        return (f"steps={len(steps)} rebuilds={hits} bridges_per_step="
                f"{[len(g) for g in got]} == host"), None

    phase("d/live-churn", path, churn)

    def streamed():
        eng.load_stream(src[:CHUNK_EDGES], dst[:CHUNK_EDGES], n,
                        chunk_edges=CHUNK_EDGES)
        for lo in range(CHUNK_EDGES, len(src), CHUNK_EDGES):
            eng.ingest_chunk(src[lo:lo + CHUNK_EDGES],
                             dst[lo:lo + CHUNK_EDGES])
        got = {k: eng.current_analysis(kind=k) for k in ref}
        check(got["bridges"] == ref["bridges"],
              "streamed bridges differ from the one-shot load")
        check(np.array_equal(got["2ecc"], ref["2ecc"]),
              "streamed 2ecc labels differ from the one-shot load")
        ing = eng.snapshot()["ingest"]
        return (f"chunks={ing['chunks']} chunk_bucket={ing['chunk_bucket']}"
                f" bridges,2ecc bit-identical to c"), None

    phase("e/streamed", path, streamed)

    def tenants():
        sched = BridgeScheduler(eng, metrics=MetricsRegistry())
        reqs = []
        for t in range(3):
            for r in range(2):
                s, d, _ = planted_bridge_graph(500 + 4 * r, 4000, 3,
                                               seed=args.seed + 10 * t + r)
                reqs.append((sched.submit(f"tenant{t}", s, d, 500 + 4 * r),
                             s, d, 500 + 4 * r))
        sched.drain_all()
        for tk, s, d, nq in reqs:
            check(tk.result() == bridges_dfs(s, d, nq),
                  f"ticket {tk.seq} ({tk.tenant}) differs from bridges_dfs")
        snap = sched.snapshot()
        check(snap["failed"] == 0, f"{snap['failed']} failed tickets")
        return (f"requests={len(reqs)} dispatches={snap['dispatches']} "
                f"failed=0 == host"), None

    phase("f/scheduler", path, tenants)

    def vertex_kinds():
        import jax

        s, d, pl = planted_bridge_graph(n, VERTEX_KIND_EDGES, N_BRIDGES,
                                        seed=args.seed + 2)
        w_cuts = pool.submit(articulation_points_dfs, s, d, n)
        w_bcc = pool.submit(host_bcc_labels, s, d, n)
        cuts = eng.analyze(s, d, n, kind="cuts")
        bcc = eng.analyze(s, d, n, kind="bcc")
        check(cuts == w_cuts.result(),
              "cuts differ from articulation_points_dfs")
        check({v for e in pl for v in e} <= cuts,
              "a planted bridge endpoint is not a cut vertex")
        check(bcc == w_bcc.result(), "bcc differs from host_bcc_labels")
        stats = jax.devices()[0].memory_stats() or {}
        return (f"|E|={len(s)} cuts={len(cuts)} blocks={len(bcc)} == host "
                f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}"), None

    phase("g/vertex-kinds", path, vertex_kinds)


def four_chips(args) -> None:
    """find_bridges on a mesh of the four chips (M = 4) under every merge
    schedule, against the one-chip answer and bridges_dfs."""
    import jax
    from jax.sharding import AxisType

    from repro.core import find_bridges
    from repro.core.bridges_host import bridges_dfs
    from repro.kernels import kernel_path

    path = kernel_path()
    n, src, dst, planted = phase("b/generate", "-",
                                 lambda: generate(args.seed))
    with ThreadPoolExecutor(max_workers=1) as pool:
        want = pool.submit(bridges_dfs, src, dst, n)

        def single():
            got = find_bridges(src, dst, n)
            check(got == want.result(), "one-chip answer differs from "
                                        "bridges_dfs")
            check(planted <= got, "a planted bridge is missing")
            return f"M=1 bridges={len(got)} == host", got

        one = phase("mesh/one-chip", path, single)
        flat = jax.make_mesh((4,), ("machines",), axis_types=(AxisType.Auto,))
        grid = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        for schedule, mesh in (("paper", flat), ("xor", flat),
                               ("hierarchical", grid)):
            def run(mesh=mesh, schedule=schedule):
                axes = tuple(mesh.axis_names)
                got = find_bridges(src, dst, n, mesh=mesh,
                                   machine_axes=axes, schedule=schedule,
                                   seed=args.seed)
                check(got == one, f"{schedule}: M=4 answer differs from "
                                  f"the one-chip answer")
                return (f"M=4 mesh={dict(mesh.shape)} bridges={len(got)} "
                        f"== one-chip == host"), None

            phase(f"mesh/{schedule}", path, run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the M = 4 mesh path, on four chips")
    args = ap.parse_args(argv)
    count = 4 if args.four_chips else 1
    try:
        devs = phase("a/device", "-", lambda: (
            "platform=tpu", require_tpu(count)))
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    print(f"# compile cache: {cache}", flush=True)
    try:
        if args.four_chips:
            four_chips(args)
        else:
            with ThreadPoolExecutor(max_workers=1) as pool:
                one_chip(args, pool)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
