"""Fig 9 (beyond-paper): per-kernel records for the connectivity rounds.

The byte counters come from the analytic traffic model in
``repro.kernels.boruvka_round.ops`` (9 B/edge "fused" vs 25 B/edge for
the three-pass lax sequence; 50 for the 2E-arc frontier round) and are
deterministic for a fixed world, so ``scripts/check_bench.py`` pins them
EXACTLY (``bytes_fused=``/``bytes_lax=``), alongside the measured Borůvka
round count (``boruvka_rounds=``) of the fixed planted world. The model
predates the kernels' current form (XLA gathers ahead of them, one pass
per segment tile), so the "fused" figure is a model, not what the kernels
read (DESIGN.md §Kernels). Wall times on shared CI runners are noise.

Per kernel, two timed operating points on the same world:

  * auto   — the dispatched production path (``use_pallas`` unset): the
    XLA path on every platform. The ``path=`` token records which, so
    numbers are attributable to a code path.
  * oracle — the same XLA sequence requested by name
    (``use_pallas=False``).

A closing parity sanity check runs the interpret-mode kernels against the
oracles on a small multigraph buffer — the smoke run refuses to report
numbers for kernels that are not bit-exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, timeit
from repro.core.forest import scan_first_forest_ex, spanning_forest_ex
from repro.graph import generators as gen
from repro.graph.datastructs import EdgeList
from repro.kernels.boruvka_round.kernel import (
    boruvka_round_pallas,
    frontier_round_pallas,
)
from repro.kernels.boruvka_round.ops import (
    boruvka_round,
    boruvka_round_bytes,
    frontier_round,
    frontier_round_bytes,
    kernel_path,
)
from repro.kernels.boruvka_round.ref import (
    boruvka_round_ref,
    frontier_round_ref,
)


def _parity_check():
    """Interpret-mode kernels vs oracles on a masked multigraph buffer."""
    rng = np.random.default_rng(9)
    e, n = 96, 40
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    mask = jnp.asarray(rng.random(e) > 0.2)
    labels = jnp.asarray(rng.integers(0, n, n), jnp.int32)
    got = boruvka_round_pallas(src, dst, mask, labels, n, interpret=True)
    want = boruvka_round_ref(src, dst, mask, labels, n)
    assert np.array_equal(np.asarray(got), np.asarray(want)), \
        "boruvka_round interpret-mode parity failed"
    frontier = jnp.asarray(rng.random(n) < 0.4)
    visited = jnp.asarray(rng.random(n) < 0.5) | frontier
    gp, ge = frontier_round_pallas(src, dst, mask, frontier, visited, n,
                                   interpret=True)
    wp, we = frontier_round_ref(src, dst, mask, frontier, visited, n)
    assert np.array_equal(np.asarray(gp), np.asarray(wp)) and \
        np.array_equal(np.asarray(ge), np.asarray(we)), \
        "frontier_round interpret-mode parity failed"


def run(out, smoke: bool = False):
    v, e = (512, 2048) if smoke else (4096, 32768)
    src, dst, _ = gen.planted_bridge_graph(v, e, n_bridges=3, seed=0)
    el = EdgeList.from_arrays(src, dst, v)
    E = int(el.src.shape[0])  # buffer capacity, what the kernels stream
    labels = jnp.arange(v, dtype=jnp.int32)
    frontier = jnp.zeros(v, bool).at[0].set(True)
    visited = frontier

    bb_f, bb_l = boruvka_round_bytes(E, True), boruvka_round_bytes(E, False)
    fb_f, fb_l = frontier_round_bytes(E, True), frontier_round_bytes(E, False)
    # the acceptance bound, enforced by the bench run itself
    assert 2 * bb_f <= bb_l and 2 * fb_f <= fb_l, \
        f"fused path must move <= half the lax bytes ({bb_f} vs {bb_l})"
    path = kernel_path()

    def bor(up):
        return jax.jit(lambda s, d, m, lb: boruvka_round(
            s, d, m, lb, v, use_pallas=up))

    t = timeit(bor(None), el.src, el.dst, el.mask, labels)
    out.append(csv_row(
        "fig9/boruvka_round_auto", t,
        f"V={v} E={E} path={path} bytes_fused={bb_f} bytes_lax={bb_l}"))
    t_lax = timeit(bor(False), el.src, el.dst, el.mask, labels)
    out.append(csv_row("fig9/boruvka_round_oracle", t_lax,
                       f"V={v} E={E} path=oracle 3-pass lax baseline"))

    def fro(up):
        return jax.jit(lambda s, d, m, f, vis: frontier_round(
            s, d, m, f, vis, v, use_pallas=up))

    t = timeit(fro(None), el.src, el.dst, el.mask, frontier, visited)
    out.append(csv_row(
        "fig9/frontier_round_auto", t,
        f"V={v} E={E} path={path} bytes_fused={fb_f} bytes_lax={fb_l}"))
    t_lax = timeit(fro(False), el.src, el.dst, el.mask, frontier, visited)
    out.append(csv_row("fig9/frontier_round_oracle", t_lax,
                       f"V={v} E={E} path=oracle 2E-arc lax baseline"))

    # end-to-end hooking loop on the same fixed world: the measured round
    # count is deterministic and pinned exactly — a boruvka_rounds drift
    # means the hooking/contraction schedule changed, the regression the
    # roofline's calibrated model would silently absorb
    t_forest = timeit(lambda: spanning_forest_ex(el))
    _, _, rounds = spanning_forest_ex(el)
    rounds = int(rounds)
    total_fused = rounds * bb_f
    out.append(csv_row(
        "fig9/forest_end_to_end", t_forest,
        f"V={v} E={E} path={path} boruvka_rounds={rounds} "
        f"round_bytes_fused={bb_f}"))
    _, _, _, _, sfs_rounds = scan_first_forest_ex(el)
    out.append(csv_row(
        "fig9/sfs_end_to_end", timeit(lambda: scan_first_forest_ex(el)),
        f"V={v} E={E} path={path} sfs_rounds={int(sfs_rounds)}"))

    _parity_check()
    out.append(csv_row(
        "fig9/parity_interpret_vs_oracle", 0.0,
        f"bit-exact on masked multigraph; total_fused_bytes={total_fused}"))
    return out
