"""Analysis registry: the final stage of the pipeline as pluggable data.

The paper's pipeline ends in one hard-coded final stage (bridge extraction
on the merged certificate). This module turns that stage into a first-class
**registry of Analysis descriptors** — one per query kind — so every
consumer (``BridgeEngine`` single/batched/incremental dispatch, the vmapped
``engine/batched.py`` pipelines, and the distributed
``core/merge.py::build_distributed_analysis_fn``) resolves kinds through
one table instead of per-kind if/elif ladders. Registering a new kind here
makes it servable on every substrate with zero engine changes.

Each ``Analysis`` declares:

* ``certificate`` — the kind's DEFAULT sparse certificate, named into the
  certificate registry (``core.certs``): ``"2ec"`` (Borůvka forest pair;
  bridges / 2ECC / bridge tree) or ``"sfs"`` (scan-first-search BFS-layer
  forest pair; articulation points / biconnected blocks — vertex
  connectivity, which arbitrary forests provably do not preserve;
  DESIGN.md §Connectivity). Engine callers may override it per query with
  any registered certificate that preserves at least what the default
  does (e.g. ``"hybrid"`` for the vertex kinds). All registered types
  live in 2(n−1)-slot buffers and compose under union-merge, so every
  kind rides the same merge schedules.
* ``device_fn`` — the traced final stage over the shared ``tour_state``.
* ``host_fn`` — the sequential host reference (also the ``final='host'``
  answering stage, run on the certificate's edges).
* ``to_result`` — device buffers → host-facing result.
* ``out_struct`` — the declared fixed result-buffer shapes, checkable with
  ``jax.eval_shape`` (the §Buffers contract for the kind's output).
* ``incremental`` — servable from the engine's live certificate state.
* ``decremental`` — servable under edge DELETIONS from the live state via
  the tombstone + certificate-hit rebuild rule (DESIGN.md §Decremental).
  True for every built-in kind: the rule is certificate-level, so any kind
  whose certificate type composes under union inherits it.

See DESIGN.md §Analysis registry for the kind × substrate matrix.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import numpy as np

from repro.connectivity.device import (
    articulation_from_state,
    bcc_from_state,
    blocks_to_sets,
    bridge_tree_from_state,
    two_ecc_from_state,
)
from repro.connectivity.host import (
    articulation_points_dfs,
    bridge_tree_dfs,
    host_bcc_labels,
    two_ecc_labels_dfs,
)
from repro.core.bridges_host import bridges_dfs
from repro.core.certs import certificate_names, get_certificate
from repro.graph.datastructs import INT, EdgeList, compact_edges


@dataclasses.dataclass(frozen=True)
class Analysis:
    """Descriptor for one connectivity query kind.

    device_fn : (src, dst, mask, n, tour_state, out_cap) -> device buffers
    host_fn   : (src, dst, n_nodes) -> host-facing reference result
    to_result : (device buffers, n_nodes) -> host-facing result
    out_struct: (n_nodes, capacity) -> pytree of jax.ShapeDtypeStruct
                (capacity = the buffer the final stage ran on)

    ``device_input`` picks the buffer one-shot (single/batched) device
    queries run on: ``"certificate"`` shrinks the tour to 2(n−1) slots
    first (right for the 2-edge kinds: the paper's pipeline shape, cheap
    on dense buffers), ``"full"`` runs the tour directly on the input
    buffer (right for the vertex kinds: every tour primitive is
    polylog-round, whereas building the SFS certificate costs O(diameter)
    BFS rounds — the certificate is only needed where a bounded exchange
    format is, i.e. final='host', distributed merges, incremental state).
    """

    kind: str
    result: str
    certificate: str
    incremental: bool
    device_fn: Callable
    host_fn: Callable
    to_result: Callable
    out_struct: Callable
    device_input: str = "certificate"
    decremental: bool = True


_REGISTRY: dict[str, Analysis] = {}

_ALIASES = {"two_ecc": "2ecc", "blocks": "bcc"}


def register(analysis: Analysis) -> Analysis:
    """Add (or replace) a kind; returns the descriptor for chaining.

    ``analysis.certificate`` must name a descriptor in the certificate
    registry (``core.certs``) — the kind's declared default, which every
    substrate resolves through that registry."""
    if analysis.certificate not in certificate_names():
        raise ValueError(
            f"unknown certificate type {analysis.certificate!r}; choose "
            f"from {certificate_names()}")
    _REGISTRY[analysis.kind] = analysis
    return analysis


def analysis_kinds() -> tuple[str, ...]:
    """Canonical names of every registered kind, in registration order."""
    return tuple(_REGISTRY)


def normalize_kind(kind: str) -> str:
    k = str(kind).replace("-", "_").lower()
    k = _ALIASES.get(k, k)
    if k not in _REGISTRY:
        raise ValueError(
            f"unknown analysis kind {kind!r}; choose from {analysis_kinds()}")
    return k


def get_analysis(kind: str) -> Analysis:
    """Look up a descriptor by (normalized) kind name."""
    return _REGISTRY[normalize_kind(kind)]


def certificate_fn(certificate: str) -> Callable:
    """The certificate builder an analysis runs on: (EdgeList, capacity) ->
    (EdgeList in a fixed 2(n−1)-slot buffer, int32[2] forest rounds,
    int32[2] of them full-width), resolved via ``core.certs``
    (``Certificate.build_ex``)."""
    return get_certificate(certificate).build_ex


# ------------------------------------------------------- shared result glue
def _pair_set(out, n_nodes: int) -> set[tuple[int, int]]:
    s, d, m = (np.asarray(x) for x in out)
    s, d = s[m], d[m]
    return set((int(min(a, b)), int(max(a, b))) for a, b in zip(s, d))


def _edge_buffer_struct(n: int, cap: int):
    oc = max(n - 1, 1)
    return (jax.ShapeDtypeStruct((oc,), INT),
            jax.ShapeDtypeStruct((oc,), INT),
            jax.ShapeDtypeStruct((oc,), np.bool_))


# ------------------------------------------------------------ built-in kinds
def _bridges_device(src, dst, mask, n, st, out_cap):
    out = compact_edges(EdgeList(src, dst, mask, n), out_cap,
                        keep=st["bridge"])
    return out.src, out.dst, out.mask


def _cuts_device(src, dst, mask, n, st, out_cap):
    return articulation_from_state(src, dst, mask, n, st)


def _two_ecc_device(src, dst, mask, n, st, out_cap):
    return two_ecc_from_state(src, dst, mask, n, st["bridge"])


def _bridge_tree_device(src, dst, mask, n, st, out_cap):
    ecc = two_ecc_from_state(src, dst, mask, n, st["bridge"])
    out = bridge_tree_from_state(src, dst, mask, n, st["bridge"], ecc,
                                 out_cap)
    return out.src, out.dst, out.mask


def _bcc_device(src, dst, mask, n, st, out_cap):
    return bcc_from_state(src, dst, mask, n, st)


register(Analysis(
    kind="bridges",
    result="set[(u, v)] bridge pairs",
    certificate="2ec",
    incremental=True,
    decremental=True,
    device_fn=_bridges_device,
    host_fn=bridges_dfs,
    to_result=_pair_set,
    out_struct=_edge_buffer_struct,
))

register(Analysis(
    kind="cuts",
    result="set[int] articulation points",
    certificate="sfs",
    incremental=True,
    decremental=True,
    device_fn=_cuts_device,
    host_fn=articulation_points_dfs,
    to_result=lambda out, n: set(
        int(v) for v in np.nonzero(np.asarray(out)[:n])[0]),
    out_struct=lambda n, cap: jax.ShapeDtypeStruct((n,), np.bool_),
    device_input="full",
))

register(Analysis(
    kind="2ecc",
    result="int array[n_nodes] canonical 2ECC labels",
    certificate="2ec",
    incremental=True,
    decremental=True,
    device_fn=_two_ecc_device,
    host_fn=two_ecc_labels_dfs,
    # padding vertices are isolated singletons, so trimming is exact
    to_result=lambda out, n: np.asarray(out)[:n].copy(),
    out_struct=lambda n, cap: jax.ShapeDtypeStruct((n,), INT),
))

register(Analysis(
    kind="bridge_tree",
    result="set[(a, b)] 2ECC supernode pairs",
    certificate="2ec",
    incremental=True,
    decremental=True,
    device_fn=_bridge_tree_device,
    host_fn=bridge_tree_dfs,
    to_result=_pair_set,
    out_struct=_edge_buffer_struct,
))

register(Analysis(
    kind="bcc",
    result="set[frozenset[int]] biconnected blocks as vertex sets",
    certificate="sfs",
    incremental=True,
    decremental=True,
    device_fn=_bcc_device,
    host_fn=host_bcc_labels,
    to_result=lambda out, n: blocks_to_sets(out),
    out_struct=lambda n, cap: (
        jax.ShapeDtypeStruct((cap,), INT), jax.ShapeDtypeStruct((cap,), INT),
        jax.ShapeDtypeStruct((cap,), INT),
        jax.ShapeDtypeStruct((cap,), np.bool_)),
    device_input="full",
))

#: import-time snapshot of the BUILT-IN kind names (query-facing; aliases
#: like "bridge-tree" accepted). Code that must see kinds registered at
#: runtime — new descriptors added via ``register()`` — should call
#: ``analysis_kinds()`` instead, which reads the live registry (that is
#: what ``serve_bridges`` and ``benchmarks/fig8`` do).
ANALYSIS_KINDS = analysis_kinds()
