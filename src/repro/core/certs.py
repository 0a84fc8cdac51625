"""Certificate registry: the certificate stage of the pipeline as data.

PR 3's Analysis registry made the pipeline's *final* stage pluggable; this
module does the same for the *certificate* stage. Every consumer — the
``BridgeEngine`` live state (materialize / insert fold-in / delete-rebuild),
the one-shot ``engine/batched.py::make_analysis_fn`` pipelines, and the
distributed ``core/merge.py::merged_certificate`` phases — resolves
certificates through this table instead of per-name if/elif ladders.
Registering a new ``Certificate`` here makes it servable on every substrate
with zero engine edits (proven by ``hybrid``, which no engine file names).

Each ``Certificate`` declares (DESIGN.md §Certificate registry):

* ``build`` — the pure traced builder: ``(EdgeList, capacity=...) ->``
  certificate pair in a fixed 2(n−1)-slot buffer. Used by the recertify
  merge phases (union-then-rebuild).
* ``build_ex`` — the same builder with its forests' round counts:
  ``(EdgeList, capacity=...) -> (certificate, int32[2], int32[2])``, the
  rounds up to and including the first forest and those of the second,
  then the same two counts of the rounds whose edge scan ran over the
  whole buffer. It is the type's ``*_ex`` builder viewed through
  ``counted`` (no second path). Used by the one-shot pipelines, which
  return the counts beside the answer (``certificate/rounds`` and
  ``certificate/full_rounds``, DESIGN.md §Observability).
* ``load_state`` — ``(EdgeList, capacity) -> state``: the live-serving
  state, a flat tuple whose FIRST THREE leaves are the pair's
  ``(src, dst, mask)`` buffers and whose remaining leaves are whatever
  auxiliary arrays the fold-in needs (warm-start labels for ``2ec``;
  nothing for the rescan certificates). The engine jits this both as the
  initial load and as the decremental rebuild program — the rebuild
  "program factory" is the same function on the surviving full buffer.
* ``fold_state`` — ``(state, recv EdgeList, capacity) -> state``: the
  incremental fold-in of an edge delta (or, distributed, of a received
  certificate) into the live state.
* ``lazy`` — the engine materializes the state only on the first query
  that resolves to this certificate (from the live full buffer), so
  workloads that never ask for it never pay its passes.
* ``warm_merge`` — the distributed merge phases may carry ``load_state``/
  ``fold_state`` across phases under ``merge='incremental'`` (the
  warm-start Borůvka deltas); certificates without it re-certify the
  union each phase, which is always valid (union-then-recertify).
* ``preserves`` — which connectivity structure the pair certifies:
  ``"lambda2"`` (min(λ, 2): bridges / 2ECC / bridge tree) and/or
  ``"kappa2"`` (vertex cuts and blocks). The engine validates
  per-kind certificate overrides against the kind's declared default:
  an override must preserve at least what the default does.

Layering: this module builds only on ``core.certificate`` and ``graph``;
``connectivity/registry.py`` validates ``Analysis.certificate`` against it
and ``engine/`` dispatches through it.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp

from repro.core.certificate import (
    hybrid_certificate,
    hybrid_certificate_ex,
    merge_certificates_incremental,
    sfs_certificate,
    sfs_certificate_ex,
    sparse_certificate,
    sparse_certificate_ex,
)
from repro.graph.datastructs import INT, EdgeList, concat_edges

#: the structure tokens ``preserves`` may declare
PRESERVABLE = frozenset({"lambda2", "kappa2"})


@dataclasses.dataclass(frozen=True)
class Certificate:
    """Descriptor for one sparse-certificate type (see module docstring).

    build      : (EdgeList, capacity=...) -> EdgeList
    build_ex   : (EdgeList, capacity=...)
                 -> (EdgeList, int32[2] rounds, int32[2] full rounds)
    load_state : (EdgeList, capacity) -> (src, dst, mask, *aux)
    fold_state : ((src, dst, mask, *aux), recv EdgeList, capacity) -> state
    """

    name: str
    summary: str
    preserves: frozenset
    build: Callable
    build_ex: Callable
    load_state: Callable
    fold_state: Callable
    lazy: bool = False
    warm_merge: bool = False

    def stream_load(self, chunks, capacity: int) -> tuple:
        """Fold an iterable of edge chunks into one live state.

        The streaming-ingest identity (DESIGN.md §Streaming ingest): the
        chunks partition the edge multiset, certificate union is valid
        over disjoint unions (§Fault tolerance's lemma), so
        ``load_state(chunk0)`` then ``fold_state`` per remaining chunk
        certifies exactly what one-shot ``load_state`` of the whole
        buffer does — for EVERY registered certificate, with zero name
        branches, because both hooks are the descriptor's own. Peak
        device residency is one chunk plus the state. An empty iterable
        yields the empty-graph state (all chunks must share ``n_nodes``;
        pass one all-masked chunk for an edgeless world).
        """
        state = None
        for chunk in chunks:
            if state is None:
                state = self.load_state(chunk, capacity)
            else:
                state = self.fold_state(state, chunk, capacity)
        if state is None:
            raise ValueError(
                f"stream_load({self.name!r}): no chunks; stream at least "
                "one (possibly all-masked) chunk to fix n_nodes")
        return state


_REGISTRY: dict[str, Certificate] = {}


def register_certificate(cert: Certificate) -> Certificate:
    """Add (or replace) a certificate type; returns it for chaining."""
    if not cert.name:
        raise ValueError("certificate name must be non-empty")
    unknown = frozenset(cert.preserves) - PRESERVABLE
    if unknown:
        raise ValueError(
            f"certificate {cert.name!r} declares unknown structure "
            f"tokens {sorted(unknown)}; choose from {sorted(PRESERVABLE)}")
    _REGISTRY[cert.name] = cert
    return cert


def certificate_names() -> tuple[str, ...]:
    """Every registered certificate name, in registration order."""
    return tuple(_REGISTRY)


def get_certificate(name: str) -> Certificate:
    """Look up a descriptor; ValueError names the registered choices."""
    cert = _REGISTRY.get(str(name))
    if cert is None:
        raise ValueError(
            f"unknown certificate {name!r}; choose from {certificate_names()}")
    return cert


def certificate_builder(name: str) -> Callable:
    """The plain builder view: (EdgeList, capacity=...) -> EdgeList."""
    return get_certificate(name).build


def primary_certificate() -> str:
    """The first eagerly-materialized certificate — the pair ``load``
    computes up front and ``num_live_edges`` reports."""
    for name, cert in _REGISTRY.items():
        if not cert.lazy:
            return name
    raise ValueError("no eager certificate registered")


# -------------------------------------------------------------- state glue
def counted(build_ex: Callable) -> Callable:
    """``build_ex`` view of an ``*_ex`` builder, whose first output is the
    certificate and whose last two are its passes' round counts and, of
    those, the full-width ones: each folds into ``int32[2]`` — every pass
    before the second forest, then the second forest (the hybrid's chain
    contraction joins its first)."""

    def fold(counts):
        *first, second = counts
        return jnp.stack([sum(first), second]).astype(INT)

    def build(edges: EdgeList, capacity: int | None = None):
        out = build_ex(edges, capacity=capacity)
        return out[0], fold(out[-2]), fold(out[-1])

    return build


def _pair_state(cert: EdgeList) -> tuple:
    return cert.src, cert.dst, cert.mask


def _state_pair(state: tuple, n_nodes: int) -> EdgeList:
    return EdgeList(state[0], state[1], state[2], n_nodes)


def _warm_load(edges: EdgeList, capacity: int) -> tuple:
    cert, lab1, lab2, *_ = sparse_certificate_ex(edges, capacity=capacity)
    return (*_pair_state(cert), lab1, lab2)


def _warm_fold(state: tuple, recv: EdgeList, capacity: int) -> tuple:
    cs, cd, cm, lab1, lab2 = state
    cert, lab1, lab2, _ = merge_certificates_incremental(
        EdgeList(cs, cd, cm, recv.n_nodes), lab1, lab2, recv)
    return (*_pair_state(cert), lab1, lab2)


def _rescan_load(build: Callable) -> Callable:
    def load(edges: EdgeList, capacity: int) -> tuple:
        return _pair_state(build(edges, capacity=capacity))

    return load


def _rescan_fold(build: Callable) -> Callable:
    """Fold-in by re-certifying the bounded cert ∪ delta union: O(n + Δ)
    per update, never O(E) — the generic path for certificates whose
    layered structure does not warm-start (BFS layers shift globally)."""

    def fold(state: tuple, recv: EdgeList, capacity: int) -> tuple:
        own = _state_pair(state, recv.n_nodes)
        return _pair_state(build(concat_edges(own, recv), capacity=capacity))

    return fold


# ---------------------------------------------------------- built-in types
register_certificate(Certificate(
    name="2ec",
    summary="Borůvka forest pair F1 ∪ F2 (Nagamochi–Ibaraki, k=2): "
            "preserves min(λ, 2); warm-start labels make deltas cheap",
    preserves=frozenset({"lambda2"}),
    build=sparse_certificate,
    build_ex=counted(sparse_certificate_ex),
    load_state=_warm_load,
    fold_state=_warm_fold,
    lazy=False,
    warm_merge=True,
))

register_certificate(Certificate(
    name="sfs",
    summary="scan-first-search BFS-layer pair (Cheriyan–Kao–Thurimella): "
            "preserves vertex cuts and blocks; O(diameter) rounds",
    preserves=frozenset({"kappa2"}),
    build=sfs_certificate,
    build_ex=counted(sfs_certificate_ex),
    load_state=_rescan_load(sfs_certificate),
    fold_state=_rescan_fold(sfs_certificate),
    lazy=True,
))

register_certificate(Certificate(
    name="hybrid",
    summary="Borůvka-contracted chains + scan-first pair on the contracted "
            "graph: same guarantees as sfs with BFS rounds bounded by the "
            "contracted diameter (sparse/path-like worlds)",
    preserves=frozenset({"kappa2"}),
    build=hybrid_certificate,
    build_ex=counted(hybrid_certificate_ex),
    load_state=_rescan_load(hybrid_certificate),
    fold_state=_rescan_fold(hybrid_certificate),
    lazy=True,
))

#: import-time snapshot of the built-in names; call ``certificate_names()``
#: for the live registry (runtime registrations included).
CERTIFICATE_NAMES = certificate_names()
