"""Analytic MODEL_FLOPS per (arch x shape) — the 'useful work' denominators
for the roofline's MODEL_FLOPS / HLO_FLOPs ratio.

Formulas (matmul terms only, documented per family):

LM (PaLM-appendix convention):
  train:   6 * N_active * T            (fwd 2N + bwd 4N per token)
         + 12 * L * H * dh * S * T / 2 (causal attention QK^T + PV, fwd+bwd)
  prefill: 2 * N_active * T + 2 * 2 * L * H * dh * S * T / 2
  decode:  2 * N_active * B + 2 * 2 * L * H * dh * S_cache * B

GNN (per layer, full graph; E directed messages):
  graphsage: 2*E*d_in (agg is bandwidth) + 2*N*(d_in*d_out*2)
  pna:       ~4 aggs * 2*E*d + 2*N*(13*d_in)*d_out
  egnn:      2*E*(2d+1)*d + 2*E*d*d + 2*E*d + 2*N*2d*d
  gatedgcn:  5 matmuls: 2*N*d*d*3 + 2*E*d*d*2 (A,B on nodes; C,V on edges via
             gather) — counted as 2*(3N+2E)*d^2
  train = 3 * fwd (bwd ~ 2x fwd).

recsys (SASRec): blocks: 2 * B*S*d*d * (4 attn proj + 2 ffn) + attn
  2*B*S^2*d; scoring: train 2*B*S*d*2 (pos+neg); serve 2*B*V*d;
  bulk 2*B*V*d; retrieval 2*B*C*d + bag gather.

bridges (the paper's workload; int-vector ops counted as FLOP-equivalents):
  phase0 certificate: 2 passes * rounds(log2 V) * (E/M) * ~8 ops
  merge phases: log2(M) * 2 * log2(V) * 4(V-1) * ~8
  final PRAM bridges: ~40 * V * log2(V)
  collective bytes (exact by construction): log2(M) phases * 2(V-1) * 9 B.
  Memory traffic per round-scanned edge slot: the "fused" byte model
  (9 B/edge/round) or the three-pass lax sequence (25 B/edge/round) —
  ``fused=`` selects the term (byte model: repro.kernels.boruvka_round.ops,
  pinned by fig9; it no longer matches the kernels, DESIGN.md §Kernels).
"""
from __future__ import annotations

import math

from repro.kernels.boruvka_round.ops import boruvka_round_bytes


def lm_flops(cfg, shape: dict) -> float:
    l, h, dh = cfg.n_layers, cfg.n_heads, cfg.d_head
    n_act = cfg.n_active_params()
    kind = shape["kind"]
    b, s = shape["global_batch"], shape["seq_len"]
    t = b * s
    attn = 12 * l * h * dh * s * t / 2
    if kind == "train":
        return 6 * n_act * t + 3 * attn
    if kind == "prefill":
        return 2 * n_act * t + attn / 3 * 1  # fwd only: 4*L*H*dh*S*T/2
    if kind == "decode":
        return 2 * n_act * b + 4 * l * h * dh * s * b
    raise ValueError(kind)


def gnn_flops(arch: str, n_layers: int, d_hidden: int, shape: dict) -> float:
    kind = shape["kind"]
    if kind == "full":
        n, e = shape["n_nodes"], shape["n_edges"]
        scale = 1
    elif kind == "sampled":
        b = shape["batch_nodes"]
        f1, f2 = shape["fanout"]
        n = b + b * f1 + b * f1 * f2
        e = b * f1 + b * f1 * f2
        scale = 1
    else:  # batched
        n, e = shape["n_nodes"], shape["n_edges"]
        scale = shape["batch"]
    d = d_hidden
    e2 = 2 * e  # messages both directions
    per_layer = {
        "graphsage": 2 * e2 * d + 4 * n * d * d,
        "pna": 8 * e2 * d + 2 * n * (13 * d) * d,
        "egnn": 2 * e2 * (2 * d + 1) * d + 2 * e2 * d * d + 4 * n * d * d,
        "gatedgcn": 2 * (3 * n + 2 * e2) * d * d,
    }[arch]
    fwd = n_layers * per_layer * scale
    return 3 * fwd  # train step


def recsys_flops(cfg, shape: dict) -> float:
    kind = shape["kind"]
    b = shape["batch"]
    s, d, v = cfg.seq_len, cfg.d, cfg.n_items
    blocks = cfg.n_blocks * (2 * b * s * d * d * 6 + 2 * b * s * s * d)
    if kind == "train":
        return 3 * (blocks + 2 * b * s * d * 2)
    if kind == "serve":
        return blocks + 2 * b * v * d
    if kind == "bulk":
        return blocks + 2 * b * v * d
    if kind == "retrieval":
        c = shape["n_candidates"]
        return blocks + 2 * b * c * d
    raise ValueError(kind)


def bridges_model(shape: dict, m: int, merge: str = "recertify",
                  rounds_phase0: float | None = None,
                  rounds_merge: float | None = None,
                  fused: bool = True) -> dict:
    """Analytic terms for the paper's algorithm (see module docstring).

    ``rounds_*`` default to the worst case ceil(log2 V); pass MEASURED
    convergence counts (artifacts/perf/bridges_rounds*.json — the while
    loops pay only actual rounds) for the calibrated model.
    ``merge='incremental'`` models the warm-start merge: per phase the two
    delta passes scan only the received 2(n-1) buffer (rounds_merge is then
    the measured f1+f2 DELTA rounds) plus one 4(n-1) concat+compact.
    ``fused`` selects the per-round edge-scan traffic: the "fused" byte
    model (9 B/edge/round) vs the three-pass lax sequence (25 B/edge/round).
    """
    v, e = shape["n_nodes"], shape["n_edges"]
    worst = math.ceil(math.log2(v))
    r0 = rounds_phase0 if rounds_phase0 is not None else worst
    phases = math.ceil(math.log2(m))
    ops_phase0 = 2 * r0 * (e / m) * 8
    cert_bytes = 2 * (v - 1) * 9  # src,dst int32 + mask byte
    rb = boruvka_round_bytes(1, fused)  # bytes per edge slot per round scan
    if merge == "incremental":
        rm = rounds_merge if rounds_merge is not None else 2 * worst
        # rm = f1+f2 delta rounds over the 2(n-1) recv buffer (each a fused
        # or three-pass round scan), + concat/compact of the 4(n-1) union
        # once per phase (a copy: 9 B/slot regardless of kernel path)
        mem_merge = phases * (rm * 2 * v * rb + 4 * v * 9)
        ops_merge = phases * (rm * 2 * v + 4 * v) * 8
    else:
        rm = rounds_merge if rounds_merge is not None else 2 * worst
        # rm = f1+f2 rounds (worst case 2 passes x log2 V), each scanning
        # the full 4(n-1) union
        mem_merge = phases * rm * 4 * v * rb
        ops_merge = phases * rm * 4 * v * 8
    ops_final = 40 * v * math.ceil(math.log2(max(v, 2)))
    return {
        "model_ops": ops_phase0 + ops_merge + ops_final,
        "collective_bytes_per_device": phases * cert_bytes,
        "memory_bytes_per_device": 2 * r0 * (e / m) * rb + mem_merge,
    }


def model_flops_for(spec, shape_name: str, n_chips: int) -> float | None:
    shape = spec.shapes[shape_name]
    if spec.family == "lm":
        return lm_flops(spec.config, shape)
    if spec.family == "gnn":
        return gnn_flops(spec.config.arch, spec.config.n_layers,
                         spec.config.d_hidden, shape)
    if spec.family == "recsys":
        return recsys_flops(spec.config, shape)
    if spec.family == "graph":
        return bridges_model(shape, n_chips)["model_ops"]
    return None
