"""Per-arch smoke tests: REDUCED config, one forward/train step on CPU,
assert output shapes + no NaNs. Full configs are exercised only via the
dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get
from repro.models import gnn as gnn_mod
from repro.models import recsys as rec_mod
from repro.models import transformer as tfm
from repro.models.transformer import Parallelism
from repro.optim import AdamWConfig
from repro.optim.adamw import adamw_init
from repro.training import (
    make_gnn_train_step,
    make_lm_decode_step,
    make_lm_train_step,
    make_recsys_steps,
)


PAR = Parallelism.none()
LM_ARCHS = ["qwen3_0_6b", "stablelm_12b", "qwen3_14b", "dbrx_132b",
            "qwen3_moe_235b_a22b"]
GNN_ARCHS = ["graphsage_reddit", "pna", "egnn", "gatedgcn"]


def _finite(tree):
    return all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_smoke_train_step(arch):
    cfg = get(arch).smoke_config
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(make_lm_train_step(cfg, PAR, AdamWConfig(lr=1e-3)))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab)
    }
    params, opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < 2 * np.log(cfg.vocab)
    assert _finite(params)
    # loss decreases over a few steps
    l0 = float(metrics["loss"])
    for _ in range(4):
        params, opt, metrics = step(params, opt, batch)
    assert float(metrics["loss"]) < l0


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_smoke_decode(arch):
    cfg = get(arch).smoke_config
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    cache = tfm.init_cache(cfg, 2, 16)
    decode = jax.jit(make_lm_decode_step(cfg, PAR))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 1), 0, cfg.vocab)
    logits, cache = decode(params, cache, toks, jnp.int32(1))
    assert logits.shape == (2, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", GNN_ARCHS)
def test_gnn_smoke_full_graph(arch):
    from repro.graph import generators as gen

    cfg = get(arch).smoke_config
    key = jax.random.PRNGKey(0)
    src, dst = gen.random_graph(40, 120, seed=0)
    if cfg.arch == "egnn":
        g = {
            "h": jax.random.normal(key, (40, cfg.d_feat)),
            "x": jax.random.normal(key, (40, 3)),
            "src": jnp.asarray(src), "dst": jnp.asarray(dst),
            "mask": jnp.ones(len(src), bool),
            "target": jnp.ones((1,), jnp.float32),
        }
    else:
        g = {
            "feats": jax.random.normal(key, (40, cfg.d_feat)),
            "src": jnp.asarray(src), "dst": jnp.asarray(dst),
            "mask": jnp.ones(len(src), bool),
            "labels": jax.random.randint(key, (40,), 0, cfg.n_classes),
            "label_mask": jnp.ones(40, bool),
        }
    params = gnn_mod.init_gnn(cfg, key)
    opt = adamw_init(params)
    step = jax.jit(make_gnn_train_step(cfg, PAR, mode="full"))
    params, opt, metrics = step(params, opt, g)
    assert np.isfinite(float(metrics["loss"]))
    assert _finite(params)


def test_graphsage_smoke_sampled():
    cfg = get("graphsage_reddit").smoke_config
    key = jax.random.PRNGKey(0)
    b, (f1, f2), d = 8, cfg.sample_sizes, cfg.d_feat
    batch = {
        "x0": jax.random.normal(key, (b, d)),
        "x1": jax.random.normal(key, (b, f1, d)),
        "x2": jax.random.normal(key, (b, f1, f2, d)),
        "m1": jnp.ones((b, f1), bool),
        "m2": jnp.ones((b, f1, f2), bool),
        "labels": jax.random.randint(key, (b,), 0, cfg.n_classes),
    }
    params = gnn_mod.init_gnn(cfg, key)
    opt = adamw_init(params)
    step = jax.jit(make_gnn_train_step(cfg, PAR, mode="sampled"))
    params, opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_sasrec_smoke_all_modes():
    cfg = get("sasrec").smoke_config
    key = jax.random.PRNGKey(0)
    params = rec_mod.init_sasrec(cfg, key)
    opt = adamw_init(params)
    steps = make_recsys_steps(cfg, PAR)
    b, s = 4, cfg.seq_len
    batch = {
        "seq": jax.random.randint(key, (b, s), 0, cfg.n_items),
        "pos": jax.random.randint(key, (b, s), 1, cfg.n_items),
        "neg": jax.random.randint(key, (b, s), 1, cfg.n_items),
    }
    params, opt, metrics = jax.jit(steps["train"])(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    scores = steps["serve"](params, batch["seq"])
    assert scores.shape == (b, cfg.n_items)
    ts, ti = steps["bulk"](params, batch["seq"])
    assert ts.shape[0] == b and np.isfinite(np.asarray(ts)).all()
    rs = steps["retrieval"](
        params, batch["seq"][:1], jnp.ones((1, s), bool),
        jax.random.randint(key, (64,), 1, cfg.n_items),
    )
    assert rs.shape == (1, 64) and np.isfinite(np.asarray(rs)).all()


def test_sasrec_bulk_topk_matches_full_scores():
    """Shard-local top-k + merge must be EXACTLY the full-table top-k
    (the distributed-serving optimization cannot change results)."""
    cfg = get("sasrec").smoke_config
    key = jax.random.PRNGKey(1)
    params = rec_mod.init_sasrec(cfg, key)
    b, s, k = 4, cfg.seq_len, 10
    seq = jax.random.randint(key, (b, s), 0, cfg.n_items)
    full = rec_mod.serve_scores(params, seq, cfg, None)  # [B, V] oracle
    want = jax.lax.top_k(full.astype(jnp.float32), k)[0]
    for nsh in (1, 2, 4):
        got, _ = rec_mod.serve_bulk_topk(params, seq, cfg, None, k=k,
                                         n_chunks=8, n_shards=nsh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_bridges_smoke():
    from repro.core import find_bridges
    from repro.graph import generators as gen

    cfg = get("bridges_dense").smoke_config
    src, dst, planted = gen.planted_bridge_graph(cfg.n_nodes, cfg.n_edges, 3, seed=0)
    got = find_bridges(src, dst, cfg.n_nodes, final="device")
    assert planted <= got


def test_registry_complete():
    assert len(ARCH_IDS) == 11  # 10 assigned + paper workload
    for a in ARCH_IDS:
        spec = get(a)
        assert spec.shapes, a
        assert spec.smoke_config is not None, a


def test_head_padding_is_exact():
    """TP head padding (e.g. 40->48 heads) must be mathematically invisible:
    embedding the real heads of an UNPADDED model into the padded layout
    gives bit-comparable logits, and padded lanes receive zero gradient."""
    import dataclasses

    base = tfm.LMConfig(name="t", n_layers=2, d_model=64, n_heads=6,
                        n_kv_heads=2, d_ff=128, vocab=97, d_head=16,
                        qk_norm=True, param_dtype="float32", attn_chunk=8,
                        remat=False, tp_align=1)
    padded = dataclasses.replace(base, tp_align=4)  # 6 heads -> g 3->4 -> 8
    assert padded.h_padded == 8 and padded.g_padded == 4
    key = jax.random.PRNGKey(0)
    p_ref = tfm.init_params(base, key)
    p_pad = tfm.init_params(padded, key)
    # embed real head weights into the kv-grouped padded slots
    wq = np.zeros(p_pad["layers"]["wq"].shape, np.float32)
    wo = np.zeros(p_pad["layers"]["wo"].shape, np.float32)
    for kv in range(2):
        for g in range(3):
            wq[:, :, kv * 4 + g] = np.asarray(p_ref["layers"]["wq"])[:, :, kv * 3 + g]
            wo[:, kv * 4 + g] = np.asarray(p_ref["layers"]["wo"])[:, kv * 3 + g]
    p_pad = dict(p_pad)
    p_pad["layers"] = dict(p_ref["layers"], wq=jnp.asarray(wq), wo=jnp.asarray(wo))
    p_pad["embed"] = p_ref["embed"]
    p_pad["final_norm"] = p_ref["final_norm"]

    toks = {"tokens": jax.random.randint(key, (2, 17), 0, 97)}
    par = Parallelism.none()
    l_ref = tfm.lm_loss(p_ref, toks, base, par)
    l_pad = tfm.lm_loss(p_pad, toks, padded, par)
    np.testing.assert_allclose(float(l_ref), float(l_pad), rtol=2e-5)

    # dead lanes get exactly zero grad (they can never be revived)
    g = jax.grad(lambda p: tfm.lm_loss(p, toks, padded, par))(p_pad)
    gq = np.asarray(g["layers"]["wq"])
    go = np.asarray(g["layers"]["wo"])
    for kv in range(2):
        assert np.all(gq[:, :, kv * 4 + 3] == 0)
        assert np.all(go[:, kv * 4 + 3] == 0)
