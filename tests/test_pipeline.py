"""Pipeline parallelism: GPipe schedule must be EXACTLY the non-PP model.

Runs in a subprocess with 8 host devices (same pattern as
test_distributed.py) on a (pipe=4, data=2) mesh.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path


REPO = Path(__file__).resolve().parent.parent


def run_with_devices(code: str, n_devices: int = 8) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )


def test_pp_loss_matches_non_pp():
    r = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.models import transformer as tfm
        from repro.models.pipeline import (PipelineConfig, make_pp_loss_fn,
                                           stageify_params)
        from repro.models.transformer import Parallelism

        mesh = jax.make_mesh((4, 2), ("pipe", "data"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = tfm.LMConfig(name="t", n_layers=4, d_model=32, n_heads=4,
                           n_kv_heads=2, d_ff=64, vocab=61, d_head=8,
                           param_dtype="float32", attn_chunk=8, remat=False,
                           tp_align=1)
        key = jax.random.PRNGKey(0)
        params = tfm.init_params(cfg, key)

        n_micro, mb, s = 4, 2, 16
        tokens = jax.random.randint(key, (n_micro, mb, s + 1), 0, cfg.vocab)

        # reference: plain (non-PP) mean loss over the same microbatches
        par0 = Parallelism.none()
        ref = np.mean([
            float(tfm.lm_loss(params, {"tokens": tokens[i]}, cfg, par0))
            for i in range(n_micro)
        ])

        par = Parallelism(mesh=mesh, dp_axes=("data",), tp_axis="model")
        pp = PipelineConfig(n_stages=4, n_micro=n_micro)
        loss_fn = make_pp_loss_fn(cfg, par, pp)
        staged = stageify_params(params, 4)
        with jax.set_mesh(mesh):
            got = float(jax.jit(loss_fn)(staged, {"tokens": tokens}))
        assert abs(got - ref) < 2e-4, (got, ref)

        # gradients flow to every stage's params
        with jax.set_mesh(mesh):
            g = jax.jit(jax.grad(loss_fn))(staged, {"tokens": tokens})
        gq = np.asarray(g["layers"]["wq"])  # [stages, L/S, ...]
        for st in range(4):
            assert np.abs(gq[st]).max() > 0, f"stage {st} got zero grad"
        print("OK", got, ref)
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
