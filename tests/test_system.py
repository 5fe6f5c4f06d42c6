"""End-to-end behaviour tests for the whole system."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def test_quickstart_flow():
    """The README flow: config -> model -> train a few steps -> serve."""
    from repro.configs import get_smoke
    from repro.models import LM
    from repro.serving import Request, ServeEngine
    from repro.training import OptConfig, make_train_step
    from repro.training.optimizer import adamw_init

    cfg = get_smoke("qwen2_5_3b")
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    opt = adamw_init(params)
    step = jax.jit(make_train_step(model, OptConfig(lr=1e-3, warmup_steps=2, total_steps=20)))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)), jnp.int32),
    }
    first = None
    for _ in range(8):
        params, opt, m = step(params, opt, batch)
        first = first if first is not None else float(m["loss"])
    assert float(m["loss"]) < first

    eng = ServeEngine(model, params, batch_slots=2, max_len=64)
    eng.submit(Request(rid=0, prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=4))
    (done,) = eng.run()
    assert len(done.out_tokens) == 4


def test_train_driver_cli(tmp_path):
    from repro.launch.train import main

    res = main(
        [
            "--arch", "mamba2_1_3b", "--smoke", "--steps", "6", "--batch", "2",
            "--seq", "32", "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
            "--log-every", "100",
        ]
    )
    assert res["final_step"] == 6
    from repro.training.checkpoint import latest_step

    assert latest_step(tmp_path) == 6


def test_multidevice_lowering_smoke():
    """Miniature of the production dry-run: 8 host devices, (2,4) mesh,
    smoke arch, lower + compile the sharded train step in a subprocess
    (the 512-device flag must never leak into this test process)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_smoke
from repro.distributed.autoshard import best_rules
from repro.distributed.sharding import use_rules
from repro.launch.mesh import make_local_mesh
from repro.models import LM
from repro.models.layers import spec_shapes
from repro.training import OptConfig, make_train_step
mesh = make_local_mesh(2, 4)
cfg = get_smoke("gemma_7b").replace(vocab=512, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128)
name, rules, cost = best_rules(cfg, mesh, global_batch=8, seq=32, kind="train")
model = LM(cfg)
with use_rules(rules), mesh:
    pspecs = spec_shapes(model.param_specs())
    opt = {"m": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=s.sharding), pspecs),
           "v": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=s.sharding), pspecs),
           "master": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=s.sharding), pspecs),
           "step": jax.ShapeDtypeStruct((), jnp.int32)}
    batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32, sharding=rules.sharding_for(("batch","seq"))),
             "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32, sharding=rules.sharding_for(("batch","seq")))}
    step = make_train_step(model, OptConfig())
    compiled = jax.jit(step, donate_argnums=(0,1)).lower(pspecs, opt, batch).compile()
    assert compiled.cost_analysis().get("flops", 0) > 0
print("MULTIDEV_OK", name)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIDEV_OK" in out.stdout


def test_dryrun_artifacts_if_present():
    """When the full sweep has run, every runnable cell must be ok and
    every skip principled (validates deliverable e end-state)."""
    d = REPO / "experiments" / "dryrun"
    files = list(d.glob("*.json")) if d.exists() else []
    if len(files) < 10:
        pytest.skip("dry-run sweep not complete yet")
    bad = []
    for f in files:
        rec = json.loads(f.read_text())
        if rec.get("status") == "error":
            bad.append((f.name, rec.get("error")))
        elif rec.get("status") == "skip":
            assert rec.get("reason"), f.name
    assert not bad, bad
