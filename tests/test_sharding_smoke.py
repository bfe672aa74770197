"""In-process jit+sharding smoke (tier-1).

The slow system tests (tests/test_system.py, nightly) drive sharded
train/serve/resume end-to-end in subprocesses — minutes of wall clock. This
smoke exercises the SAME code path in-process and in seconds: a real
``jax.jit`` with in/out shardings and donation on the 4x2 ("data", "model")
debug mesh (8 fake CPU devices, forced by tests/conftest.py before jax
initializes), through ``params_shardings`` / ``opt_state_shardings`` /
``make_train_step`` on a smoke-sized config. A regression in the sharding
rules, the step builder, or mesh plumbing fails here on every push instead
of at the next nightly.
"""
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.data.pipeline import synthetic_batch
from repro.launch.mesh import make_debug_mesh
from repro.models import lm
from repro.optim import adamw
from repro.runtime import hints
from repro.runtime import sharding as shd
from repro.runtime import steps as steps_mod


def test_jit_sharding_smoke():
    if jax.device_count() < 8:
        pytest.skip("needs 8 (fake CPU) devices; conftest.py sets XLA_FLAGS "
                    "before jax init — something initialized jax earlier")
    cfg = configs.get_smoke_config("qwen2.5-3b")
    shape = configs.ShapeConfig("smoke", 16, 8, "train")
    par = configs.ParallelConfig(remat="full")
    mesh = make_debug_mesh(8)
    hints.set_mesh_axes({k: v for k, v in mesh.shape.items()})
    opt_cfg = adamw.AdamWConfig(total_steps=2)
    with mesh:
        params = lm.init_model(cfg, jax.random.PRNGKey(0))
        p_sh = shd.params_shardings(cfg, par, mesh, params)
        o_sh = shd.opt_state_shardings(cfg, par, mesh, params)
        params = jax.device_put(params, p_sh)
        opt = jax.device_put(adamw.init_state(params), o_sh)
        step = jax.jit(steps_mod.make_train_step(cfg, par, opt_cfg),
                       out_shardings=(p_sh, o_sh, None),
                       donate_argnums=(0, 1))
        losses = []
        for i in range(2):
            batch = {k: jnp.asarray(v)
                     for k, v in synthetic_batch(cfg, shape, i).items()}
            params, opt, metrics = step(params, opt, batch)
            losses.append(float(metrics["loss"]))
    assert all(l == l for l in losses), losses        # no NaN
    assert losses[-1] < losses[0] + 0.5, losses       # not diverging
    # the state is actually laid out across the mesh, not replicated on one
    # device: at least one param leaf spans multiple devices
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree_util.tree_leaves(params)}
    assert max(spans) > 1, spans


def test_train_entry_sharded_matches_one_device():
    """`launch.train.train()` (the path `chip_smoke.py --four-chips` takes):
    state built under jit onto a 2x2 mesh, batches placed, no checkpoint
    store; one step agrees with the same step on one device."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 (fake CPU) devices")
    from repro.launch.mesh import make_mesh
    from repro.launch.train import train

    cfg = configs.get_smoke_config("granite-moe-1b-a400m")
    shape = configs.ShapeConfig("smoke", 32, 8, "train")
    par = configs.ParallelConfig(remat="full")
    got = []
    for devices, grid in ((jax.devices()[:4], (2, 2)),
                          (jax.devices()[:1], (1, 1))):
        state = train(cfg, shape, make_mesh(devices, grid), 1, par=par)
        (metrics,) = state["history"]
        got.append((float(metrics["loss"]), float(metrics["grad_norm"])))
        spans = {len(leaf.sharding.device_set)
                 for leaf in jax.tree_util.tree_leaves(state["params"])}
        assert max(spans) == len(devices), spans
    (l4, g4), (l1, g1) = got
    assert abs(l4 - l1) <= 2e-3 * abs(l1), got
    assert abs(g4 - g1) <= 5e-2 * abs(g1), got
