"""Integration: the launcher's sharded path end-to-end on the LOCAL mesh.

The mesh fixture parametrizes over every (data, model) shape the host's
device count can fill — a single-device host still runs the 1x1 lane
(every sharding rule, activation hint and spec resolves through the same
code path as the production mesh; sizes of 1 make each spec a no-op
placement, but structure mismatches, bad specs, and hint rank errors all
still fail loudly), while CI's multi-device lane
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) additionally
executes REAL collective placement at 2- and 4-way data parallelism.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.launch import sharding as SD
from repro.launch.mesh import host_device_count, make_mesh
from repro.models import pshard as PS
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import init_train_state, make_train_step

# (data, model) shapes; the model axis stays 1 so the smoke configs' head
# and hidden dims never pick up a divisibility constraint, while the data
# axis carries real multi-device placement (batch of 4 -> up to 4-way).
MESH_SHAPES = [(1, 1), (2, 1), (4, 1)]


@pytest.fixture(scope="module", params=MESH_SHAPES,
                ids=[f"{d}x{m}" for d, m in MESH_SHAPES])
def mesh(request):
    shape = request.param
    need = shape[0] * shape[1]
    if host_device_count() < need:
        pytest.skip(f"mesh {shape} needs {need} devices, "
                    f"have {host_device_count()}")
    return make_mesh(shape, ("data", "model"))


def _active(mesh):
    """``jax.set_mesh`` where it exists; the Mesh context manager (same
    activation semantics) on older releases."""
    return jax.set_mesh(mesh) if hasattr(jax, "set_mesh") else mesh


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen3-moe-235b-a22b",
                                  "jamba-1.5-large-398b"])
def test_sharded_train_step_runs(arch, mesh):
    cfg = get_smoke_config(arch)
    opt = AdamWConfig(lr_peak=1e-3, warmup_steps=1, total_steps=4)
    key = jax.random.PRNGKey(0)

    with _active(mesh), PS.use_policy(
            {"dp": ("data",), "tp": "model", "moe_groups": 1}):
        state = init_train_state(cfg, key, opt)
        state_shapes = jax.eval_shape(lambda: state)
        state_sh = SD.to_shardings(SD.state_pspecs(state_shapes, mesh), mesh)
        state = jax.device_put(state, state_sh)

        toks = jax.random.randint(key, (4, 16), 0, cfg.vocab)
        batch = {"tokens": toks,
                 "labels": jnp.concatenate(
                     [toks[:, 1:], -jnp.ones((4, 1), jnp.int32)], axis=1)}
        batch_shapes = jax.eval_shape(lambda: batch)
        batch_sh = SD.to_shardings(SD.batch_pspecs(batch_shapes, mesh), mesh)
        batch = jax.device_put(batch, batch_sh)

        step = jax.jit(
            make_train_step(cfg, opt, n_micro=2),
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
        )
        new_state, metrics = step(state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        assert int(new_state.opt.step) == 1
        # second step with donated-style reuse
        new_state, metrics2 = step(new_state, batch)
        assert bool(jnp.isfinite(metrics2["loss"]))


def test_remat_policies_agree():
    """'nothing' and 'dots' remat policies compute identical losses."""
    import dataclasses
    from repro.models.registry import get_model
    base = get_smoke_config("olmo-1b")
    key = jax.random.PRNGKey(1)
    toks = jax.random.randint(key, (2, 16), 0, base.vocab)
    outs = {}
    for pol in ("nothing", "dots"):
        cfg = dataclasses.replace(base, remat=True, remat_policy=pol,
                                  n_layers=4)
        m = get_model(cfg)
        params = m.init_params(cfg, key)
        loss = jnp.mean(m.forward(cfg, params, toks, dtype=jnp.float32))
        grad = jax.grad(lambda p: jnp.mean(
            m.forward(cfg, p, toks, dtype=jnp.float32) ** 2))(params)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                             for x in jax.tree.leaves(grad)))
        outs[pol] = (float(loss), float(gnorm))
    assert np.allclose(outs["nothing"][0], outs["dots"][0], rtol=1e-5)
    assert np.allclose(outs["nothing"][1], outs["dots"][1], rtol=1e-3)
