"""Partition-rule unit tests: every param/cache leaf of every arch gets a
spec whose sharded dims actually divide (AbstractMesh — no devices needed)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.configs.registry import ARCHS, get_config
from repro.launch import sharding as SD
from repro.models.registry import get_model
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import init_train_state

MESH_1POD = AbstractMesh((16, 16), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)


def _axes_size(mesh, entry):
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _check_tree(shapes, specs, mesh):
    flat_s = jax.tree.leaves(shapes)
    flat_p = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_s) == len(flat_p)
    for leaf, spec in zip(flat_s, flat_p):
        assert len(spec) <= len(leaf.shape)
        for dim, entry in enumerate(spec):
            n = _axes_size(mesh, entry)
            assert leaf.shape[dim] % n == 0, (leaf.shape, spec, dim)


@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD], ids=["1pod", "2pod"])
@pytest.mark.parametrize("arch", list(ARCHS))
def test_param_specs_divisible(arch, mesh):
    cfg = get_config(arch)
    model = get_model(cfg)
    shapes = jax.eval_shape(functools.partial(model.init_params, cfg),
                            jax.random.PRNGKey(0))
    specs = SD.param_pspecs(shapes, mesh)
    _check_tree(shapes, specs, mesh)


@pytest.mark.parametrize("arch", ["llama3-405b", "gemma3-1b", "mamba2-370m",
                                  "jamba-1.5-large-398b", "whisper-small"])
def test_cache_specs_divisible(arch):
    from repro.launch.shapes import SHAPES, cell_skip_reason
    cfg = get_config(arch)
    model = get_model(cfg)
    for shape in ("decode_32k", "long_500k"):
        if cell_skip_reason(arch, shape):
            continue
        cell = SHAPES[shape]
        shapes = jax.eval_shape(
            lambda: model.init_cache(cfg, cell.global_batch, cell.seq_len,
                                     dtype=jnp.bfloat16))
        for mesh in (MESH_1POD, MESH_2POD):
            specs = SD.cache_pspecs(shapes, mesh)
            _check_tree(shapes, specs, mesh)


def test_whisper_vocab_falls_back_to_replicated():
    """51865 is not 16-divisible: the vocab dim must NOT be sharded, while
    the d_model dim still FSDPs (padding handles the logits side)."""
    cfg = get_config("whisper-small")
    assert cfg.padded_vocab == 51_968           # padded to 128
    spec = SD._param_rule(MESH_1POD, "embed", (cfg.vocab, cfg.d_model))
    assert spec[0] is None and spec[1] is not None


def test_tensorstate_spec_structure_matches():
    cfg = get_config("olmo-1b")
    opt = AdamWConfig()
    shapes = jax.eval_shape(
        functools.partial(init_train_state, cfg, opt_cfg=opt),
        jax.random.PRNGKey(0))
    specs = SD.state_pspecs(shapes, MESH_1POD)
    # moments mirror params 1:1
    assert jax.tree.structure(specs.opt.mu, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(specs.params, is_leaf=lambda x: isinstance(x, P))
    _check_tree(shapes.params, specs.params, MESH_1POD)
    _check_tree(shapes.opt.mu, specs.opt.mu, MESH_1POD)


def test_batch_specs():
    from repro.launch.shapes import input_specs
    ins = input_specs("llama3-405b", "train_4k")
    specs = SD.batch_pspecs(ins, MESH_2POD)
    assert specs["tokens"][0] == ("pod", "data")
    ins1 = input_specs("mamba2-370m", "long_500k")
    specs1 = SD.batch_pspecs(ins1, MESH_1POD)
    assert specs1["token"][0] is None           # batch 1: replicated
