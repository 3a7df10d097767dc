"""Sharding rules: specs are valid (divisibility), rank-correct, and the
1-device debug mesh runs a sharded train step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, TrainConfig
from repro.configs.registry import ARCHS, shape_applicable
from repro.models import model_zoo as zoo
from repro.sharding import specs as sh


class FakeMesh:
    """Mesh stand-in exposing only .shape (rules need nothing else)."""
    def __init__(self, **axes):
        self.shape = dict(axes)


PROD = FakeMesh(data=16, model=16)
PROD_MP = FakeMesh(pod=2, data=16, model=16)


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("mesh", [PROD, PROD_MP], ids=["single", "multi"])
def test_param_specs_divisible_and_rank_correct(arch, mesh):
    cfg = ARCHS[arch]
    tree = zoo.init_params_spec(cfg)
    spec_tree = sh.param_specs(tree, mesh, fsdp=True)
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    specs = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(specs)
    for (path, leaf), spec in zip(leaves, specs):
        assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert dim % n == 0, (path, spec, leaf.shape)


def test_data_spec_divisibility():
    assert sh.data_spec(PROD, 256, 2) == P(("data",), None)
    assert sh.data_spec(PROD_MP, 256, 2) == P(("pod", "data"), None)
    # batch=1 cannot shard
    assert sh.data_spec(PROD, 1, 2) == P(None, None)


@pytest.mark.parametrize("arch", ["gemma3-1b", "mamba2-370m", "zamba2-2.7b"])
@pytest.mark.parametrize("shape", ["decode_32k", "long_500k"])
def test_cache_specs_valid(arch, shape):
    cfg, shp = ARCHS[arch], SHAPES[shape]
    ok, _ = shape_applicable(cfg, shp)
    if not ok:
        pytest.skip("assignment skip rule")
    mesh = PROD
    tree = sh.shape_tree(cfg, shp)
    spec_tree = sh.cache_specs(cfg, mesh, shp)
    for (path, leaf), spec in zip(
            jax.tree_util.tree_flatten_with_path(tree)[0],
            jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert dim % n == 0, (path, spec, leaf.shape)


def test_one_device_mesh_train_step(key):
    """The sharded code path runs on the real 1-device mesh."""
    from repro.launch.mesh import make_debug_mesh
    from repro.optim import adamw
    from repro.training import trainer
    cfg = ARCHS["qwen2-1.5b"].reduced()
    mesh = make_debug_mesh(1, 1)
    params = zoo.init_params(key, cfg)
    tcfg = TrainConfig(grad_accum=2, remat=True, bf16_state=False)
    opt = adamw.init_state(params, tcfg)
    batch = {
        "tokens": jax.random.randint(key, (4, 16), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (4, 16), 0, cfg.vocab_size),
    }
    with jax.set_mesh(mesh):
        p_sh = sh.param_shardings(params, mesh, fsdp=True)
        step = jax.jit(trainer.make_train_step(cfg, tcfg),
                       in_shardings=(p_sh, None, None))
        params2, opt2, metrics = step(params, opt, batch)
    assert jnp.isfinite(metrics["loss"])


def test_activation_constraint_noop_outside_mesh():
    from repro.sharding import act
    x = jnp.ones((4, 8, 16))
    y = act.shard_hidden(x)            # no ambient mesh -> identity
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
