"""Sharding-rule tests: every param/cache spec must tile its dim evenly on
the production mesh for all 10 archs (no compile needed — eval_shape)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, INPUT_SHAPES, get_config, for_shape
from repro.models.model import init_cache, init_params


@pytest.fixture(scope="module")
def mesh():
    # AbstractMesh: NamedShardings without 256 devices
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


@pytest.fixture(scope="module")
def pod_mesh():
    return jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(tree, specs, mesh):
    leaves = jax.tree.leaves(tree)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(
        x, jax.sharding.PartitionSpec))
    assert len(leaves) == len(spec_leaves)
    for leaf, spec in zip(leaves, spec_leaves):
        for dim, entry in zip(leaf.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            extent = 1
            for a in axes:
                extent *= mesh.shape[a]
            assert dim % extent == 0, (leaf.shape, spec)


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_divide(arch, mesh):
    from repro.launch.sharding import param_specs

    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = param_specs(shapes, mesh, fsdp=True)
    _check_divisible(shapes, specs, mesh)


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_divide_multipod(arch, pod_mesh):
    from repro.launch.sharding import param_specs

    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = param_specs(shapes, pod_mesh, fsdp=True)
    _check_divisible(shapes, specs, pod_mesh)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("shape_name", ["decode_32k", "long_500k"])
def test_cache_shardings_divide(arch, shape_name, mesh):
    from repro.launch.sharding import cache_shardings

    shape = INPUT_SHAPES[shape_name]
    cfg = for_shape(get_config(arch), shape)
    shapes = jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
    shardings = cache_shardings(shapes, mesh, shape.global_batch)
    spec_tree = jax.tree.map(lambda s: s.spec, shardings,
                             is_leaf=lambda x: isinstance(
                                 x, jax.sharding.NamedSharding))
    _check_divisible(shapes, spec_tree, mesh)


def test_model_axis_used_for_big_params(mesh):
    """Tensor parallelism actually engages: every >=1M-element param is
    sharded over the model axis somewhere."""
    from repro.launch.sharding import param_specs

    cfg = get_config("qwen3-32b")
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = param_specs(shapes, mesh, fsdp=True)
    flat_s, _ = jax.tree.flatten(shapes)
    flat_p = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(
        x, jax.sharding.PartitionSpec))
    for leaf, spec in zip(flat_s, flat_p):
        if int(np.prod(leaf.shape)) >= 1_000_000:
            assert "model" in jax.tree.leaves(tuple(spec)), (leaf.shape, spec)
