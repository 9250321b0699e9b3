"""Sharding-rule validity on the production meshes, device-free via
AbstractMesh: every PartitionSpec axis must divide the dim it shards, for
all 10 archs x both meshes x params/batches/caches."""
import jax
import jax.numpy as jnp
import math
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, input_specs
from repro.models import sharding as S
from repro.models.model import init_cache, init_params


def _abstract_mesh(sizes, names):
    return AbstractMesh(tuple(sizes), tuple(names))


def _meshes():
    return [_abstract_mesh((16, 16), ("data", "model")),
            _abstract_mesh((2, 16, 16), ("pod", "data", "model"))]


def _axis_size(mesh, axes):
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def _check_spec_divides(mesh, spec: P, shape):
    assert len(spec) <= len(shape), (spec, shape)
    for dim, axes in zip(shape, spec):
        if axes is not None:
            assert dim % _axis_size(mesh, axes) == 0, (spec, shape)


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh", _meshes(), ids=["16x16", "2x16x16"])
def test_param_specs_divide(arch, mesh):
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    specs = S.param_specs(cfg, mesh)
    leaves_sh = jax.tree.leaves(shapes)
    leaves_sp = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves_sh) == len(leaves_sp)
    for sh, sp in zip(leaves_sh, leaves_sp):
        _check_spec_divides(mesh, sp, sh.shape)


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh", _meshes(), ids=["16x16", "2x16x16"])
@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_batch_and_cache_specs_divide(arch, mesh, shape_name):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    specs = input_specs(cfg, shape)
    bspecs = S.batch_specs(specs["batch"], mesh)
    for sh, sp in zip(jax.tree.leaves(specs["batch"]),
                      jax.tree.leaves(bspecs, is_leaf=lambda x: isinstance(x, P))):
        _check_spec_divides(mesh, sp, sh.shape)
    if shape.kind == "decode":
        cshapes = jax.eval_shape(
            lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
        cspecs = S.cache_specs(cfg, mesh, shape.global_batch, shape.seq_len)
        for sh, sp in zip(jax.tree.leaves(cshapes),
                          jax.tree.leaves(cspecs, is_leaf=lambda x: isinstance(x, P))):
            _check_spec_divides(mesh, sp, sh.shape)


@pytest.mark.parametrize("arch", ["mixtral-8x22b", "arctic-480b"])
def test_expert_sharding_strategy(arch):
    """Arctic (128e) must be expert-parallel on the model axis; Mixtral (8e)
    must fall back to per-expert FFN tensor parallelism."""
    cfg = get_config(arch)
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    specs = S.param_specs(cfg, mesh)
    w1_spec = specs["layers"]["moe"]["w1"]
    if cfg.n_experts % 16 == 0:
        assert w1_spec[1] == "model"      # (L, E->model, d, f)
    else:
        assert w1_spec[1] is None and w1_spec[3] == "model"


def test_vocab_padding_is_model_shardable():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        assert cfg.padded_vocab % (16 * 128) == 0
        assert cfg.padded_vocab >= cfg.vocab_size


def test_batch_axes_fallback_for_batch_1():
    mesh = _abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    assert S.batch_axes(mesh, 1) is None            # long_500k: replicate
    assert S.batch_axes(mesh, 128) == ("pod", "data")
    assert S.batch_axes(mesh, 32) == ("pod", "data")
    mesh1 = _abstract_mesh((16, 16), ("data", "model"))
    assert S.batch_axes(mesh1, 256) == ("data",)
