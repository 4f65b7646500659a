"""Partitioning rules + elastic helpers (single-device mesh semantics
checked here; the 512-device meshes are proven by launch/dryrun.py in its
own process — conftest must NOT set device-count flags)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as PS

from repro.configs import ARCHS
from repro.dist.partitioning import (
    activation_constrainer,
    input_shardings,
    param_pspecs,
    pspec_for_axes,
)
from repro.launch.mesh import make_mesh_for
from repro.models import build_model


def _mesh2d(data=1, model=1):
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def test_pspec_basic_rules():
    mesh = _mesh2d()
    assert pspec_for_axes(("embed", "heads", None), mesh) == PS(None, "model", None)
    assert pspec_for_axes(("vocab", "embed"), mesh) == PS("model", None)
    assert pspec_for_axes(("experts", "embed", "ffn"), mesh) == \
        PS("model", None, None)  # model axis claimed once


def test_pspec_fsdp_claims_data_axis():
    mesh = _mesh2d()
    assert pspec_for_axes(("embed", "ffn"), mesh, fsdp=True) == \
        PS("data", "model")


def test_pspec_divisibility_guard():
    mesh = _mesh2d(model=1)  # sizes 1 divide everything
    ps = pspec_for_axes(("heads",), mesh, shape=(36,))
    assert ps == PS("model")
    big = jax.make_mesh((1, 1), ("data", "model"))
    # emulate 16-way: use shape check directly via a fake mesh is not
    # possible on 1 device; assert the arithmetic path instead
    from repro.dist import partitioning as P_
    # 36 heads % 16 != 0 -> replicate
    class FakeMesh:
        axis_names = ("data", "model")
        class devices:
            shape = (16, 16)
    assert P_.pspec_for_axes(("heads",), FakeMesh, shape=(36,)) == PS(None)
    assert P_.pspec_for_axes(("heads",), FakeMesh, shape=(64,)) == PS("model")


def test_param_pspecs_whole_model():
    mesh = _mesh2d()
    model = build_model(ARCHS["internlm2-1.8b"])
    specs = param_pspecs(model.logical_axes(), mesh,
                         abstract_tree=model.abstract_params())
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, PS))
    assert all(isinstance(p, PS) for p in flat)
    # embed table is vocab-sharded
    assert specs["embed"] == PS("model", None)


def test_constrainer_runs_under_jit():
    mesh = _mesh2d()
    constrain = activation_constrainer(mesh)

    @jax.jit
    def f(x):
        return constrain(x, ("batch", None, "embed")) * 2

    out = f(jnp.ones((4, 8, 16)))
    assert out.shape == (4, 8, 16)


def test_make_mesh_for_elastic_shapes():
    m = make_mesh_for(1, model_parallel=1)
    assert m.devices.size == 1
    # model_parallel rounded down to a divisor of device count
    m2 = make_mesh_for(1, model_parallel=7)
    assert m2.devices.size == 1


def test_elastic_restore_roundtrip(tmp_path):
    from repro.configs import reduced
    from repro.train import CheckpointManager
    from repro.train.elastic import elastic_restore

    cfg = reduced(ARCHS["internlm2-1.8b"])
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    state = {"params": params}
    CheckpointManager(str(tmp_path)).save(3, state, extra={"step": 3})
    restored, mesh, extra = elastic_restore(
        model, str(tmp_path), model_parallel=1, template=state)
    assert extra["step"] == 3
    w0 = jax.tree.leaves(params)[0]
    w1 = jax.tree.leaves(restored["params"])[0]
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
