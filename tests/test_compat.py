"""The JAX compat shim works against whatever JAX this env has."""

import jax
import numpy as np
import pytest

from repro import compat


def test_tpu_compiler_params_builds():
    cp = compat.tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert cp.dimension_semantics == ("parallel", "arbitrary")


def test_tpu_compiler_params_rejects_unknown_kwargs():
    with pytest.raises(TypeError, match="some_future_knob"):
        compat.tpu_compiler_params(
            dimension_semantics=("parallel",),
            some_future_knob_that_does_not_exist=123)


def test_mesh_axis_types_shape_or_none():
    types = compat.mesh_axis_types(3)
    assert types == (jax.sharding.AxisType.Auto,) * 3


def test_make_mesh_single_device():
    mesh = compat.make_mesh((1,), ("data",))
    assert mesh.axis_names == ("data",)
    assert mesh.devices.shape == (1,)


def test_shard_map_identity_single_device():
    from jax.sharding import PartitionSpec as P

    mesh = compat.make_mesh((1,), ("x",))
    f = compat.shard_map(lambda a: a * 2.0, mesh=mesh, in_specs=(P(),),
                         out_specs=P(), check_vma=False)
    out = f(jax.numpy.ones((4,)))
    np.testing.assert_allclose(np.asarray(out), 2.0)
