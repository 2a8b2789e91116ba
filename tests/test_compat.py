"""Unit tests for the JAX seam (repro.compat)."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro import compat


def test_shard_map_resolved():
    # the installed jax spells the replication check `check_vma`, the one
    # kwarg the wrapper passes
    params = inspect.signature(jax.shard_map).parameters
    assert "check_vma" in params and "check_rep" not in params


def test_shard_map_runs_on_single_device_mesh():
    mesh = compat.make_mesh((1,), ("data",))
    f = compat.shard_map(
        lambda x: x * 2.0, mesh, in_specs=(P(),), out_specs=P()
    )
    out = jax.jit(f)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), [0.0, 2.0, 4.0, 6.0])


def test_check_kwarg_adaptation(monkeypatch):
    """The wrapper must translate `check=` onto `check_vma=`, off unless
    asked for."""
    seen = []

    def fake(f, *, mesh, in_specs, out_specs, check_vma=True):
        seen.append(check_vma)
        return f

    monkeypatch.setattr(jax, "shard_map", fake)
    compat.shard_map(lambda x: x, None, in_specs=(), out_specs=())
    compat.shard_map(lambda x: x, None, in_specs=(), out_specs=(), check=True)
    assert seen == [False, True]


def test_make_mesh_axes():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.shape == {"data": 1, "model": 1}
    # Auto axes: indexing an array sharded on the mesh stays a plain slice
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert compat.mesh_data_axes(mesh) == ("data",)
    assert compat.mesh_model_axis(mesh) == "model"
    no_model = compat.make_mesh((1,), ("data",))
    assert compat.mesh_model_axis(no_model) is None


def test_donation_gating():
    assert compat.supports_donation("tpu")
    assert compat.supports_donation("gpu")
    assert not compat.supports_donation("cpu")
    # jit with donation requested still works on the current backend
    f = compat.jit(lambda x: x + 1, donate_argnums=(0,))
    assert float(f(jnp.float32(1.0))) == 2.0


def test_ensure_host_device_count_after_init():
    # backend is initialized by the time tests run: must be a no-op that
    # reports the real count instead of mutating XLA_FLAGS
    n = len(jax.devices())
    assert compat.ensure_host_device_count(64) == n


def test_cached_program_builder_called_once():
    calls = []

    @compat.cached_program
    def build(key):
        calls.append(key)
        return lambda x: x * key

    f1 = build(3)
    f2 = build(3)
    assert f1 is f2 and calls == [3]
    build(4)
    assert calls == [3, 4]
