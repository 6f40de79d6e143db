"""Production meshes.

Functions, not module constants: importing this module must never touch jax
device state (device count locks on first backend init — dryrun.py and
tests/conftest.py set XLA_FLAGS before importing anything).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with GSPMD ``Auto`` axes: the step bodies place
    arrays through ``with_sharding_constraint`` under ``with mesh`` and
    let the partitioner propagate the rest, which ``Explicit`` axes (the
    default of ``jax.make_mesh``) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod = 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Reduced mesh for unit tests.

    Fails loudly when the backend holds fewer devices than the requested
    shape needs — ``jax.make_mesh`` would otherwise raise a shape-mismatch
    deep in device assignment that reads like a bug, when the actual fix is
    provisioning fake host devices before jax initializes. Tests get them
    from ``tests/conftest.py``; standalone scripts set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` at module top
    the way ``launch/dryrun.py`` does.
    """
    need = max(pod, 1) * data * model
    have = jax.device_count()
    if have < need:
        raise RuntimeError(
            f"make_test_mesh(data={data}, model={model}, pod={pod}) needs "
            f"{need} devices but the {jax.default_backend()} backend has "
            f"{have}. Set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} BEFORE jax initializes (tests inherit it from "
            "tests/conftest.py; scripts set it at module top like "
            "launch/dryrun.py).")
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
