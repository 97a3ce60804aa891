"""Shared fixtures.

Multi-device sharding tests need several host CPU devices, which XLA only
provides when the flag is set BEFORE jax initializes:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest -x -q

The CI matrix runs the tier-1 suite both ways (1 and 8 host devices); with
fewer than 8 devices the expert-parallel tests skip rather than fail.
"""
import jax
import pytest

from repro.launch.mesh import auto_mesh


@pytest.fixture
def ep_mesh():
    """An 8-way expert-parallel ("data", "model") = (8, 1) host-CPU mesh."""
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return auto_mesh((8, 1), ("data", "model"))


@pytest.fixture
def tp_mesh():
    """A tp=2 mesh: (2, 2) ep x tp with >=4 devices, (1, 2) with >=2.

    Adaptive so the fused reduce-scatter epilogue path runs in every CI
    device leg that has a second device; with 4+ the same fixture also
    exercises the 2-D ep x tp composition."""
    n = len(jax.devices())
    if n >= 4:
        return auto_mesh((2, 2), ("data", "model"))
    if n >= 2:
        return auto_mesh((1, 2), ("data", "model"))
    pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=2 (or more)")


@pytest.fixture
def eptp_mesh():
    """The full 2-D (4, 2) ep x tp host-CPU mesh (8 devices)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return auto_mesh((4, 2), ("data", "model"))
