"""Production mesh construction (brief-mandated shapes).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state, so tests/benches keep their 1-CPU view unless the caller
explicitly builds a mesh (the dry-run sets XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["auto_mesh", "make_production_mesh", "make_serving_mesh"]


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto.

    ``jax.make_mesh`` now defaults to Explicit axes, and
    ``jax.lax.with_sharding_constraint`` -- which the activation rules in
    ``parallel.sharding`` apply -- accepts only Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_serving_mesh(*, ep: int | None = None, tp: int = 1):
    """A (ep, tp) serving mesh over ("data", "model") axes.

    The data axis doubles as the expert-parallel axis (docs/parallelism.md):
    packed MoE expert banks split E/ep rows per device along it, and
    ``moe_forward`` shard_maps the grouped kernel over it.  ``ep`` defaults
    to ``n_devices // tp`` (use every local device).  For MoE serving pick an
    ep that divides ``cfg.n_experts`` -- an indivisible bank falls back to
    replication (``parallel.sharding.expert_shard_size`` has the exact rule).
    """
    n = len(jax.devices())
    if tp <= 0:
        raise ValueError(f"tp must be positive, got {tp}")
    if ep is None:
        ep = max(n // tp, 1)
    if ep <= 0:
        raise ValueError(f"ep must be positive, got {ep}")
    if ep * tp > n:
        raise ValueError(
            f"serving mesh ({ep}, {tp}) needs {ep * tp} devices but only {n} "
            f"are visible (set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"=N for host-CPU testing)"
        )
    return auto_mesh((ep, tp), ("data", "model"))
