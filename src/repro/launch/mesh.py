"""Production mesh construction (functions only — importing this module
never touches jax device state).

Every mesh is built with ``Auto`` axis types: the model code shards through
GSPMD sharding constraints (``repro.nn.sharding.shard``), and JAX >= 0.7's
default ``Explicit`` axes would instead demand an output sharding on every
gather (``embed_lookup``'s ``jnp.take`` raises ``ShardingTypeError``).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; the multi-pod mesh adds a leading pure-DP
    "pod" axis (2 pods = 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(dp: int = 1, tp: int = 1):
    """Small ``(data, model)`` mesh over host devices (tests / examples).

    Validates the request against the visible device count up front — the
    error out of ``jax.make_mesh`` for an oversubscribed mesh is an opaque
    reshape failure.
    """
    if dp < 1 or tp < 1:
        raise ValueError(
            f"make_host_mesh: dp and tp must be >= 1, got dp={dp} tp={tp}")
    n = len(jax.devices())
    if dp * tp > n:
        raise ValueError(
            f"make_host_mesh: mesh {dp}x{tp} needs {dp * tp} devices but "
            f"only {n} are visible (on the CPU backend, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={dp * tp} "
            f"before the first device query; or shrink the mesh)")
    return _auto_mesh((dp, tp), ("data", "model"))
