"""Shared ``shard_map`` entry point and the scan-in-manual-region rule.

Every module in this repo imports ``shard_map`` from here, so the one
place that knows the installed JAX's spelling (top-level ``jax.shard_map``
with ``check_vma`` / ``axis_names``) is this file.
"""
from __future__ import annotations

from jax import shard_map as _shard_map


def scan_safe_in_manual(mesh, manual_axes) -> bool:
    """Whether ``lax.scan`` may stay inside a shard_map-manual region.

    XLA's SPMD partitioner check-fails (``sharding.IsManualSubgroup()``)
    on control flow nested in a *partially*-manual computation — some
    mesh axes manual, the rest GSPMD-auto — on every JAX release this
    repo supports, so those regions must python-unroll their layer
    stacks.  A *fully*-manual region (every mesh axis manual, the
    top-level serving shard_map) hands XLA a plain per-shard program and
    scan partitions trivially; with no mesh on record we cannot prove
    full coverage and conservatively report unsafe.
    """
    if mesh is None:
        return False
    return frozenset(manual_axes) >= frozenset(mesh.axis_names)


def shard_map(
    f,
    *,
    mesh,
    in_specs,
    out_specs,
    check_vma: bool | None = None,
    axis_names=None,
):
    """``jax.shard_map``; ``axis_names`` may be any iterable of axes."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return _shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )
