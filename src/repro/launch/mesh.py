"""Production meshes.  Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init)."""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_rules", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data","model").  Multi-pod: 2 pods =
    512 chips ("pod","data","model"); the pod axis is DP by default (or
    pipeline stages via repro.train.pipeline_parallel)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_rules(mesh, *, sequence_parallel: bool = True):
    from repro.sharding.rules import MeshRules

    rules = MeshRules(mesh)
    if sequence_parallel:
        rules.logical["seq"] = ("model",)
    return rules


class HW:
    """TPU v5e roofline constants (per chip)."""

    PEAK_FLOPS_BF16 = 197e12  # FLOP/s
    HBM_BW = 819e9  # B/s
    ICI_LINK_BW = 50e9  # B/s per link (assignment constant)
    # ring collectives stream both directions of a torus axis concurrently
    ICI_LINKS_USED = 2
    HBM_PER_CHIP = 16 * 2**30
