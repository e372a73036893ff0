"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state. The dry-run sets XLA_FLAGS for 512 host devices *before* any jax
import (see dryrun.py); real launches get the same topology from the TPU
runtime.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 (one v5e pod, 256 chips) or 2x16x16 (two pods, 512 chips).

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    The "pod" axis is the slow (DCN-ish) axis: only data-parallel gradient
    reduction and MoE-weight FSDP gathers cross it.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist, as a (data, model) mesh — smoke tests (1 CPU
    device) and small real runs."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
