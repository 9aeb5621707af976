"""Mesh construction. FUNCTIONS, not module-level constants, so importing
this module never touches jax device state (the dry-run entry point must set
XLA_FLAGS before the first jax call).

Every mesh in the repo is built here, with ``AxisType.Auto`` axes: a bare
``jax.make_mesh`` makes ``Explicit`` axes, under which the sharding-rule
constraints (``with_sharding_constraint`` on logical axes) are refused."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A mesh of ``shape`` over ``axes`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Production meshes: 16x16 = 256 chips/pod; 2 pods = 512 chips.

    Axes are roles (DESIGN.md §5): `data` = DP/FSDP/SP, `model` = TP/EP;
    `pod` is the outer DP (or pipeline) axis across the slower inter-pod
    links.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small (data, model) mesh over the local devices — the 2x2 serving
    mesh of one four-chip host, and the forced host devices of the tests
    and CPU examples."""
    return make_mesh((data, model), ("data", "model"))
