"""Production meshes. A FUNCTION (never a module-level constant) so that
importing this module never touches jax device state.

`make_mesh` builds every mesh of the repo with `Auto` axes: the
sharding rules in `repro.parallel.sharding` place arrays by
constraint, not by explicit-axis typing.
"""
from __future__ import annotations

import jax


def make_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_fabric_mesh(pods: int, devices_per_pod: int = 1):
    """The verbs fabric's second mesh axis: a (`pod`, `device`) grid for
    routed multi-pod QPs, built through `make_mesh`. Returns ``None``
    when the rig does not expose exactly ``pods * devices_per_pod`` devices (the
    1-device CPU test rig): the fabric then routes over the logical grid
    only, with identical addressing semantics."""
    if pods * devices_per_pod != len(jax.devices()):
        return None
    return make_mesh((pods, devices_per_pod), ("pod", "device"))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
