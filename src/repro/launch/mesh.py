"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  The dry-run forces 512 host devices via XLA_FLAGS before
any jax import; everything else sees the real device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``.  Newer JAX defaults to
    ``Explicit`` axes, which ``with_sharding_constraint`` (the activation
    policy in ``repro.parallel.ctx``) refuses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh over however many (possibly forced-host) devices exist."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


MESHES = {
    "single": dict(multi_pod=False),   # 16 x 16 = 256 chips (one pod)
    "multi": dict(multi_pod=True),     # 2 x 16 x 16 = 512 chips (two pods)
}
