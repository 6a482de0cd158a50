"""Production mesh definitions (assignment: MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.distributed.sharding import (LOGICAL_RULES_1POD,
                                        LOGICAL_RULES_2POD, MeshRules)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_rules(mesh) -> MeshRules:
    rules = LOGICAL_RULES_2POD if "pod" in mesh.axis_names \
        else LOGICAL_RULES_1POD
    return MeshRules(mesh, rules)


def make_debug_mesh(n_devices: int | None = None, *, model: int = 2):
    """Small mesh over however many (possibly forced-host) devices exist —
    used by tests; same axis names as the single-pod production mesh."""
    n = n_devices or len(jax.devices())
    model = min(model, n)
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_serving_mesh(n_devices: int | None = None):
    """All (possibly forced-host) devices on the ``data`` axis.

    The serving subsystem (:mod:`repro.serve.sharded`) is pure data
    parallelism — the request batch axis shards over ``data`` and the
    mapped program is replicated — so the model axis stays at 1. Axis
    names match the debug/production meshes, and CPU CI gets >= 8
    shards via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    return make_debug_mesh(n_devices, model=1)
