"""Meshes: the one place in the repo that builds a ``jax.sharding.Mesh``.

Every mesh is built by `make_mesh`, with ``AxisType.Auto`` axes: the
sharding rules (`repro.dist.sharding`) and the fleet scans leave layout to
GSPMD and place constraints with ``with_sharding_constraint``, which refuses
the Explicit axes that a bare ``jax.make_mesh`` builds by default.

Mesh builders are FUNCTIONS (never module-level constants) so that importing
this module does not touch jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain placeholder devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


# single source of truth for the production topology (v5e 256-chip pods)
PRODUCTION_TOPOLOGY = {
    False: {"data": 16, "model": 16},                # 16x16 = 256 chips
    True: {"pod": 2, "data": 16, "model": 16},       # 2x16x16 = 512 chips
}


def make_mesh(shape, names, devices=None) -> jax.sharding.Mesh:
    """Mesh of ``shape`` over ``names`` with Auto axes, on ``devices`` (the
    default: all of ``jax.devices()``)."""
    shape, names = tuple(shape), tuple(names)
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips/pod single-pod, or 2x16x16 = 512 chips multi-pod."""
    topo = PRODUCTION_TOPOLOGY[multi_pod]
    return make_mesh(topo.values(), topo)


def make_data_mesh(n: int | None = None) -> jax.sharding.Mesh:
    """1-D ``("data",)`` mesh over the first ``n`` devices (default: all) —
    the fleet simulators' client-axis layout."""
    devs = jax.devices()
    n = len(devs) if n is None else n
    return make_mesh((n,), ("data",), devices=devs[:n])


class SpecMesh:
    """Device-free mesh stand-in: just axis name -> size.

    ``repro.dist.sharding``'s spec constructors only read ``mesh.shape`` and
    ``mesh.axis_names``, so production layouts can be computed and validated
    on machines without the 512 placeholder devices (unit tests, CI).
    """

    def __init__(self, shape: dict[str, int]):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def production_spec_mesh(*, multi_pod: bool = False) -> SpecMesh:
    """Shape-only twin of ``make_production_mesh`` (no jax device state)."""
    return SpecMesh(PRODUCTION_TOPOLOGY[multi_pod])


def make_local_mesh(model: int = 1) -> jax.sharding.Mesh:
    """``(data, model)`` mesh over whatever devices exist (CPU tests/smoke
    runs; ``data`` takes what ``model`` leaves)."""
    n = jax.device_count()
    return make_mesh((n // model, model), ("data", "model"))


# TPU v5e hardware constants for the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12     # FLOP/s
HBM_BW = 819e9               # B/s
ICI_BW = 50e9                # B/s per link
