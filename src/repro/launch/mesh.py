"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device;
only ``dryrun.py`` forces 512 host devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_serve_mesh(shape):
    """The serving engine's (data=replica, model=TP) mesh from a "DxM"
    string or (data, model) tuple — the launch-layer face of
    ``distributed/serve_sharding.py``."""
    from repro.distributed.serve_sharding import parse_mesh_arg
    return jax.make_mesh(parse_mesh_arg(shape), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_host_mesh():
    """Whatever devices exist locally, as a (data, model) mesh with model=1.

    Used by the trainer/examples on this CPU container and by the elastic
    subprocess tests with forced host device counts.
    """
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
