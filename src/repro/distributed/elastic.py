"""Elastic scaling: resume any checkpoint on any device count.

Checkpoints are stored as full logical arrays (``repro/checkpoint``), so
elasticity is purely a *placement* question: build the new mesh from
whatever devices exist, resolve shardings from the same logical rules, and
``device_put`` the restored leaves.  Combined with the deterministic data
pipeline (batch = f(seed, step, shard)) a job can lose a pod, restart on
half the chips, and reproduce the exact gradient sequence (modulo batch
layout) from the last checkpoint.

``choose_mesh_shape`` picks the largest (data, model) factorization with
model <= requested TP degree — the policy a real launcher applies after a
node failure re-inventory.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType

from repro.distributed.sharding import params_shardings, \
    sharding_rules_for_mesh


def choose_mesh_shape(n_devices: int, prefer_model: int = 16):
    """Largest power-of-two model axis <= prefer_model dividing n."""
    model = 1
    m = 1
    while m * 2 <= prefer_model and n_devices % (m * 2) == 0:
        m *= 2
    model = m
    return (n_devices // model, model)


def make_elastic_mesh(prefer_model: int = 16):
    n = len(jax.devices())
    shape = choose_mesh_shape(n, prefer_model)
    return jax.make_mesh(shape, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def reshard_restore(ckpt_dir: str, template, param_specs, *,
                    prefer_model: int = 16, step: Optional[int] = None):
    """Restore a checkpoint onto a mesh built from the CURRENT device set.

    Returns (tree, mesh, step, extra).
    """
    from repro.checkpoint import load_checkpoint

    mesh = make_elastic_mesh(prefer_model)
    rules = sharding_rules_for_mesh(mesh)
    shardings = params_shardings(param_specs, mesh, rules, shapes=template)
    tree, step, extra = load_checkpoint(ckpt_dir, template, step,
                                        shardings=shardings)
    return tree, mesh, step, extra
