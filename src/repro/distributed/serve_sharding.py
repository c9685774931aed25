"""Serve-side sharding: the (data=replica, model=TP) mesh plan for the
continuous-batching engine.

The training stack already has everything needed to shard a forward pass
(`sharding.py` logical rules + ``annotate`` constraints); what serving
adds is a *placement plan* for the engine's long-lived device state:

  * weights        — TP-only (``inference_rules``): heads/mlp/vocab shard
                     over ``model``, everything else replicated.  No FSDP:
                     the decode loop reads every weight every step, so the
                     full model lives on each replica.
  * slot pools     — the capacity axis shards over ``data`` (each replica
                     owns a contiguous band of slots) and the head axes
                     shard over ``model`` (each TP rank owns its heads'
                     KV/recurrent state).  The cache *sequence* axis stays
                     local: slot decode addresses it with per-row dynamic
                     indices, which sequence-sharding would turn into
                     per-step collectives.
  * paged arenas   — page payloads shard on the head axis only; the page
                     axis is a shared id space (any slot may hold any
                     page), so it must not shard.  Block tables are tiny
                     int32 index tensors and stay fully REPLICATED — every
                     device resolves the same page indirection locally.
  * decode state   — the per-slot scalar vectors (tokens, positions,
                     remaining, eos, done) and PRNG chains shard over
                     ``data`` with the slots they describe.

``ServeMeshPlan`` is hashable (one canonical instance per mesh shape via
``get_serve_plan``) so it can extend the engine's jit-cache key, and the
jitted engine functions are traced under ``use_rules(plan.mesh, ...)`` so
the model-internal ``annotate`` calls pin activations to the same layout
— per-layer collectives (the TP psums of attention/MLP output
projections) are then the only cross-device traffic in a macro step.

Everything here is inert at ``plan=None`` (the single-device engine), and
validatable in this container via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import (
    LOGICAL_RULES_SINGLE_POD,
    inference_rules,
    logical_to_spec,
    params_shardings,
    use_rules,
)


def serve_sharding_rules() -> dict:
    """Inference rules specialised to SLOT decode.

    ``inference_rules`` shards the cache sequence axis (flash-decode
    style) — right for one long sequence, wrong for a slot pool where
    every row reads/writes its own dynamic position every step.  Serving
    shards the slot ("batch") axis over ``data`` and the head axes over
    ``model`` instead, keeping each position update device-local.
    """
    r = inference_rules(LOGICAL_RULES_SINGLE_POD)
    r["cache_seq"] = None
    return r


def parse_mesh_arg(s) -> Tuple[int, int]:
    """``"DxM"`` / ``(D, M)`` -> a (data, model) shape tuple."""
    if isinstance(s, tuple):
        shape = s
    else:
        parts = str(s).lower().split("x")
        if len(parts) != 2:
            raise ValueError(
                f"mesh layout {s!r} must be DATAxMODEL, e.g. '2x2'")
        try:
            shape = (int(parts[0]), int(parts[1]))
        except ValueError:
            raise ValueError(
                f"mesh layout {s!r} must be DATAxMODEL, e.g. '2x2'")
    if len(shape) != 2 or shape[0] < 1 or shape[1] < 1:
        raise ValueError(f"mesh shape {shape} must be two positive sizes "
                         "(data, model)")
    return (int(shape[0]), int(shape[1]))


def validate_serve_mesh(shape, cfg, capacity: int,
                        n_devices: Optional[int] = None) -> Tuple[int, int]:
    """Reject layouts that cannot shard this engine, with errors that
    name the offending geometry (instead of an XLA shape crash later).
    """
    data, model = parse_mesh_arg(shape)
    if n_devices is not None and data * model != n_devices:
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices but "
            f"{n_devices} are visible (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{data * model}, or pick a layout whose product is "
            f"{n_devices})")
    if cfg.n_heads % model != 0:
        raise ValueError(
            f"model axis {model} does not divide {cfg.name!r}'s "
            f"n_heads={cfg.n_heads} — tensor parallelism splits the head "
            f"axis, so pick model from the divisors of {cfg.n_heads}")
    if capacity % data != 0:
        raise ValueError(
            f"data axis {data} does not divide the slot-pool capacity "
            f"{capacity} — each replica owns capacity/data slots, so "
            f"raise --capacity to a multiple of {data} or shrink the "
            f"data axis")
    return (data, model)


def choose_serve_mesh_shape(n_devices: int, cfg, capacity: int
                            ) -> Tuple[int, int]:
    """Pick a (data, model) layout for this device count + model geometry:
    the largest TP (model) axis that divides both the device count and the
    head count, with the remainder as data replicas dividing capacity.
    TP-first mirrors ``elastic.choose_mesh_shape``'s preference — weights
    are the scarce memory, and TP is what shrinks them per device."""
    for model in sorted((m for m in range(1, n_devices + 1)
                         if n_devices % m == 0), reverse=True):
        data = n_devices // model
        if cfg.n_heads % model == 0 and capacity % data == 0:
            return (data, model)
    raise ValueError(
        f"no (data, model) layout over {n_devices} devices divides both "
        f"n_heads={cfg.n_heads} and capacity={capacity}; adjust "
        f"--capacity or pass --mesh explicitly")


class ServeMeshPlan:
    """One mesh + the sharding builders the engine needs.  Hashable by
    identity; ``get_serve_plan`` canonicalises per shape so every engine
    over the same mesh shares one jit cache."""

    def __init__(self, shape: Tuple[int, int]):
        self.shape = shape
        self.data, self.model = shape
        self.n_devices = self.data * self.model
        self.mesh = jax.make_mesh(shape, ("data", "model"),
                                  axis_types=(AxisType.Auto,) * 2)
        self.rules = serve_sharding_rules()

    def describe(self) -> str:
        return f"{self.data}x{self.model}"

    # ------------------------------------------------------------ shardings
    def params_shardings_for(self, fam, cfg, params):
        return params_shardings(fam.param_specs(cfg), self.mesh,
                                self.rules, shapes=params)

    def pool_shardings(self, fam, cfg, pool, meta):
        """NamedSharding tree for one slot pool (dense or paged).

        Dense pools resolve ``fam.cache_specs(cfg)`` directly (the
        "batch" axis is the slot axis -> data; kv_heads/lru -> model,
        with the divisibility guard replicating non-dividing head
        counts).  Paged pools re-map each DECLARED group: arena payloads
        keep only the layer + trailing (head) axes of the dense spec —
        the page axis (and, for seq groups, the in-page axis) must NOT
        shard, since pages are one shared id space any slot may hold —
        and the block table is replicated everywhere.  Leaves a group
        does not name (dense per-slot carries) shard like the dense
        pool.
        """
        specs = fam.cache_specs(cfg)
        if meta is None:
            return params_shardings(specs, self.mesh, self.rules,
                                    shapes=pool)
        paged = {g.path[0]: g for g in meta.groups}

        def leaf_sh(logical, leaf):
            return NamedSharding(
                self.mesh, logical_to_spec(tuple(logical), leaf.shape,
                                           self.mesh, self.rules))

        def walk(sp, pl, g=None):
            if isinstance(pl, dict) and "bt" in pl and g is not None:
                out = {}
                for lk, leaf in pl.items():
                    if lk == "bt":
                        out[lk] = NamedSharding(self.mesh, P())
                    elif lk in g.leaves:
                        # seq: (L, B, S, ...) -> (L, pages, page, ...);
                        # slot: (L, B, tail...) -> (L, pages, tail...)
                        arena = ((sp[lk][0], None, None)
                                 + tuple(sp[lk][3:]) if g.kind == "seq"
                                 else (sp[lk][0], None) + tuple(sp[lk][2:]))
                        out[lk] = leaf_sh(arena, leaf)
                    else:
                        out[lk] = leaf_sh(sp[lk], leaf)
                return out
            if isinstance(pl, dict):
                return {k: walk(sp[k], pl[k], paged.get(k)) for k in pl}
            return leaf_sh(sp, pl)

        return walk(specs, pool)

    def state_shardings(self):
        """The engine's persistent decode-state six-tuple: per-slot
        vectors ride the data axis with their slots."""
        d = NamedSharding(self.mesh, P("data"))
        return (d, d, d, d, d, NamedSharding(self.mesh, P("data", None)))

    # ------------------------------------------------------------ admission
    def free_slot_order(self, capacity: int):
        """Slot ids in admission order, round-robining consecutive
        admissions across data replicas: the j-th admitted request lands
        on replica ``j % data`` (each replica owns a contiguous
        capacity/data band of the slot axis), so light traffic spreads
        over replicas instead of saturating replica 0's band first."""
        band = capacity // self.data
        return [(j % self.data) * band + j // self.data
                for j in range(capacity)]

    # -------------------------------------------------------------- tracing
    def wrap(self, fn):
        """Run ``fn`` under this plan's mesh + logical rules, so the
        model-internal ``annotate`` calls become live sharding
        constraints at trace time.  Entering the context per call is a
        few thread-local writes — nothing on the steady-state hot path
        recompiles or syncs."""
        if fn is None:
            return None

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with self.mesh, use_rules(self.mesh, self.rules):
                return fn(*args, **kwargs)

        return wrapped


@functools.lru_cache(maxsize=None)
def get_serve_plan(shape: Tuple[int, int]) -> ServeMeshPlan:
    """Canonical plan per mesh shape (identity-hashable jit-cache key)."""
    return ServeMeshPlan(parse_mesh_arg(shape))


def per_device_bytes(tree) -> int:
    """Bytes one device holds for ``tree`` — the startup report's
    per-device pool reservation.  Uses each leaf's actual sharding
    (committed arrays), falling back to the full shape for uncommitted
    single-device arrays."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        sh = getattr(leaf, "sharding", None)
        shape = leaf.shape
        if sh is not None:
            try:
                shape = sh.shard_shape(leaf.shape)
            except Exception:
                pass
        total += int(np.prod(shape)) * np.dtype(leaf.dtype).itemsize
    return total
