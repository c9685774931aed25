"""Logical-axis sharding rules (flax.linen.partitioning style, stand-alone).

Every parameter and activation in the model zoo is annotated with *logical*
axis names ("batch", "heads", "mlp", ...).  A rules table maps logical names
to physical mesh axes.  This keeps the model code mesh-agnostic: the same
forward function lowers for 1 device (tests), a 16x16 pod, or a 2x16x16
multi-pod mesh.

Shardability guard: a logical axis only binds to a mesh axis when the
dimension is divisible by the mesh-axis size — e.g. kv_heads=8 cannot shard
over model=16 and silently falls back to replicated, which is exactly the
GQA-on-TPU convention (q heads sharded, kv replicated/partially sharded).
"""
from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> physical mesh axis (or tuple of axes)
LOGICAL_RULES_SINGLE_POD = {
    "batch": ("data",),
    "seq": None,
    "embed": None,
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": None,
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_mlp": None,
    "layers": None,
    "lru": ("model",),
    "q_lora": None,
    "kv_lora": None,
    "capacity": None,
    "stack": None,  # growth-operator weight-slot mode
    "grow_in": ("data",),
    "grow_out": ("model",),
    "rank": None,
    "cache_seq": None,  # KV-cache sequence axis (sharded for inference)
    "moe_group": ("data",),  # MoE dispatch-group axis (tokens stay local
    #                          in training; None for serving => tokens move
    #                          to expert owners, weights stay resident)
}

LOGICAL_RULES_MULTI_POD = dict(LOGICAL_RULES_SINGLE_POD)
LOGICAL_RULES_MULTI_POD["batch"] = ("pod", "data")
LOGICAL_RULES_MULTI_POD["moe_group"] = ("pod", "data")


def fsdp_rules(rules: dict, multi_pod: bool = False) -> dict:
    """FSDP+TP: parameter d_model ("embed") axes additionally shard over the
    data axis (GSPMD all-gathers at use, reduce-scatters grads — ZeRO-3).
    Activation specs are unaffected: their "batch" axis claims the data axis
    first, so "embed" falls back to replicated there (see logical_to_spec's
    used-axis tracking)."""
    r = dict(rules)
    r["embed"] = ("data",) if not multi_pod else ("data",)
    return r


def inference_rules(rules: dict) -> dict:
    """Serving layout: TP-only weights (no FSDP — GSPMD hoists the
    loop-invariant param all-gathers out of the decode loop, materializing
    the full model per device), KV caches sharded along *sequence* over the
    model axis (flash-decode style partial-softmax; required when kv_heads
    < model axis size), experts sharded 2-D (data x model) so 100B+-param
    MoEs fit without FSDP."""
    r = dict(rules)
    r["embed"] = None
    r["cache_seq"] = ("model",)
    # NOTE: within a cache spec, cache_seq claims "model" first and the
    # used-axis guard then replicates kv_heads there; weight specs have no
    # cache_seq, so wk/wv still shard over model.
    r["kv_heads"] = ("model",)
    r["heads"] = ("model",)
    r["experts"] = ("data", "model")
    r["expert_mlp"] = ("model",)  # experts axis rarely divides data*model
    # dispatched-token tensors follow the expert owners (all-to-all on the
    # tiny token activations) instead of forcing weight gathers
    r["moe_group"] = None
    return r


def sharding_rules_for_mesh(mesh: Mesh, fsdp: bool = False,
                            inference: bool = False) -> dict:
    multi = "pod" in mesh.axis_names
    base = LOGICAL_RULES_MULTI_POD if multi else LOGICAL_RULES_SINGLE_POD
    if inference:
        return inference_rules(base)
    return fsdp_rules(base, multi) if fsdp else base


class _RulesState(threading.local):
    def __init__(self):
        self.rules = None
        self.mesh = None


_STATE = _RulesState()


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: dict | None = None):
    """Activate logical->physical rules; inside, ``annotate`` is live."""
    prev = (_STATE.rules, _STATE.mesh)
    _STATE.rules = rules if rules is not None else sharding_rules_for_mesh(mesh)
    _STATE.mesh = mesh
    try:
        yield
    finally:
        _STATE.rules, _STATE.mesh = prev


def _axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def logical_to_spec(logical, shape=None, mesh: Mesh | None = None,
                    rules: dict | None = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec.

    ``shape`` (optional) enables the divisibility guard.
    """
    mesh = mesh if mesh is not None else _STATE.mesh
    rules = rules if rules is not None else _STATE.rules
    if rules is None:
        return P()
    sizes = _axis_sizes(mesh) if mesh is not None else {}
    out = []
    used = set()
    for i, name in enumerate(logical):
        axes = rules.get(name)
        if axes is None:
            out.append(None)
            continue
        axes = tuple(a for a in axes if a not in used and a in sizes)
        if not axes:
            out.append(None)
            continue
        total = 1
        for a in axes:
            total *= sizes[a]
        if shape is not None and shape[i] % total != 0:
            # fall back: try a prefix of the axes that divides
            ok = ()
            tot = 1
            for a in axes:
                if shape[i] % (tot * sizes[a]) == 0:
                    ok = ok + (a,)
                    tot *= sizes[a]
                else:
                    break
            axes = ok
        if not axes:
            out.append(None)
        else:
            used.update(axes)
            out.append(axes if len(axes) > 1 else axes[0])
    return P(*out)


def annotate(x, logical):
    """with_sharding_constraint by logical names; no-op outside use_rules.

    Inside a partial-auto ``shard_map`` region (lazy-sync FSDP step), the
    ambient abstract mesh has Manual axes: constraints are rebuilt on that
    mesh with the manual axes stripped from the spec (they are physical
    there, not the partitioner's business).
    """
    if _STATE.rules is None or _STATE.mesh is None:
        return x
    spec = logical_to_spec(logical, shape=x.shape)
    mesh = _STATE.mesh
    cur = jax.sharding.get_abstract_mesh()
    manual = set(cur.manual_axes)
    if manual:
        parts = []
        for e in spec:
            if e is None:
                parts.append(None)
                continue
            es = e if isinstance(e, tuple) else (e,)
            kept = tuple(a for a in es if a not in manual)
            parts.append(kept if len(kept) > 1
                         else (kept[0] if kept else None))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(cur, P(*parts)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def params_shardings(param_specs, mesh: Mesh, rules: dict | None = None,
                     shapes=None):
    """Resolve a pytree of logical-spec tuples into NamedShardings.

    ``shapes`` — optional matching pytree of ShapeDtypeStructs/arrays used for
    the divisibility guard.
    """
    rules = rules if rules is not None else sharding_rules_for_mesh(mesh)

    if shapes is None:
        def f(spec):
            return NamedSharding(mesh, logical_to_spec(spec, None, mesh, rules))
        return jax.tree.map(f, param_specs, is_leaf=lambda x: isinstance(x, tuple))

    def g(spec, arr):
        return NamedSharding(
            mesh, logical_to_spec(spec, arr.shape, mesh, rules)
        )
    return jax.tree.map(
        g, param_specs, shapes, is_leaf=lambda x: isinstance(x, tuple)
    )


def named_sharding_tree(tree, mesh: Mesh, spec=P()):
    """Uniform NamedSharding over a whole pytree (e.g. replicated)."""
    return jax.tree.map(lambda _: NamedSharding(mesh, spec), tree)


def zero_shardings(base_shardings, shapes, mesh: Mesh,
                   zero_axes=("data",)):
    """ZeRO-style extra sharding for optimizer state.

    For each leaf, take the parameter's sharding and additionally shard the
    *largest free (replicated) dimension* over ``zero_axes`` if divisible.
    Optimizer moments/master weights are only touched by the update (no
    activation interplay), so this is free memory savings; GSPMD inserts the
    all-gather/reduce-scatter pair around the update.
    """
    sizes = _axis_sizes(mesh)

    def one(sh, leaf):
        spec = list(sh.spec) + [None] * (len(leaf.shape) - len(sh.spec))
        used = set()
        for e in spec:
            if e is None:
                continue
            used.update(e if isinstance(e, tuple) else (e,))
        # place every still-unused zero axis on the largest divisible free
        # dim (each axis independently — pod and data may land on different
        # dims, or stack on the same one if divisibility allows)
        shard_per_dim = [
            int(np.prod([sizes[a] for a in
                         (e if isinstance(e, tuple) else (e,))]))
            if e is not None else 1 for e in spec]
        for a in zero_axes:
            if a in used or a not in sizes:
                continue
            cands = [(leaf.shape[i] // shard_per_dim[i], i)
                     for i in range(len(spec))
                     if (leaf.shape[i] % (shard_per_dim[i] * sizes[a]) == 0)]
            if not cands:
                continue
            _, idx = max(cands)
            cur = spec[idx]
            if cur is None:
                spec[idx] = a
            else:
                spec[idx] = (cur if isinstance(cur, tuple) else (cur,)) + (a,)
            shard_per_dim[idx] *= sizes[a]
            used.add(a)
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, base_shardings, shapes)
