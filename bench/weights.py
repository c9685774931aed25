"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program: the tree has the
program's layout (taken from ``jax.eval_shape`` of its init) and the
values come from here, in the dtype the configuration serves.  Norm
scales and biases are drawn away from 1 and 0 so that the reference
comparison sees them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02  # matrices and embeddings
SCALE_STD = 0.1  # norm scales around 1
BIAS_STD = 0.05


def seed_key(seed, stream=0):
    """A PRNG key from any whole-number seed (all of its bits count)."""
    state = np.random.SeedSequence([int(seed) % (1 << 64), stream])
    return jnp.asarray(state.generate_state(2, np.uint32))


def _kind(path):
    last = str(getattr(path[-1], "key", path[-1]))
    if last == "scale" or last.endswith("_norm"):
        return "scale"
    if last == "bias" or (last.startswith("b") and last != "bt"):
        return "bias"
    return "matrix"


def make_params(shapes, key):
    """Values for a tree of ``jax.ShapeDtypeStruct`` (one jitted call)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def gen(key):
        out = []
        for i, (path, s) in enumerate(flat):
            z = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  jnp.float32)
            kind = _kind(path)
            if kind == "scale":
                v = 1.0 + SCALE_STD * z
            elif kind == "bias":
                v = BIAS_STD * z
            else:
                v = STD * z
            out.append(v.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(gen)(key)
