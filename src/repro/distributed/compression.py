"""Gradient compression for the bandwidth-thin cross-pod axis.

At 2+ pods the data-parallel all-reduce crosses the inter-pod links (DCN or
optical), which are far thinner than intra-pod ICI.  Two standard tricks,
implemented as a ``grad_transform`` hook for ``make_train_step``:

  * bf16 reduction — cast grads to bf16 before the cross-pod psum
    (halves wire bytes; Adam is insensitive to bf16 gradient noise);
  * int8 + error feedback (1-bit-Adam-family, arXiv:2102.02888 lineage) —
    per-tensor scaled int8 quantization with the quantization residual
    carried to the next step, preserving convergence.

Inside pjit, collectives are partitioner-inserted, so explicit compression
uses ``shard_map`` over the pod axis: within the map we quantize, psum the
int8/bf16 payload, and dequantize.  The intra-pod reduction stays full
precision (fat links), only the pod axis is compressed.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bf16_compress(grads):
    """Lossy cast hook (applied pre-optimizer, after the mean)."""
    return jax.tree.map(
        lambda g: g.astype(jnp.bfloat16).astype(g.dtype), grads)


def _quantize_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def make_crosspod_psum(mesh, *, method: str = "bf16", axis: str = "pod"):
    """Returns psum_fn(grads) -> grads, averaging over ``axis`` with
    compressed payloads via shard_map.  Error feedback state (int8 mode) is
    carried functionally: psum_fn(grads, err) -> (grads, err)."""
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis}")
    other = tuple(a for a in mesh.axis_names if a != axis)
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    if method == "bf16":
        def inner(g):
            return jax.lax.psum(g.astype(jnp.bfloat16),
                                axis).astype(g.dtype) / n

        def psum_fn(grads):
            fn = jax.shard_map(
                lambda t: jax.tree.map(inner, t), mesh=mesh,
                in_specs=P(), out_specs=P(), check_vma=False)
            return fn(grads)
        return psum_fn

    if method == "int8":
        def inner(g, e):
            x = g.astype(jnp.float32) + e
            q, scale = _quantize_int8(x)
            err = x - _dequantize(q, scale)  # residual feedback
            total = jax.lax.psum(q.astype(jnp.int32), axis)
            s_total = jax.lax.psum(scale, axis)  # conservative shared scale
            out = (total.astype(jnp.float32) * (s_total / n) / n)
            return out.astype(g.dtype), err

        def psum_fn(grads, err):
            def mapped(gt, et):
                out = jax.tree.map(inner, gt, et)
                g_new = jax.tree.map(lambda t: t[0], out,
                                     is_leaf=lambda t: isinstance(t, tuple))
                e_new = jax.tree.map(lambda t: t[1], out,
                                     is_leaf=lambda t: isinstance(t, tuple))
                return g_new, e_new
            fn = jax.shard_map(
                mapped, mesh=mesh, in_specs=(P(), P()),
                out_specs=(P(), P()), check_vma=False)
            return fn(grads, err)
        return psum_fn

    raise ValueError(method)


def init_error_feedback(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
