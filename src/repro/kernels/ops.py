"""Public jit'd entry points for the Pallas kernels.

The kernels are written for a TPU.  Callers select how they run via
``mode``:

  mode="auto"      — compiled Pallas; raises :class:`NoTPUError` on any
                     backend that is not a TPU (never falls back)
  mode="interpret" — the Pallas interpreter, on any backend (CPU tests)
  mode="reference" — the jnp oracles in ``ref.py``
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import (
    chunk_verify_attention as _chunk_verify,
    decode_attention as _decode,
    paged_chunk_verify_attention as _paged_chunk_verify,
    paged_ring_decode_attention as _paged_ring_decode,
    paged_slot_decode_attention as _paged_slot_decode,
    ring_decode_attention as _ring_decode,
    slot_decode_attention as _slot_decode,
)
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.rglru_scan import rglru_scan as _rglru
from repro.kernels.tr_sandwich import tr_sandwich as _sandwich


class NoTPUError(RuntimeError):
    """``mode="auto"`` asked for compiled kernels off a TPU backend."""


def _interp(mode: str) -> bool:
    if mode == "interpret":
        return True
    if mode == "auto":
        backend = jax.default_backend()
        if backend != "tpu":
            raise NoTPUError(
                f"mode='auto' runs the compiled Pallas kernels, which need "
                f"a TPU backend, but JAX's default backend is {backend!r}; "
                f"use mode='interpret' (the Pallas interpreter) or "
                f"'reference' (the jnp oracles) off a TPU")
        return False
    raise ValueError(mode)


def tr_sandwich(x, a_i, a_o, *, mode="auto", **kw):
    if mode == "reference":
        return ref.tr_sandwich_ref(x, a_i, a_o)
    return _sandwich(x, a_i, a_o, interpret=_interp(mode), **kw)


def flash_attention(q, k, v, *, causal=True, mode="auto", **kw):
    if mode == "reference":
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _flash(q, k, v, causal=causal, interpret=_interp(mode), **kw)


def decode_attention(q, k, v, kv_len, *, mode="auto", done=None, **kw):
    if done is not None:
        # the macro-step done vector is sugar for kv_len = 0 — apply it
        # here so the reference oracle and the kernel agree on done rows
        kv_len = jnp.where(done, 0, jnp.broadcast_to(
            jnp.asarray(kv_len, jnp.int32).reshape(-1), (q.shape[0],)))
    if mode == "reference":
        return ref.decode_attention_ref(q, k, v, kv_len)
    return _decode(q, k, v, kv_len, interpret=_interp(mode), **kw)


def slot_decode_attention(q, k, v, kv_len, *, mode="auto", done=None, **kw):
    """Full-KV slot decode over the serve pool layout (B, S, KV, hd).
    ``done`` rows are folded into ``kv_len = 0`` (exact-zero output)."""
    kv_len = jnp.broadcast_to(
        jnp.asarray(kv_len, jnp.int32).reshape(-1), (q.shape[0],))
    if done is not None:
        kv_len = jnp.where(done, 0, kv_len)
    if mode == "reference":
        return ref.slot_decode_attention_ref(q, k, v, kv_len)
    return _slot_decode(q, k, v, kv_len, interpret=_interp(mode), **kw)


def ring_decode_attention(q, k, v, slot_positions, *, window, mode="auto",
                          done=None, **kw):
    """Ring-buffer window slot decode over the pool layout.  ``done``
    rows are folded into ``slot_positions = -1`` (exact-zero output)."""
    slot_positions = jnp.broadcast_to(
        jnp.asarray(slot_positions, jnp.int32).reshape(-1), (q.shape[0],))
    if done is not None:
        slot_positions = jnp.where(done, -1, slot_positions)
    if mode == "reference":
        return ref.ring_decode_attention_ref(q, k, v, slot_positions,
                                             window=window)
    return _ring_decode(q, k, v, slot_positions, window=window,
                        interpret=_interp(mode), **kw)


def chunk_verify_attention(q, ck, cv, k, v, offsets, *, ring, window=None,
                           mode="auto", done=None, **kw):
    """Speculative chunk-verify attention (read-only cache) over the pool
    layout.  ``done`` rows are folded into ``offsets = -1``."""
    offsets = jnp.broadcast_to(
        jnp.asarray(offsets, jnp.int32).reshape(-1), (q.shape[0],))
    if done is not None:
        offsets = jnp.where(done, -1, offsets)
    if mode == "reference":
        return ref.chunk_verify_attention_ref(q, ck, cv, k, v, offsets,
                                              ring=ring, window=window)
    return _chunk_verify(q, ck, cv, k, v, offsets, ring=ring, window=window,
                         interpret=_interp(mode), **kw)


def paged_slot_decode_attention(q, k, v, bt, kv_len, *, mode="auto",
                                done=None, **kw):
    """Full-KV slot decode over a PAGED pool: (n_pages, page, KV, hd)
    arenas + (B, nblk) block tables.  ``done`` rows fold into
    ``kv_len = 0`` exactly as in the dense entry."""
    kv_len = jnp.broadcast_to(
        jnp.asarray(kv_len, jnp.int32).reshape(-1), (q.shape[0],))
    if done is not None:
        kv_len = jnp.where(done, 0, kv_len)
    if mode == "reference":
        return ref.paged_slot_decode_attention_ref(q, k, v, bt, kv_len)
    return _paged_slot_decode(q, k, v, bt, kv_len, interpret=_interp(mode),
                              **kw)


def paged_ring_decode_attention(q, k, v, bt, slot_positions, *, window,
                                mode="auto", done=None, **kw):
    """Ring-buffer window slot decode over a PAGED pool.  ``done`` rows
    fold into ``slot_positions = -1``."""
    slot_positions = jnp.broadcast_to(
        jnp.asarray(slot_positions, jnp.int32).reshape(-1), (q.shape[0],))
    if done is not None:
        slot_positions = jnp.where(done, -1, slot_positions)
    if mode == "reference":
        return ref.paged_ring_decode_attention_ref(q, k, v, bt,
                                                   slot_positions,
                                                   window=window)
    return _paged_ring_decode(q, k, v, bt, slot_positions, window=window,
                              interpret=_interp(mode), **kw)


def paged_chunk_verify_attention(q, ck, cv, bt, k, v, offsets, *, ring,
                                 window=None, mode="auto", done=None, **kw):
    """Speculative chunk-verify over a PAGED pool (cache read-only).
    ``done`` rows fold into ``offsets = -1``."""
    offsets = jnp.broadcast_to(
        jnp.asarray(offsets, jnp.int32).reshape(-1), (q.shape[0],))
    if done is not None:
        offsets = jnp.where(done, -1, offsets)
    if mode == "reference":
        return ref.paged_chunk_verify_attention_ref(
            q, ck, cv, bt, k, v, offsets, ring=ring, window=window)
    return _paged_chunk_verify(q, ck, cv, bt, k, v, offsets, ring=ring,
                               window=window, interpret=_interp(mode), **kw)


def paged_latent_gather(arena, bt, *, mode="auto"):
    """Dense (B, S, r) view of a paged MLA latent arena.

    Not a Pallas kernel: the absorbed-MLA decode consumes the latent
    cache as ordinary matmul operands, so the paged layout only needs a
    layout gather (XLA fuses it into the consuming einsum).  The entry
    lives here so paged MLA dispatches through the same mode switch as
    every other paged cache group and the oracle suite covers it."""
    if mode == "reference":
        return ref.paged_latent_gather_ref(arena, bt)
    if mode not in ("auto", "interpret"):
        raise ValueError(mode)
    n_pages = arena.shape[0]
    g = arena[jnp.minimum(jnp.asarray(bt, jnp.int32), n_pages - 1)]
    return g.reshape((bt.shape[0], -1) + arena.shape[2:])


def rglru_scan(a, b, h0=None, *, mode="auto", **kw):
    if mode == "reference":
        return ref.rglru_scan_ref(a, b, h0)
    return _rglru(a, b, h0, interpret=_interp(mode), **kw)
