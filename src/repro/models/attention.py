"""Attention: GQA/MQA grouped einsum with memory-efficient chunking.

Naive attention materializes the (B, H, S, S) logits tensor — at the assigned
shapes (e.g. train_4k: B=256, S=4096; prefill_32k: S=32768) that is TBs of
HBM, so the *default* lowering path is a FlashAttention-style query-chunk scan
(Rabe & Staats, arXiv:2112.05682): O(S * chunk) live memory, with
``jax.remat`` on the chunk body so the backward pass recomputes chunk logits
instead of saving them.  The Pallas ``flash_attention`` kernel in
``repro/kernels`` is the TPU-native realization of the same schedule; this
module is the partitioner-friendly jnp form used for lowering/dry-run.

GQA is computed grouped — queries reshaped to (B, S, KV, G, hd) — so repeated
KV heads are never materialized.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.serve import tracing

NEG_INF = -1e30


def _band_mask(qpos, kpos, *, causal: bool, window: Optional[int],
               kv_len=None):
    """(Sq, Sk) bool mask — or (B, Sq, Sk) when ``kv_len`` is per-row (B,).

    qpos/kpos are int32 position vectors; a vector ``kv_len`` is the
    continuous-batching case where every batch row is a slot at its own
    sequence length.
    """
    m = jnp.ones((qpos.shape[-1], kpos.shape[-1]), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        m &= kpos[None, :] > (qpos[:, None] - window)
    if kv_len is not None:
        kvl = jnp.asarray(kv_len)
        if kvl.ndim == 0:
            m &= kpos[None, :] < kvl
        else:
            m = m[None] & (kpos[None, None, :] < kvl[:, None, None])
    return m


def _sdpa(q, k, v, mask, scale, logits_dtype=jnp.float32):
    """q: (B,Sq,KV,G,hd)  k,v: (B,Sk,KV,hd)  mask: (Sq,Sk) or (B,Sq,Sk).

    ``logits_dtype=bf16`` halves the S x S intermediate chain (max-shifted
    exp stays well-conditioned in bf16) — the jnp-path approximation of
    what the Pallas flash kernel gets for free by keeping logits in VMEM.
    """
    logits = jnp.einsum(
        "bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask.ndim == 2:
        mask = mask[None, None, None]
    else:
        mask = mask[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    if logits_dtype != jnp.float32:
        m = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.exp((logits - m).astype(logits_dtype))
        denom = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
        probs = p.astype(jnp.float32) / denom
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskh->bqkgh", probs.astype(v.dtype), v
    )
    return out


def attention(q, k, v, *, causal=True, window=None, q_offset=0,
              kv_len=None, scale=None, chunk_q=512, unroll=False,
              logits_dtype=jnp.float32, prefix_chunks=False):
    """Grouped-query attention.

    q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd_k/hd_v); returns (B, Sq, H, hd_v).
    ``q_offset``  — absolute position of q[0] (prefill chunking / decode).
    ``kv_len``    — valid prefix length of k/v (padded caches), traced scalar ok.
    ``prefix_chunks`` — causal self-attention only: unroll the query-chunk
    loop in python so chunk i attends a *static KV prefix* [0, (i+1)*chunk)
    instead of the full masked S — cuts the ~2x causal masked-compute waste
    of the scan path at the cost of O(nc) HLO size (§Perf optimization).
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if scale is None:
        scale = hd ** -0.5
    qg = q.reshape(B, Sq, KV, G, hd)

    Sk = k.shape[1]
    kpos = jnp.arange(Sk, dtype=jnp.int32)

    if Sq <= chunk_q:
        qpos = q_offset + jnp.arange(Sq, dtype=jnp.int32)
        mask = _band_mask(qpos, kpos, causal=causal, window=window,
                          kv_len=kv_len)
        out = _sdpa(qg, k, v, mask, scale, logits_dtype)
        if kv_len is not None and jnp.ndim(kv_len) == 1:
            # rows with kv_len == 0 (idle/finished slots in the macro-step
            # decode loop) have every key masked; the softmax degenerates to
            # uniform garbage, so pin them to the Pallas decode kernel's
            # semantics: exact zeros.
            out = jnp.where(
                (jnp.asarray(kv_len) > 0)[:, None, None, None, None], out, 0)
        return out.reshape(B, Sq, H, v.shape[-1])

    if Sq % chunk_q:  # ragged tail (e.g. MTP's S-1 stream): pad + slice
        pad = chunk_q - Sq % chunk_q
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        out = attention(qp, k, v, causal=causal, window=window,
                        q_offset=q_offset, kv_len=kv_len, scale=scale,
                        chunk_q=chunk_q, unroll=unroll,
                        logits_dtype=logits_dtype)
        return out[:, :Sq]
    nc = Sq // chunk_q
    qc = qg.reshape(B, nc, chunk_q, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)

    if (prefix_chunks and causal and window is None and kv_len is None
            and Sq == Sk and q_offset == 0):
        sdpa = jax.remat(_sdpa, prevent_cse=False,
                         static_argnums=(4, 5))
        outs = []
        for ci in range(nc):
            hi = (ci + 1) * chunk_q
            qpos = ci * chunk_q + jnp.arange(chunk_q, dtype=jnp.int32)
            kpos_c = jnp.arange(hi, dtype=jnp.int32)
            mask = _band_mask(qpos, kpos_c, causal=True, window=None)
            outs.append(sdpa(qc[ci], k[:, :hi], v[:, :hi], mask, scale,
                             logits_dtype))
        out = jnp.stack(outs, 0)
        return out.transpose(1, 0, 2, 3, 4, 5).reshape(
            B, Sq, H, v.shape[-1])

    if window is not None:
        # local attention: each chunk only needs a static (window + chunk_q)
        # KV slice — O(S * window) total work instead of O(S^2).
        # look-back windows are causal by construction (griffin/gemma-style);
        # a non-causal window would need forward KV context the slice
        # doesn't cover.
        assert causal, "windowed attention requires causal=True"
        span = window + chunk_q
        pad = span  # left-pad so every dynamic_slice start is in range
        # ...and right-pad up to the padded query length: the ragged-tail
        # q padding can push the last chunk's slice past the true KV
        # length, and a clamped dynamic_slice start would silently
        # mislabel that chunk's kpos (out-of-range positions are masked
        # below instead)
        Sk_data = k.shape[1]
        right = max(0, nc * chunk_q - Sk_data)
        kp = jnp.pad(k, ((0, 0), (pad, right), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (pad, right), (0, 0), (0, 0)))

        def chunk_body(_, ci):
            qi = qc[ci]
            start = ci * chunk_q + pad - window
            ks = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
            qpos = q_offset + ci * chunk_q + jnp.arange(chunk_q,
                                                        dtype=jnp.int32)
            kpos_c = start - pad + jnp.arange(span, dtype=jnp.int32)
            mask = _band_mask(qpos, kpos_c, causal=causal, window=window,
                              kv_len=kv_len) \
                & ((kpos_c >= 0) & (kpos_c < Sk_data))[None, :]
            return None, _sdpa(qi, ks, vs, mask, scale, logits_dtype)

        body = jax.remat(chunk_body, prevent_cse=False)
        _, outs = jax.lax.scan(body, None, jnp.arange(nc), unroll=unroll)
    else:
        def chunk_body(_, ci):
            qi = qc[ci]
            qpos = q_offset + ci * chunk_q + jnp.arange(chunk_q,
                                                        dtype=jnp.int32)
            mask = _band_mask(qpos, kpos, causal=causal, window=None,
                              kv_len=kv_len)
            return None, _sdpa(qi, k, v, mask, scale, logits_dtype)

        body = jax.remat(chunk_body, prevent_cse=False)
        _, outs = jax.lax.scan(body, None, jnp.arange(nc), unroll=unroll)

    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, v.shape[-1])
    return out


def ring_positions_rows(cur_len, ring):
    """Absolute position stored in each ring-buffer cache slot, PER ROW.

    cur_len: (B,) int32 — number of positions written so far in each row
    (the ring invariant: slot ``s`` holds the largest position ``p <
    cur_len`` with ``p % ring == s``).  Returns (B, ring) int32 absolute
    positions, -1 for slots never written.  The scalar form lives in
    ``transformer._ring_positions``; this is its continuous-batching
    counterpart where every batch row is at its own length.
    """
    slot = jnp.arange(ring, dtype=jnp.int32)[None]
    cur = cur_len[:, None]
    wrap = (cur - 1) // ring
    base = wrap * ring + slot
    pos = jnp.where(base < cur, base, base - ring)
    return jnp.where(pos >= 0, pos, -1)


def ring_fill_rows(x, plens, ring, dtype):
    """Fill a ring-buffer cache from a bucket-padded prefill, PER ROW.

    x: (B, S, ...) per-position values (e.g. K or V) of a tail-padded
    prompt batch; plens: (B,) true prompt lengths.  Ring slot ``s`` of row
    ``b`` gets the value at the largest real position ``p < plens[b]``
    with ``p % ring == s`` (a gather — wrapped positions never race a
    scatter), 0 where never written.  Returns (B, ring, ...) in ``dtype``.
    """
    kpos = ring_positions_rows(plens, ring)  # (B, ring)
    shape = kpos.shape + (1,) * (x.ndim - 2)
    take = jnp.clip(kpos, 0, x.shape[1] - 1).reshape(shape)
    written = (kpos >= 0).reshape(shape)
    return jnp.where(written, jnp.take_along_axis(x, take, axis=1),
                     0).astype(dtype)


def ring_slot_attend(q, ck, cv, slot_positions, *, window, scale=None,
                     done=None):
    """One-token attention over a ring-buffer window cache at per-row slots.

    q: (B, 1, H, hd); ck/cv: (B, ring, KV, hd) ring caches whose row ``b``
    already contains this step's K/V written at ``slot_positions[b] %
    ring``; slot_positions: (B,) — each row's current length (== the
    query's absolute position).  Masking is by ABSOLUTE position
    reconstructed from the ring invariant: a slot is attendable iff its
    position is in ``(qpos - window, qpos]`` and was ever written.  Rows
    flagged ``done`` attend nothing and return exact zeros (the idle-row
    semantics of the full-cache slot path and the Pallas decode kernel).
    """
    B, Sq, H, hd = q.shape
    KV = ck.shape[2]
    ring = ck.shape[1]
    if scale is None:
        scale = hd ** -0.5
    kpos = ring_positions_rows(slot_positions + 1, ring)  # (B, ring)
    qpos = slot_positions[:, None]
    mask = (kpos <= qpos) & (kpos > qpos - window) & (kpos >= 0)
    if done is not None:
        mask &= ~done[:, None]
    qg = q.reshape(B, Sq, KV, H // KV, hd)
    out = _sdpa(qg, ck.astype(q.dtype), cv.astype(q.dtype),
                mask[:, None, :], scale)
    if done is not None:
        out = jnp.where(done[:, None, None, None, None], 0.0, out)
    return out.reshape(B, Sq, H, cv.shape[-1])


def ring_slot_update_attend(q, cache, k, v, slot_positions, *, window,
                            done=None, scale=None, kernel=None):
    """One slot-decode step over a ring-buffer window cache: write each
    row's K/V at its own ring slot (``pos % ring``), freeze ``done`` rows
    to their old bytes, and attend by absolute position.

    The single authoritative implementation of the exactness-critical
    write/freeze/attend ordering, shared by the transformer window path
    and griffin's local-attention blocks.  cache: {"k": (B, ring, KV, hd),
    "v": ...}; k/v: (B, 1, KV, hd) this step's projections; the ring
    modulus is the cache length (>= window once the pool is padded, or
    shorter never-wrapping caches when max_len < window); ``window`` sets
    the attention band.  ``kernel`` selects the attend backend: None runs
    the jnp ``ring_slot_attend``, otherwise the Pallas
    ``ring_decode_attention`` kernel in that mode (auto / interpret /
    reference) reads the pool layout directly.  Returns
    (out (B, 1, H, hd_v), new_cache).
    """
    from repro.models.common import freeze_rows

    ring = cache["k"].shape[1]
    with tracing.scope("attn.kv_write"):
        b_idx = jnp.arange(k.shape[0])
        slot_idx = slot_positions % ring
        ck = cache["k"].at[b_idx, slot_idx].set(
            k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[b_idx, slot_idx].set(
            v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}
        if done is not None:
            # done rows' frozen (token, position) re-store identical bytes
            # anyway; the explicit freeze makes the no-op unconditional
            new_cache = freeze_rows(cache, new_cache, done)
    with tracing.scope("attn.core"):
        if kernel is not None:
            assert scale is None, "the ring kernel fixes scale at hd**-0.5"
            from repro.kernels import ops
            out = ops.ring_decode_attention(
                q[:, 0], new_cache["k"], new_cache["v"], slot_positions,
                window=window, done=done, mode=kernel)[:, None]
        else:
            out = ring_slot_attend(
                q, new_cache["k"].astype(q.dtype),
                new_cache["v"].astype(q.dtype), slot_positions,
                window=window, scale=scale, done=done)
    return out, new_cache


def paged_gather(arena, bt):
    """Materialize a slot's dense cache view from a page arena.

    arena: (n_pages, page, ...) shared pages; bt: (B, nblk) int32 block
    table (page ids; ``n_pages`` is the OOB sentinel for never-allocated
    blocks).  Sentinels are CLAMPED to the last page — the garbage rows
    that produces are finite bytes at positions every caller masks away
    (per-row ``kv_len``, ring validity, or the verify band), so their
    softmax weight underflows to exactly 0.0.  Returns (B, nblk * page,
    ...) in the dense pool layout.
    """
    n_pages = arena.shape[0]
    g = arena[jnp.minimum(bt, n_pages - 1)]  # (B, nblk, page, ...)
    return g.reshape((bt.shape[0], -1) + arena.shape[2:])


def paged_ring_slot_update_attend(q, cache, k, v, slot_positions, *,
                                  window, done=None, scale=None,
                                  kernel=None):
    """``ring_slot_update_attend`` over a PAGED ring cache.

    cache: {"k": (n_pages, page, KV, hd), "v": ..., "bt": (B, nblk)} —
    the ring modulus is the logical length ``nblk * page`` and row ``b``'s
    ring slot ``s`` lives at ``arena[bt[b, s // page], s % page]``.  The
    write resolves its page through the block table; ``done`` rows (and
    rows whose block was never allocated) redirect to the page sentinel,
    where the scatter is dropped — the paged realization of the dense
    path's freeze-is-a-no-op-restore.  The attend runs either on a
    gathered dense view through the exactness-proven ``ring_slot_attend``
    (jnp) or through the paged Pallas kernel (``kernel`` mode string).
    """
    bt = cache["bt"]
    n_pages, page = cache["k"].shape[:2]
    ring = bt.shape[1] * page
    with tracing.scope("attn.kv_write"):
        sidx = slot_positions % ring
        pid = jnp.take_along_axis(bt, (sidx // page)[:, None], axis=1)[:, 0]
        if done is not None:
            pid = jnp.where(done, n_pages, pid)
        off = sidx % page
        ck = cache["k"].at[pid, off].set(k[:, 0].astype(cache["k"].dtype),
                                         mode="drop")
        cv = cache["v"].at[pid, off].set(v[:, 0].astype(cache["v"].dtype),
                                         mode="drop")
    new_cache = {"k": ck, "v": cv, "bt": bt}
    with tracing.scope("attn.core"):
        if kernel is not None:
            assert scale is None, "the ring kernel fixes scale at hd**-0.5"
            from repro.kernels import ops
            out = ops.paged_ring_decode_attention(
                q[:, 0], ck, cv, bt, slot_positions, window=window,
                done=done, mode=kernel)[:, None]
        else:
            out = ring_slot_attend(q, paged_gather(ck, bt).astype(q.dtype),
                                   paged_gather(cv, bt).astype(q.dtype),
                                   slot_positions, window=window,
                                   scale=scale, done=done)
    return out, new_cache


def paged_ring_restore_sites(bt, positions, n_feed, chunk_len, page,
                             n_pages):
    """Scatter sites for the paged speculative ring ROLLBACK.

    The verify scan already wrote the whole chunk into the paged ring
    through the block table; commit must re-store the PRE-chunk bytes at
    every rejected write site (``j >= n_feed[b]``).  Returns
    (pid_restore, pid_read, off), each (B, chunk): ``pid_read`` is the
    clamped physical page to gather old bytes from, ``pid_restore``
    redirects accepted sites (and never-allocated blocks) to the page
    sentinel ``n_pages`` so their scatter drops, ``off`` is the in-page
    offset.  Requires ``chunk_len <= ring`` (the speculative pair probe
    enforces ``d + 1 <= window``) so no ring slot is written twice within
    one chunk.
    """
    ring = bt.shape[1] * page
    j = jnp.arange(chunk_len, dtype=positions.dtype)
    sidx = (positions[:, None] + j[None]) % ring  # (B, chunk)
    pid = jnp.take_along_axis(bt, sidx // page, axis=1)
    rejected = j[None] >= n_feed[:, None]
    pid_restore = jnp.where(rejected, pid, n_pages)
    pid_read = jnp.minimum(pid, n_pages - 1)
    return pid_restore, pid_read, sidx % page


def chunk_verify_kpos(offsets, cache_len, S, *, ring: bool):
    """Absolute key positions of [cache ‖ chunk] for the speculative
    verify: (B, cache_len + S) int32, -1 for unattendable cache entries.

    ``offsets`` (B,) is each row's chunk start (== its committed length):
    ring caches reconstruct per-slot positions from the ring invariant at
    that length; full-layout caches are valid on ``[0, offsets)`` and the
    tail (stale bytes of a longer previous tenant, or positions the row
    has not reached) is masked out.  Chunk key ``i`` sits at absolute
    position ``offsets + i``.
    """
    B = offsets.shape[0]
    if ring:
        kpos_cache = ring_positions_rows(offsets, cache_len)
    else:
        kpos_cache = jnp.broadcast_to(
            jnp.arange(cache_len, dtype=jnp.int32)[None], (B, cache_len))
        kpos_cache = jnp.where(kpos_cache < offsets[:, None], kpos_cache, -1)
    kpos_chunk = offsets[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    return jnp.concatenate([kpos_cache, kpos_chunk], axis=1)


def chunk_verify_mask(offsets, kpos, S, *, window=None, done=None):
    """(B, S, Sk) mask for the speculative verify chunk: query ``j`` (at
    absolute position ``offsets + j``) attends keys whose absolute
    position is in ``(qpos - window, qpos]`` and was ever written; rows
    flagged ``done`` attend nothing (their output is pinned to zeros by
    the caller, matching the idle-row slot semantics)."""
    qpos = offsets[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    m = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= qpos[:, :, None])
    if window is not None:
        m &= kpos[:, None, :] > qpos[:, :, None] - window
    if done is not None:
        m &= ~done[:, None, None]
    return m


def chunk_verify_attend(q, ck, cv, k, v, offsets, *, ring: bool,
                        window=None, done=None, scale=None,
                        logits_dtype=jnp.float32):
    """Speculative-verify attention: S chunk queries per row over
    [cache ‖ in-flight chunk], each row's chunk starting at its own
    absolute offset, WITHOUT writing the cache.

    q: (B, S, H, hd); ck/cv: (B, Sc, KV, hd) read-only cache (full-layout
    prefix or ring buffer); k/v: (B, S, KV, hd) the chunk's own K/V;
    offsets: (B,) committed length per row.  The cache stays untouched —
    ``commit_slots`` later scatters only the *accepted* chunk prefix, so
    speculative rollback is "never wrote it" rather than "undo it".
    Returns (B, S, H, hd_v); ``done`` rows return exact zeros.
    """
    B, S, H, hd = q.shape
    KV = ck.shape[2]
    if scale is None:
        scale = hd ** -0.5
    kpos = chunk_verify_kpos(offsets, ck.shape[1], S, ring=ring)
    mask = chunk_verify_mask(offsets, kpos, S, window=window, done=done)
    k_all = jnp.concatenate([ck.astype(q.dtype), k], axis=1)
    v_all = jnp.concatenate([cv.astype(q.dtype), v], axis=1)
    qg = q.reshape(B, S, KV, H // KV, hd)
    out = _sdpa(qg, k_all, v_all, mask, scale, logits_dtype)
    if done is not None:
        out = jnp.where(done[:, None, None, None, None], 0.0, out)
    return out.reshape(B, S, H, v_all.shape[-1])


def reference_attention(q, k, v, *, causal=True, window=None, kv_len=None,
                        scale=None):
    """Tiny-oracle full attention (tests only — materializes S×S)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    if scale is None:
        scale = hd ** -0.5
    qg = q.reshape(B, Sq, KV, H // KV, hd)
    qpos = jnp.arange(Sq, dtype=jnp.int32)
    kpos = jnp.arange(k.shape[1], dtype=jnp.int32)
    mask = _band_mask(qpos, kpos, causal=causal, window=window, kv_len=kv_len)
    out = _sdpa(qg, k, v, mask, scale)
    return out.reshape(B, Sq, H, v.shape[-1])
