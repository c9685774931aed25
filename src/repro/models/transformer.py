"""Unified transformer family (decoder LM / encoder / MoE / MLA / VLM).

One functional implementation covers:
  * dense decoder LMs       (qwen1.5, qwen3, stablelm, yi, GPT)
  * encoder-only            (hubert, BERT)          — ``causal=False``
  * MoE decoders            (phi3.5-moe)            — GSPMD capacity dispatch
  * MLA + MoE + MTP         (deepseek-v3)           — latent attention
  * VLM backbones           (qwen2-vl)              — M-RoPE, stub frontend

Parameters are plain dicts; per-layer weights are stacked on a leading L axis
and the stack runs under ``jax.lax.scan`` (O(1) HLO size for 61/80-layer
models — essential for the 512-device dry-run compile times).  Heterogeneous
stacks (DeepSeek's 3 dense + 58 MoE layers) are two scans over two stacked
groups.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import annotate
from repro.models import attention as attn_lib
from repro.models import ffn as ffn_lib
from repro.models import moe as moe_lib
from repro.models.common import (
    apply_norm,
    init_norm,
    keygen,
    pad_cache_len,
    rms_norm,
    trunc_normal,
)
from repro.models.rope import apply_mrope, apply_rope
from repro.serve import tracing


# =============================================================== param init
def _attn_init(keys, cfg, layers, dtype, std):
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def shp(*s):
        return (layers, *s)

    if cfg.mla:
        p = {
            "w_dq": trunc_normal(next(keys), shp(D, cfg.q_lora_rank), std, dtype),
            "q_norm": jnp.ones(shp(cfg.q_lora_rank), dtype),
            "w_uq": trunc_normal(
                next(keys),
                shp(cfg.q_lora_rank, H * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
                std, dtype),
            "w_dkv": trunc_normal(next(keys), shp(D, cfg.kv_lora_rank), std, dtype),
            "kv_norm": jnp.ones(shp(cfg.kv_lora_rank), dtype),
            "w_kr": trunc_normal(next(keys), shp(D, cfg.qk_rope_dim), std, dtype),
            "w_uk": trunc_normal(
                next(keys), shp(cfg.kv_lora_rank, H * cfg.qk_nope_dim), std, dtype),
            "w_uv": trunc_normal(
                next(keys), shp(cfg.kv_lora_rank, H * cfg.v_head_dim), std, dtype),
            "wo": trunc_normal(next(keys), shp(H * cfg.v_head_dim, D), std, dtype),
        }
        return p

    p = {
        "wq": trunc_normal(next(keys), shp(D, H * hd), std, dtype),
        "wk": trunc_normal(next(keys), shp(D, KV * hd), std, dtype),
        "wv": trunc_normal(next(keys), shp(D, KV * hd), std, dtype),
        "wo": trunc_normal(next(keys), shp(H * hd, D), std, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(shp(H * hd), dtype)
        p["bk"] = jnp.zeros(shp(KV * hd), dtype)
        p["bv"] = jnp.zeros(shp(KV * hd), dtype)
    if cfg.attn_out_bias:
        p["bo"] = jnp.zeros(shp(D), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones(shp(hd), dtype)
        p["k_norm"] = jnp.ones(shp(hd), dtype)
    return p


def _block_group_init(keys, cfg, n, moe, dtype, std):
    """One stacked group of ``n`` blocks (dense mlp or moe)."""
    g = {
        "ln1": init_norm(cfg.norm, cfg.d_model, n, dtype),
        "ln2": init_norm(cfg.norm, cfg.d_model, n, dtype),
        "attn": _attn_init(keys, cfg, n, dtype, std),
    }
    if moe:
        g["moe"] = moe_lib.init_moe(keys, cfg, layers=n, dtype=dtype, std=std)
    else:
        g["mlp"] = ffn_lib.init_mlp(
            keys, cfg.d_model, cfg.d_ff, layers=n, act=cfg.act,
            bias=cfg.mlp_bias, dtype=dtype, std=std)
    return g


def init(rng, cfg) -> dict:
    keys = keygen(rng)
    dtype = jnp.dtype(cfg.param_dtype)
    std = 0.02
    params: dict[str, Any] = {}
    D = cfg.d_model

    if cfg.continuous_inputs:
        params["in_proj"] = trunc_normal(
            next(keys), (cfg.continuous_inputs, D), std, dtype)
    else:
        params["embed"] = trunc_normal(
            next(keys), (cfg.vocab_size, D), std, dtype)
    if cfg.learned_pos:
        params["pos_embed"] = trunc_normal(
            next(keys), (cfg.learned_pos, D), std, dtype)

    n_dense = cfg.moe_layer_start if cfg.moe else cfg.n_layers
    n_moe = cfg.n_layers - n_dense
    if n_dense:
        params["dense_blocks"] = _block_group_init(
            keys, cfg, n_dense, False, dtype, std)
    if n_moe:
        params["moe_blocks"] = _block_group_init(
            keys, cfg, n_moe, True, dtype, std)

    params["final_norm"] = init_norm(cfg.norm, D, None, dtype)
    if cfg.head == "lm" and not cfg.tie_embeddings:
        params["head"] = trunc_normal(
            next(keys), (D, cfg.vocab_size), std, dtype)
    elif cfg.head == "cls":
        params["cls_token"] = trunc_normal(next(keys), (D,), std, dtype)
        params["head"] = trunc_normal(
            next(keys), (D, cfg.n_classes), std, dtype)

    if cfg.mtp:
        params["mtp"] = {
            "proj": trunc_normal(next(keys), (2 * D, D), std, dtype),
            "norm_h": init_norm(cfg.norm, D, None, dtype),
            "norm_e": init_norm(cfg.norm, D, None, dtype),
            "block": _block_group_init(keys, cfg, 1, False, dtype, std),
        }
    return params


# ============================================================ forward pieces
def _split_heads(x, n, hd):
    B, S, _ = x.shape
    return x.reshape(B, S, n, hd)


def _slot_kv_len(slot_positions, slot_done):
    """Per-row valid cache length for the slot-decode path.

    Finished/idle rows (``slot_done``) get ``kv_len == 0`` — the same
    short-circuit the Pallas decode kernel takes for idle slots — so the
    macro-step's no-op steps skip their attention reads entirely.
    """
    kv = slot_positions + 1
    if slot_done is None:
        return kv
    return jnp.where(slot_done, 0, kv)


def _kernel_mode(cfg):
    """The slot-decode attention backend: None (pure jnp) or the mode
    string handed to ``kernels.ops`` (auto / interpret / reference)."""
    return None if cfg.decode_kernel == "jnp" else cfg.decode_kernel


def _flash_block(s):
    """Flash-attention block size for a prefill of length ``s``: the
    largest power-of-two divisor, capped at the kernel's native 128.
    None when the divisor is degenerate (< 8) — the tiny-grid launch
    overhead then exceeds the masked-compute tax the kernel avoids."""
    b = min(s & -s, 128)
    return b if b >= 8 else None


def _is_ring(cache_len, window):
    """A window cache whose length reaches the window is a wrapping ring
    (slot = pos % cache_len); a shorter one never wraps and uses the
    full-cache layout.  ``>=`` not ``==``: the pool pads the cache axis to
    a kernel block multiple, which may push a ring past the window —
    absolute-position masking keeps a larger ring attend-identical.
    """
    return window is not None and cache_len >= window


def _cache_seq_len(cache):
    """Logical sequence length of a slot cache group: the cache axis for
    the dense layout, ``nblk * page`` through the block table for a paged
    group (whose arrays no longer carry a per-slot sequence axis)."""
    if "bt" in cache:
        return cache["bt"].shape[1] * cache["k"].shape[1]
    return cache["k"].shape[1]


def _paged_slot_forward(q, p, cfg, cache, k, v, slot_positions, slot_done,
                        window, cdt):
    """Slot-decode step over a PAGED cache group.

    cache: {"k"/"v": (n_pages, page, KV, hd), "bt": (B, nblk)}.  The
    write position resolves through the block table (logical block
    ``pos // page`` → physical page); ``done`` rows redirect to the page
    sentinel so their write is dropped — the paged freeze (a done row's
    table may be all-sentinel after eviction, so the dense path's
    "re-store identical bytes" trick is not available).  Reads either
    gather the arena back to the dense layout and reuse the
    exactness-proven jnp paths, or hand the arena + table to the paged
    Pallas kernels.
    """
    bt = cache["bt"]
    n_pages, page = cache["k"].shape[:2]
    S = bt.shape[1] * page
    if _is_ring(S, window):
        out, new_cache = attn_lib.paged_ring_slot_update_attend(
            q, cache, k, v, slot_positions, window=window, done=slot_done,
            kernel=_kernel_mode(cfg))
        return _attn_out(out, p, cfg, cdt), new_cache
    with tracing.scope("attn.kv_write"):
        blk = slot_positions // page
        pid = jnp.take_along_axis(bt, blk[:, None], axis=1)[:, 0]
        if slot_done is not None:
            pid = jnp.where(slot_done, n_pages, pid)
        off = slot_positions % page
        ck = cache["k"].at[pid, off].set(k[:, 0].astype(cache["k"].dtype),
                                         mode="drop")
        cv = cache["v"].at[pid, off].set(v[:, 0].astype(cache["v"].dtype),
                                         mode="drop")
    new_cache = {"k": ck, "v": cv, "bt": bt}
    kmode = _kernel_mode(cfg)
    with tracing.scope("attn.core"):
        kvl = _slot_kv_len(slot_positions, slot_done)
        if kmode is not None:
            from repro.kernels import ops
            out = ops.paged_slot_decode_attention(
                q[:, 0], ck, cv, bt, kvl, mode=kmode)[:, None]
        else:
            out = attn_lib.attention(
                q, attn_lib.paged_gather(ck, bt).astype(cdt),
                attn_lib.paged_gather(cv, bt).astype(cdt), causal=False,
                kv_len=kvl, chunk_q=cfg.attn_chunk,
                unroll=cfg.unroll_scans,
                logits_dtype=jnp.dtype(cfg.attn_logits_dtype))
    return _attn_out(out, p, cfg, cdt), new_cache


def _attn_forward(x, p, cfg, positions, *, cache=None, q_offset=0,
                  kv_len=None, window=None, slot_positions=None,
                  slot_done=None, plens=None, chunk_offsets=None):
    """Returns (out, new_cache_entry). x: (B,S,D).

    ``slot_positions`` (B,) switches to the continuous-batching decode path:
    S must be 1, each batch row is an independent cache slot at its own
    length, the new K/V is scattered to ``cache[b, slot_positions[b]]``
    (``% ring`` for ring-buffer window caches) and attention masks per-row
    to ``kv_len = slot_positions + 1`` — or 0 for rows flagged in
    ``slot_done`` (macro-step no-op rows).

    ``plens`` (B,) marks a continuous-batching ADMISSION prefill: prompts
    are tail-padded to a bucket length, and ring-buffer window caches must
    be filled per row from each prompt's true length (a full cache needs
    nothing — its pad-tail entries stay invisible behind the per-row
    ``kv_len`` mask until overwritten).

    ``chunk_offsets`` (B,) marks a SPECULATIVE VERIFY chunk: S tokens per
    row starting at each row's own committed length.  The cache is
    READ-ONLY — attention runs over [cache ‖ in-flight chunk] by absolute
    position and the chunk's K/V is returned as the pending entry for
    ``commit_slots``'s accept-masked scatter (rejected speculative
    positions are simply never written).
    """
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = x.dtype

    if cfg.mla:
        return _mla_forward(x, p, cfg, positions, cache=cache,
                            q_offset=q_offset, kv_len=kv_len,
                            slot_positions=slot_positions,
                            slot_done=slot_done,
                            chunk_offsets=chunk_offsets)

    with tracing.scope("attn.qkv"):
        q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(cdt))
        k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(cdt))
        v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(cdt))
        if cfg.qkv_bias:
            q = q + p["bq"].astype(cdt)
            k = k + p["bk"].astype(cdt)
            v = v + p["bv"].astype(cdt)
        q = _split_heads(q, H, hd)
        k = _split_heads(k, KV, hd)
        v = _split_heads(v, KV, hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"])
            k = rms_norm(k, p["k_norm"])
        if cfg.rope == "standard":
            q = apply_rope(q, positions, theta=cfg.rope_theta,
                           fraction=cfg.rope_fraction)
            k = apply_rope(k, positions, theta=cfg.rope_theta,
                           fraction=cfg.rope_fraction)
        elif cfg.rope == "mrope":
            q = apply_mrope(q, positions, theta=cfg.rope_theta,
                            sections=cfg.mrope_sections)
            k = apply_mrope(k, positions, theta=cfg.rope_theta,
                            sections=cfg.mrope_sections)
        q = annotate(q, ("batch", "seq", "heads", "head_dim"))
        k = annotate(k, ("batch", "seq", "kv_heads", "head_dim"))
        v = annotate(v, ("batch", "seq", "kv_heads", "head_dim"))

    if chunk_offsets is not None:
        # speculative verify: attend [cache ‖ chunk] read-only.  The
        # pending entry never carries a block table — commit resolves
        # pages through the live cache's own "bt".
        is_ring = _is_ring(_cache_seq_len(cache), window)
        kmode = _kernel_mode(cfg)
        with tracing.scope("attn.core"):
            if "bt" in cache and kmode is not None:
                from repro.kernels import ops
                out = ops.paged_chunk_verify_attention(
                    q, cache["k"], cache["v"], cache["bt"], k, v,
                    chunk_offsets, ring=is_ring, window=window,
                    done=slot_done, mode=kmode)
            elif "bt" in cache:
                out = attn_lib.chunk_verify_attend(
                    q, attn_lib.paged_gather(cache["k"], cache["bt"]),
                    attn_lib.paged_gather(cache["v"], cache["bt"]),
                    k, v, chunk_offsets, ring=is_ring, window=window,
                    done=slot_done,
                    logits_dtype=jnp.dtype(cfg.attn_logits_dtype))
            elif kmode is not None:
                from repro.kernels import ops
                out = ops.chunk_verify_attention(
                    q, cache["k"], cache["v"], k, v, chunk_offsets,
                    ring=is_ring, window=window, done=slot_done,
                    mode=kmode)
            else:
                out = attn_lib.chunk_verify_attend(
                    q, cache["k"], cache["v"], k, v, chunk_offsets,
                    ring=is_ring, window=window, done=slot_done,
                    logits_dtype=jnp.dtype(cfg.attn_logits_dtype))
        return _attn_out(out, p, cfg, cdt), {"k": k, "v": v}

    new_cache = None
    if slot_positions is not None:
        if "bt" in cache:
            return _paged_slot_forward(q, p, cfg, cache, k, v,
                                       slot_positions, slot_done, window,
                                       cdt)
        if _is_ring(cache["k"].shape[1], window):
            # Ring-buffer window cache: each row writes its own slot
            # ``pos % ring`` and attends by ABSOLUTE position
            # reconstructed from the ring invariant — the per-slot mirror
            # of ``_ring_window_attend``.  Done rows freeze (their frozen
            # token/position would re-store identical bytes anyway) and
            # attend nothing.  (A window cfg whose cache is shorter than
            # the window never wraps, so it falls through to the
            # full-cache scatter below: every cached position is inside
            # the band by construction.)
            out, new_cache = attn_lib.ring_slot_update_attend(
                q, cache, k, v, slot_positions, window=window,
                done=slot_done, kernel=_kernel_mode(cfg))
            return _attn_out(out, p, cfg, cdt), new_cache
        # Scatter this step's K/V to each row's own write position, then
        # attend with a per-row valid length.  Row arithmetic is identical
        # to the scalar-offset decode path (same einsums, same masked
        # softmax), which is what makes continuous batching token-exact
        # against sequential generate().  Done rows scatter too: their
        # token and position are frozen, so the write re-stores the exact
        # same K/V values (a bit-identical no-op) while kv_len == 0 keeps
        # the position unreadable.
        with tracing.scope("attn.kv_write"):
            b_idx = jnp.arange(B)
            ck = cache["k"].at[b_idx, slot_positions].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[b_idx, slot_positions].set(
                v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}
        kmode = _kernel_mode(cfg)
        with tracing.scope("attn.core"):
            kvl = _slot_kv_len(slot_positions, slot_done)
            if kmode is not None:
                from repro.kernels import ops
                out = ops.slot_decode_attention(
                    q[:, 0], ck, cv, kvl, mode=kmode)[:, None]
            else:
                out = attn_lib.attention(
                    q, ck.astype(cdt), cv.astype(cdt), causal=False,
                    kv_len=kvl, chunk_q=cfg.attn_chunk,
                    unroll=cfg.unroll_scans,
                    logits_dtype=jnp.dtype(cfg.attn_logits_dtype))
        return _attn_out(out, p, cfg, cdt), new_cache
    if cache is not None:
        # cache: {"k": (B, Smax, KV, hd), "v": ...} — window caches are ring
        # buffers of size ``window`` (slot = abs_pos % window).
        ck, cv = cache["k"], cache["v"]
        wsize = ck.shape[1]
        if _is_ring(wsize, window):
            # the ring modulus is the CACHE length (>= window once the
            # pool pads to a kernel block multiple), not the window
            with tracing.scope("attn.kv_write"):
                if plens is not None and S > 1:
                    # admission prefill of tail-padded prompts: fill each
                    # row's ring from its TRUE length
                    ck = attn_lib.ring_fill_rows(k, plens, wsize, ck.dtype)
                    cv = attn_lib.ring_fill_rows(v, plens, wsize, cv.dtype)
                else:
                    w_eff = min(S, wsize)
                    idx = (q_offset + S - w_eff + jnp.arange(w_eff)) % wsize
                    ck = ck.at[:, idx].set(k[:, -w_eff:].astype(ck.dtype))
                    cv = cv.at[:, idx].set(v[:, -w_eff:].astype(cv.dtype))
            new_cache = {"k": ck, "v": cv}
            with tracing.scope("attn.core"):
                if S > 1:
                    # prefill: window attention over the in-flight k/v
                    # directly
                    out = attn_lib.attention(
                        q, k, v, causal=cfg.causal, window=window,
                        q_offset=q_offset, chunk_q=cfg.attn_chunk,
                        unroll=cfg.unroll_scans)
                else:
                    kpos_abs = _ring_positions(q_offset + S, wsize)
                    out = _ring_window_attend(
                        q, ck.astype(cdt), cv.astype(cdt), kpos_abs,
                        q_offset, cfg)
            return _attn_out(out, p, cfg, cdt), new_cache
        with tracing.scope("attn.kv_write"):
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), q_offset, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), q_offset, axis=1)
        new_cache = {"k": ck, "v": cv}
        k, v = ck.astype(cdt), cv.astype(cdt)
        kv_len = q_offset + S
        kmode = _kernel_mode(cfg)
        if (S > 1 and kmode is not None and cfg.causal and window is None
                and isinstance(q_offset, int) and q_offset == 0):
            # Batched prefill admission on the kernel backend: causal
            # flash attention over exactly the S in-flight positions.
            # At q_offset 0, kv_len == S, so the jnp path below masks
            # nothing beyond the causal band — the flash kernel computes
            # the identical softmax.  K/V come back out of the cache
            # slice (not the raw in-flight tensors) so cache-dtype
            # rounding matches the jnp path bit-for-bit.  Tail-padded
            # rows still compute, but stay unread: admission gathers each
            # row's first token at plens-1, inside its true prompt.
            blk = _flash_block(S)
            if blk is not None:
                from repro.kernels import ops
                with tracing.scope("attn.core"):
                    of = ops.flash_attention(
                        q.transpose(0, 2, 1, 3),
                        k[:, :S].transpose(0, 2, 1, 3),
                        v[:, :S].transpose(0, 2, 1, 3),
                        causal=True, mode=kmode,
                        **({} if kmode == "reference"
                           else {"bq": blk, "bk": blk}))
                return (_attn_out(of.transpose(0, 2, 1, 3), p, cfg, cdt),
                        new_cache)

    with tracing.scope("attn.core"):
        out = attn_lib.attention(
            q, k, v, causal=cfg.causal, window=window, q_offset=q_offset,
            kv_len=kv_len, chunk_q=cfg.attn_chunk, unroll=cfg.unroll_scans,
            logits_dtype=jnp.dtype(cfg.attn_logits_dtype),
            prefix_chunks=cfg.attn_prefix_chunks)
    return _attn_out(out, p, cfg, cdt), new_cache


def _attn_out(out, p, cfg, cdt):
    B, S = out.shape[:2]
    with tracing.scope("attn.out"):
        out = out.reshape(B, S, -1)
        y = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(cdt))
        if cfg.attn_out_bias:
            y = y + p["bo"].astype(cdt)
    return y


def _ring_positions(cur_len, ring):
    """Absolute position stored in each ring-buffer slot; -1 if unwritten.
    ``ring`` is the cache length (the ring modulus), not the window."""
    slot = jnp.arange(ring)
    wrap = (cur_len - 1) // ring
    base = wrap * ring + slot
    pos = jnp.where(base < cur_len, base, base - ring)
    return jnp.where(pos >= 0, pos, -1)


def _ring_window_attend(q, ck, cv, kpos_abs, q_offset, cfg):
    """Decode/short-prefill attention over a ring-buffer window cache."""
    B, S, H, hd = q.shape
    KV = ck.shape[2]
    qg = q.reshape(B, S, KV, H // KV, hd)
    qpos = q_offset + jnp.arange(S)
    mask = (kpos_abs[None, :] <= qpos[:, None]) & \
           (kpos_abs[None, :] > qpos[:, None] - cfg.window) & \
           (kpos_abs[None, :] >= 0)
    out = attn_lib._sdpa(qg, ck.astype(q.dtype), cv.astype(q.dtype),
                         mask, cfg.head_dim ** -0.5)
    return out.reshape(B, S, H, hd)


def _mla_forward(x, p, cfg, positions, *, cache=None, q_offset=0, kv_len=None,
                 slot_positions=None, slot_done=None, chunk_offsets=None):
    """DeepSeek-V3 Multi-head Latent Attention (arXiv:2412.19437)."""
    B, S, D = x.shape
    cdt = x.dtype
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim

    with tracing.scope("attn.qkv"):
        cq = jnp.einsum("bsd,dr->bsr", x, p["w_dq"].astype(cdt))
        cq = rms_norm(cq, p["q_norm"])
        q = jnp.einsum("bsr,rh->bsh", cq, p["w_uq"].astype(cdt))
        q = q.reshape(B, S, H, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        q_rope = apply_rope(q_rope, positions, theta=cfg.rope_theta)

        ckv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(cdt))
        kr = jnp.einsum("bsd,dr->bsr", x, p["w_kr"].astype(cdt))
        kr = apply_rope(kr[:, :, None, :], positions,
                        theta=cfg.rope_theta)[:, :, 0]

    if chunk_offsets is not None:
        # speculative verify in the latent space: absorbed-weight
        # attention over [cached latents ‖ chunk latents] at per-row
        # offsets, cache read-only; the raw chunk latents are the pending
        # entry for ``commit_slots`` (never carrying a block table —
        # commit resolves pages through the live cache's own "bt")
        with tracing.scope("attn.core"):
            cache_view = cache
            if "bt" in cache:
                cc, cr = _mla_paged_gather(cache, cfg)
                cache_view = {"ckv": cc, "kr": cr}
            out = _mla_chunk_verify(q_nope, q_rope, cache_view, ckv, kr, p,
                                    cfg, chunk_offsets, slot_done)
        return _mla_out(out, p, cdt), {"ckv": ckv, "kr": kr}
    new_cache = None
    if slot_positions is not None:
        if "bt" in cache:
            # paged latent cache: the write resolves its page through the
            # block table (done rows redirect to the sentinel and drop —
            # the paged freeze), the absorbed-weight attention reads a
            # gathered dense view
            bt = cache["bt"]
            n_pages, page = cache["ckv"].shape[:2]
            with tracing.scope("attn.kv_write"):
                blk = slot_positions // page
                pid = jnp.take_along_axis(bt, blk[:, None], axis=1)[:, 0]
                if slot_done is not None:
                    pid = jnp.where(slot_done, n_pages, pid)
                off = slot_positions % page
                cc = cache["ckv"].at[pid, off].set(
                    ckv[:, 0].astype(cache["ckv"].dtype), mode="drop")
                cr = cache["kr"].at[pid, off].set(
                    kr[:, 0].astype(cache["kr"].dtype), mode="drop")
            new_cache = {"ckv": cc, "kr": cr, "bt": bt}
            with tracing.scope("attn.core"):
                gc, gr = _mla_paged_gather(new_cache, cfg)
                out = _mla_absorbed_decode(
                    q_nope, q_rope, gc.astype(cdt), gr.astype(cdt), p, cfg,
                    kv_len=_slot_kv_len(slot_positions, slot_done))
            return _mla_out(out, p, cdt), new_cache
        # continuous-batching decode: per-row latent-cache scatter + the
        # absorbed-weight attention with per-row valid lengths
        with tracing.scope("attn.kv_write"):
            b_idx = jnp.arange(B)
            cc = cache["ckv"].at[b_idx, slot_positions].set(
                ckv[:, 0].astype(cache["ckv"].dtype))
            cr = cache["kr"].at[b_idx, slot_positions].set(
                kr[:, 0].astype(cache["kr"].dtype))
        new_cache = {"ckv": cc, "kr": cr}
        with tracing.scope("attn.core"):
            out = _mla_absorbed_decode(
                q_nope, q_rope, cc.astype(cdt), cr.astype(cdt), p, cfg,
                kv_len=_slot_kv_len(slot_positions, slot_done))
        return _mla_out(out, p, cdt), new_cache
    if cache is not None:
        cc, cr = cache["ckv"], cache["kr"]
        with tracing.scope("attn.kv_write"):
            cc = jax.lax.dynamic_update_slice_in_dim(
                cc, ckv.astype(cc.dtype), q_offset, axis=1)
            cr = jax.lax.dynamic_update_slice_in_dim(
                cr, kr.astype(cr.dtype), q_offset, axis=1)
        new_cache = {"ckv": cc, "kr": cr}
        if S == 1:
            # Absorbed-weight MLA decode (DeepSeek-V3 §: W_uk folded into q,
            # W_uv applied after the latent attention) — attends directly in
            # the compressed kv_lora space, avoiding re-expanding K/V to
            # (B, S_cache, H, dn+dv) every step.
            with tracing.scope("attn.core"):
                out = _mla_absorbed_decode(
                    q_nope, q_rope, cc.astype(cdt), cr.astype(cdt), p, cfg,
                    kv_len=q_offset + 1)
            return _mla_out(out, p, cdt), new_cache
        ckv, kr = cc.astype(cdt), cr.astype(cdt)
        kv_len = q_offset + S

    with tracing.scope("attn.core"):
        ckv_n = rms_norm(ckv, p["kv_norm"])
        k_nope = jnp.einsum("bsr,rh->bsh", ckv_n, p["w_uk"].astype(cdt))
        k_nope = k_nope.reshape(B, -1, H, dn)
        v = jnp.einsum("bsr,rh->bsh", ckv_n, p["w_uv"].astype(cdt))
        v = v.reshape(B, -1, H, dv)

        qf = jnp.concatenate([q_nope, q_rope], -1)
        kf = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                      (*k_nope.shape[:3], dr))], -1)
        qf = annotate(qf, ("batch", "seq", "heads", "head_dim"))
        kf = annotate(kf, ("batch", "seq", "heads", "head_dim"))
        v = annotate(v, ("batch", "seq", "heads", "head_dim"))
        out = attn_lib.attention(
            qf, kf, v, causal=cfg.causal, q_offset=q_offset, kv_len=kv_len,
            scale=(dn + dr) ** -0.5, chunk_q=cfg.attn_chunk,
            unroll=cfg.unroll_scans,
            logits_dtype=jnp.dtype(cfg.attn_logits_dtype),
            prefix_chunks=cfg.attn_prefix_chunks)
        out = out.reshape(B, S, H * dv)
    return _mla_out(out, p, cdt), new_cache


def _mla_out(out, p, cdt):
    with tracing.scope("attn.out"):
        return jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(cdt))


def _mla_paged_gather(cache, cfg):
    """Dense (B, S, ·) views of a paged MLA latent group.  A kernel-mode
    config routes through ``kernels.ops.paged_latent_gather`` so the
    independently-derived reference gather oracles the arena layout (MLA
    has no Pallas decode kernel — the absorbed-weight path is jnp)."""
    kmode = _kernel_mode(cfg)
    if kmode is not None:
        from repro.kernels import ops
        return (ops.paged_latent_gather(cache["ckv"], cache["bt"],
                                        mode=kmode),
                ops.paged_latent_gather(cache["kr"], cache["bt"],
                                       mode=kmode))
    return (attn_lib.paged_gather(cache["ckv"], cache["bt"]),
            attn_lib.paged_gather(cache["kr"], cache["bt"]))


def _mla_absorbed_decode(q_nope, q_rope, ckv, kr, p, cfg, *, kv_len):
    """One-token MLA attention in the latent space.

    q_nope: (B,1,H,dn); q_rope: (B,1,H,dr); ckv: (B,Smax,R); kr: (B,Smax,dr).
    Returns (B, 1, H*dv).
    """
    B, _, H, dn = q_nope.shape
    R, dv = cfg.kv_lora_rank, cfg.v_head_dim
    ckv_n = rms_norm(ckv, p["kv_norm"])  # (B,S,R)
    w_uk = p["w_uk"].astype(q_nope.dtype).reshape(R, H, dn)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)  # (B,1,H,R)
    logits = jnp.einsum("bqhr,bsr->bhqs", q_lat, ckv_n,
                        preferred_element_type=jnp.float32)
    logits += jnp.einsum("bqhd,bsd->bhqs", q_rope, kr,
                         preferred_element_type=jnp.float32)
    logits *= (dn + cfg.qk_rope_dim) ** -0.5
    kvl = jnp.asarray(kv_len)
    if kvl.ndim == 0:
        mask = (jnp.arange(ckv.shape[1]) < kvl)[None, None, None]
    else:  # per-row lengths (continuous batching)
        mask = (jnp.arange(ckv.shape[1])[None] < kvl[:, None])[:, None, None]
    logits = jnp.where(mask, logits, attn_lib.NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(ckv.dtype)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", probs, ckv_n)  # (B,1,H,R)
    w_uv = p["w_uv"].astype(ckv.dtype).reshape(R, H, dv)
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv)
    if kvl.ndim == 1:
        # fully-masked rows (kv_len == 0: idle/finished slots) degenerate
        # to a uniform softmax over the cache — pin them to the exact
        # zeros the standard attention path and Pallas kernel return
        out = jnp.where((kvl > 0)[:, None, None, None], out, 0)
    return out.reshape(B, 1, H * dv)


def _mla_chunk_verify(q_nope, q_rope, cache, ckv, kr, p, cfg, offsets, done):
    """Speculative-verify MLA attention: S chunk queries per row over
    [cached latents ‖ this chunk's raw latents], cache read-only.

    q_nope: (B,S,H,dn); q_rope: (B,S,H,dr); cache: {"ckv": (B,Smax,R),
    "kr": (B,Smax,dr)}; ckv/kr: (B,S,·) the chunk's latents.  Returns
    (B, S, H*dv); ``done`` rows return exact zeros.
    """
    B, S, H, dn = q_nope.shape
    R, dv = cfg.kv_lora_rank, cfg.v_head_dim
    ckv_all = jnp.concatenate([cache["ckv"].astype(ckv.dtype), ckv], 1)
    kr_all = jnp.concatenate([cache["kr"].astype(kr.dtype), kr], 1)
    ckv_n = rms_norm(ckv_all, p["kv_norm"])
    w_uk = p["w_uk"].astype(q_nope.dtype).reshape(R, H, dn)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)
    logits = jnp.einsum("bqhr,bsr->bhqs", q_lat, ckv_n,
                        preferred_element_type=jnp.float32)
    logits += jnp.einsum("bqhd,bsd->bhqs", q_rope, kr_all,
                         preferred_element_type=jnp.float32)
    logits *= (dn + cfg.qk_rope_dim) ** -0.5
    kpos = attn_lib.chunk_verify_kpos(offsets, cache["ckv"].shape[1], S,
                                      ring=False)
    mask = attn_lib.chunk_verify_mask(offsets, kpos, S, done=done)
    logits = jnp.where(mask[:, None], logits, attn_lib.NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(ckv_all.dtype)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", probs, ckv_n)
    w_uv = p["w_uv"].astype(ckv_all.dtype).reshape(R, H, dv)
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv)
    if done is not None:
        out = jnp.where(done[:, None, None, None], 0.0, out)
    return out.reshape(B, S, H * dv)


def _block(x, bp, cfg, positions, *, moe, cache=None, q_offset=0,
           window=None, slot_positions=None, slot_done=None, plens=None,
           chunk_offsets=None):
    with tracing.scope("attn.qkv"):
        xn = apply_norm(x, bp["ln1"], cfg.norm)
    h, new_cache = _attn_forward(
        xn, bp["attn"], cfg, positions,
        cache=cache, q_offset=q_offset, window=window,
        slot_positions=slot_positions, slot_done=slot_done, plens=plens,
        chunk_offsets=chunk_offsets)
    with tracing.scope("attn.out"):
        x = x + h
    with tracing.scope("mlp"):
        hin = apply_norm(x, bp["ln2"], cfg.norm)
        if moe:
            h, aux = moe_lib.moe_mlp(hin, bp["moe"], cfg)
        else:
            h, aux = ffn_lib.mlp(hin, bp["mlp"], cfg.act), 0.0
        x = x + h
        x = annotate(x, ("batch", "seq", "embed"))
    return x, aux, new_cache


def _layer_block_table(bt, layer, n_pages, n_layers):
    """Layer ``layer``'s block table into the layer-flattened arena
    ``(n_layers * n_pages, page, ...)``: live entries shift onto that
    layer's pages; the sentinel ``n_pages`` (never-allocated blocks) maps
    to the flat arena's own sentinel ``n_layers * n_pages`` — a plain
    shift would land it on layer ``layer + 1``'s first page, and the ring
    path WRITES through sentinel entries (dropped only when out of
    range)."""
    return jnp.where(bt < n_pages, bt + layer * n_pages, n_layers * n_pages)


def _run_group(x, group, cfg, positions, *, moe, caches=None, q_offset=0,
               slot_positions=None, slot_done=None, plens=None,
               chunk_offsets=None):
    """Scan a stacked block group. caches: stacked (n, ...) or None.

    Slot decode over a paged k/v group carries the arenas whole through
    the scan, viewed as one layer-flattened arena ``(n * n_pages, ...)``,
    and scans only the block table: each layer scatters its row into the
    carried arena in place and reads its pages through a per-layer
    offset table (``_layer_block_table``).  Scanning the arenas as
    ``xs``/``ys`` would slice out and re-stack every layer's arena on
    every decode step instead.
    """
    arena = None
    if slot_positions is not None and caches is not None \
            and set(caches) == {"k", "v", "bt"}:
        n_layers, n_pages = caches["k"].shape[:2]
        arena = {lk: caches[lk].reshape((n_layers * n_pages,)
                                        + caches[lk].shape[2:])
                 for lk in ("k", "v")}

    def body(carry, xs):
        xc, aux_sum, arena_c = carry
        if caches is None:
            bp = xs
            xc, aux, _ = _block(xc, bp, cfg, positions, moe=moe,
                                q_offset=q_offset, window=cfg.window)
            return (xc, aux_sum + aux, arena_c), None
        if arena_c is None:
            bp, cache_l = xs
        else:
            bp, (layer, bt) = xs
            cache_l = {**arena_c, "bt": _layer_block_table(
                bt, layer, n_pages, n_layers)}
        xc, aux, nc = _block(xc, bp, cfg, positions, moe=moe, cache=cache_l,
                             q_offset=q_offset, window=cfg.window,
                             slot_positions=slot_positions,
                             slot_done=slot_done, plens=plens,
                             chunk_offsets=chunk_offsets)
        if arena_c is None:
            return (xc, aux_sum + aux, None), nc
        return (xc, aux_sum + aux, {lk: nc[lk] for lk in arena_c}), None

    if cfg.remat == "block":
        body = jax.remat(body, prevent_cse=False)
    elif cfg.remat == "dots":
        # save matmul outputs, recompute elementwise — trades HBM for a
        # ~2x cut of backward recompute traffic
        body = jax.remat(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if caches is None:
        xs = group
    elif arena is not None:
        xs = (group, (jnp.arange(n_layers, dtype=caches["bt"].dtype),
                      caches["bt"]))
    else:
        xs = (group, caches)
    (x, aux, arena), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), arena), xs,
        unroll=cfg.unroll_scans)
    if arena is not None:
        new_caches = {**caches, **{lk: a.reshape(caches[lk].shape)
                                   for lk, a in arena.items()}}
    return x, aux, new_caches


# ================================================================== forward
def embed_inputs(params, batch, cfg):
    with tracing.scope("embed"):
        cdt = jnp.dtype(cfg.compute_dtype)
        if cfg.continuous_inputs:
            x = jnp.einsum("bsi,id->bsd", batch["inputs"].astype(cdt),
                           params["in_proj"].astype(cdt))
        else:
            tokens = batch["tokens"]
            x = params["embed"].astype(cdt)[tokens]
        if cfg.scale_embeddings:
            x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
        if cfg.head == "cls":
            cls = jnp.broadcast_to(params["cls_token"].astype(cdt),
                                   (x.shape[0], 1, x.shape[-1]))
            x = jnp.concatenate([cls, x], axis=1)
        B, S = x.shape[:2]
        if cfg.learned_pos:
            pos = batch.get("positions")
            if pos is None or pos.ndim != 2:
                pos = jnp.arange(S, dtype=jnp.int32)[None, :]
            x = x + params["pos_embed"].astype(cdt)[pos]
        return annotate(x, ("batch", "seq", "embed"))


def _positions_from_batch(batch, B, S, cfg, q_offset=0):
    pos = batch.get("positions")
    if pos is not None:
        return pos
    p = q_offset + jnp.arange(S, dtype=jnp.int32)[None, :]
    p = jnp.broadcast_to(p, (B, S))
    if cfg.rope == "mrope":
        return jnp.broadcast_to(p[None], (3, B, S))
    return p


def forward(params, batch, cfg):
    """Full forward. batch: {"tokens": (B,S)} or {"inputs": (B,S,Din)}.

    Returns (logits, aux) where aux = {"moe_aux": scalar, "mtp_logits": ...}.
    """
    x = embed_inputs(params, batch, cfg)
    B, S = x.shape[:2]
    positions = _positions_from_batch(batch, B, S, cfg)
    aux_total = 0.0
    if "dense_blocks" in params:
        x, aux, _ = _run_group(x, params["dense_blocks"], cfg, positions,
                               moe=False)
        aux_total += aux
    if "moe_blocks" in params:
        x, aux, _ = _run_group(x, params["moe_blocks"], cfg, positions,
                               moe=True)
        aux_total += aux
    x = _final_norm(params, x, cfg)
    aux = {"moe_aux": aux_total}

    if cfg.mtp and "mtp" in params and not cfg.continuous_inputs:
        aux["mtp_logits"] = _mtp_forward(params, x, batch, positions, cfg)

    logits = _head(params, x, cfg)
    return logits, aux


def _final_norm(params, x, cfg):
    with tracing.scope("head"):
        return apply_norm(x, params["final_norm"], cfg.norm)


def _head(params, x, cfg):
    cdt = x.dtype
    if cfg.head == "none":
        return x
    with tracing.scope("head"):
        if cfg.head == "cls":
            return jnp.einsum("bd,dc->bc", x[:, 0],
                              params["head"].astype(cdt))
        w = params["embed"].T if cfg.tie_embeddings else params["head"]
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(cdt))
        return annotate(logits, ("batch", "seq", "vocab"))


def _mtp_forward(params, h, batch, positions, cfg):
    """DeepSeek-V3 depth-1 multi-token prediction head (predicts t+2)."""
    mp = params["mtp"]
    cdt = h.dtype
    emb = params["embed"].astype(cdt)[batch["tokens"]]
    hh = apply_norm(h[:, :-1], mp["norm_h"], cfg.norm)
    ee = apply_norm(emb[:, 1:], mp["norm_e"], cfg.norm)
    z = jnp.einsum("bsd,dD->bsD", jnp.concatenate([hh, ee], -1),
                   mp["proj"].astype(cdt))
    pos = positions[:, :-1] if positions.ndim == 2 else positions[..., :-1]
    z, _, _ = _run_group(z, mp["block"], cfg, pos, moe=False)
    return _head(params, z, cfg)


# ============================================================== serve (KV)
def init_cache(cfg, batch_size, max_len, dtype=None):
    """Stacked per-group caches.

    The cache axis is padded to a kernel block multiple
    (``common.pad_cache_len`` — the TPU-layout pool contract), so the
    Pallas decode kernels always find a valid cache-axis block even for
    prime/odd ``max_len``.  The padding is invisible: full layouts mask
    it behind per-row ``kv_len``, ring layouts take the padded length as
    their ring modulus.
    """
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    n_dense = cfg.moe_layer_start if cfg.moe else cfg.n_layers
    n_moe = cfg.n_layers - n_dense
    wlen = min(max_len, cfg.window) if cfg.window else max_len
    wlen = pad_cache_len(wlen)
    flen = pad_cache_len(max_len)

    def one(n):
        if cfg.mla:
            return {
                "ckv": jnp.zeros((n, batch_size, flen, cfg.kv_lora_rank),
                                 dtype),
                "kr": jnp.zeros((n, batch_size, flen, cfg.qk_rope_dim),
                                dtype),
            }
        return {
            "k": jnp.zeros((n, batch_size, wlen, cfg.n_kv_heads,
                            cfg.head_dim), dtype),
            "v": jnp.zeros((n, batch_size, wlen, cfg.n_kv_heads,
                            cfg.head_dim), dtype),
        }

    cache = {}
    if n_dense:
        cache["dense"] = one(n_dense)
    if n_moe:
        cache["moe"] = one(n_moe)
    return cache


def _forward_cached(params, batch, cfg, cache, q_offset, plens=None):
    x = embed_inputs(params, batch, cfg)
    B, S = x.shape[:2]
    positions = _positions_from_batch(batch, B, S, cfg, q_offset=q_offset)
    new_cache = {}
    if "dense_blocks" in params:
        x, _, nc = _run_group(x, params["dense_blocks"], cfg, positions,
                              moe=False, caches=cache["dense"],
                              q_offset=q_offset, plens=plens)
        new_cache["dense"] = nc
    if "moe_blocks" in params:
        x, _, nc = _run_group(x, params["moe_blocks"], cfg, positions,
                              moe=True, caches=cache["moe"],
                              q_offset=q_offset, plens=plens)
        new_cache["moe"] = nc
    x = _final_norm(params, x, cfg)
    return _head(params, x, cfg), new_cache


def prefill(params, batch, cfg, cache):
    """Run the prompt through the model, filling the cache.

    Returns (last-position logits (B, V), cache).
    """
    logits, cache = _forward_cached(params, batch, cfg, cache, q_offset=0)
    return logits[:, -1], cache


def decode_step(params, tokens, pos, cache, cfg):
    """One decode step. tokens: (B,) int32; pos: scalar int32 (current len).

    Returns (logits (B, V), new_cache).
    """
    batch = {"tokens": tokens[:, None]}
    if cfg.learned_pos:
        # absolute learned positions must track the decode offset (rope
        # models get this through q_offset already)
        batch["positions"] = jnp.full((tokens.shape[0], 1), pos, jnp.int32)
    logits, cache = _forward_cached(params, batch, cfg, cache, q_offset=pos)
    return logits[:, -1], cache


def prefill_full(params, batch, cfg, cache):
    """Prefill returning logits at EVERY prompt position: (B, S, V).

    The continuous-batching engine pads prompts to a bucket length to bound
    prefill recompiles; it reads the logits at each request's true last
    prompt token, so it needs the whole sequence of logits.  An optional
    ``batch["plens"]`` (B,) carries each row's TRUE prompt length — ignored
    by full caches (pad-tail entries hide behind the per-row ``kv_len``
    mask) but required to fill ring-buffer window caches per row.
    """
    plens = batch.get("plens")
    batch = {k: v for k, v in batch.items() if k != "plens"}
    return _forward_cached(params, batch, cfg, cache, q_offset=0,
                           plens=plens)


def _forward_cached_slots(params, batch, cfg, cache, slot_positions,
                          slot_done=None):
    x = embed_inputs(params, batch, cfg)
    B, S = x.shape[:2]
    positions = slot_positions[:, None]
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(positions[None], (3, B, S))
    new_cache = {}
    if "dense_blocks" in params:
        x, _, nc = _run_group(x, params["dense_blocks"], cfg, positions,
                              moe=False, caches=cache["dense"],
                              slot_positions=slot_positions,
                              slot_done=slot_done)
        new_cache["dense"] = nc
    if "moe_blocks" in params:
        x, _, nc = _run_group(x, params["moe_blocks"], cfg, positions,
                              moe=True, caches=cache["moe"],
                              slot_positions=slot_positions,
                              slot_done=slot_done)
        new_cache["moe"] = nc
    x = _final_norm(params, x, cfg)
    return _head(params, x, cfg), new_cache


def decode_step_slots(params, tokens, positions, cache, cfg, done=None):
    """Continuous-batching decode: one token per slot at per-slot lengths.

    tokens: (B,) int32 — the last generated token of each slot;
    positions: (B,) int32 — each slot's current length (the write position
    of this step's K/V);
    done: optional (B,) bool — finished/idle rows; they attend with
    ``kv_len == 0`` (the idle-row short-circuit) and their cache write is a
    bit-identical re-store, so the macro-step scan can keep running them as
    no-ops.  Returns (logits (B, V), new_cache).
    """
    batch = {"tokens": tokens[:, None], "positions": positions[:, None]}
    logits, cache = _forward_cached_slots(params, batch, cfg, cache,
                                          positions, slot_done=done)
    return logits[:, -1], cache


def verify_step_slots(params, tokens, positions, cache, cfg, done=None):
    """Speculative verify: feed an (B, S) token chunk per slot, each row
    starting at its own committed length ``positions[b]``, in ONE batched
    forward — the parallel target pass of speculative decoding.

    Returns (logits (B, S, V), pending): ``logits[:, j]`` is the
    distribution after each row consumed its chunk prefix ``[:j + 1]``.
    The slot cache is READ-ONLY here; ``pending`` carries the chunk's
    per-layer K/V (latents for MLA) so ``commit_slots`` can scatter
    exactly the accepted prefix afterwards — speculative rollback is
    "never wrote it", not "undo it", for every KV layout including
    ring-buffer windows.  ``done`` rows attend nothing and return
    garbage logits the caller must mask.
    """
    B, S = tokens.shape
    batch = {"tokens": tokens}
    pos2d = positions[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    if cfg.learned_pos:
        # clamp keeps speculative overshoot past the position table legal;
        # overshot positions are never committed (budget-masked)
        batch["positions"] = jnp.minimum(pos2d, cfg.learned_pos - 1)
    x = embed_inputs(params, batch, cfg)
    pos = pos2d
    if cfg.rope == "mrope":
        pos = jnp.broadcast_to(pos2d[None], (3, B, S))
    pending = {}
    if "dense_blocks" in params:
        x, _, pd = _run_group(x, params["dense_blocks"], cfg, pos,
                              moe=False, caches=cache["dense"],
                              chunk_offsets=positions, slot_done=done)
        pending["dense"] = pd
    if "moe_blocks" in params:
        x, _, pd = _run_group(x, params["moe_blocks"], cfg, pos,
                              moe=True, caches=cache["moe"],
                              chunk_offsets=positions, slot_done=done)
        pending["moe"] = pd
    x = _final_norm(params, x, cfg)
    return _head(params, x, cfg), pending


def commit_slots(params, tokens, positions, n_feed, cache, pending, cfg,
                 done=None):
    """Commit each row's accepted chunk prefix: scatter the pending K/V of
    chunk indices ``j < n_feed[b]`` at ``positions[b] + j`` (``% ring``
    for ring-buffer layouts) and drop the rest — rejected speculative
    positions never reach the cache, so KV truncation is implicit in the
    row's committed length.  Rows with ``n_feed == 0`` (or ``done``) are
    untouched bit-for-bit: their scatter indices are all out of range.
    """
    del params, tokens
    if done is not None:
        n_feed = jnp.where(done, 0, n_feed)
    leaf0 = jax.tree.leaves(pending)[0]
    B, S = leaf0.shape[1], leaf0.shape[2]
    pos = positions[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    committed = jnp.arange(S)[None] < n_feed[:, None]
    b_idx = jnp.arange(B)[:, None]

    def per_leaf(cl, pl):
        # cl: (L, B, Sc, ...) cache; pl: (L, B, S, ...) chunk pending.
        # ``pos % Sc`` is the ring slot for wrapping window caches and the
        # identity for full layouts (committed positions are < Sc by the
        # engine's max_len admission bound); uncommitted rows target the
        # out-of-range index Sc and are dropped by the scatter.
        Sc = cl.shape[2]
        idx = jnp.where(committed, pos % Sc, Sc)
        return jax.vmap(
            lambda c, ch: c.at[b_idx, idx].set(ch.astype(c.dtype)))(cl, pl)

    def per_paged_group(cg, pg):
        # cg: {leaf arenas (L, n_pages, page, ...), "bt": (L, B, nblk)};
        # pg: matching (L, B, S, ...) leaves — pending never carries a
        # table.  Leaves are k/v for KV layouts, ckv/kr for MLA latents.
        # Chunk position ``pos`` resolves to page ``bt[b, (pos % ring) //
        # page]`` (ring == the logical length, so the mod is the identity
        # for full layouts); rejected positions — and rows whose block
        # was never allocated — redirect to the page sentinel and drop.
        leaves = [key for key in cg if key != "bt"]
        n_pages, page = cg[leaves[0]].shape[1:3]
        bt = cg["bt"][0]  # layers share one table
        ring = bt.shape[1] * page
        sidx = pos % ring
        pid = jnp.take_along_axis(bt, sidx // page, axis=1)  # (B, S)
        pid = jnp.where(committed, pid, n_pages)
        off = sidx % page
        out = {"bt": cg["bt"]}
        for key in leaves:
            out[key] = jax.vmap(
                lambda c, ch: c.at[pid, off].set(ch.astype(c.dtype),
                                                 mode="drop"))(
                cg[key], pg[key])
        return out

    def walk(cg, pg):
        if isinstance(cg, dict) and "bt" in cg:
            return per_paged_group(cg, pg)
        if isinstance(cg, dict):
            return {key: walk(cg[key], pg[key]) for key in cg}
        return per_leaf(cg, pg)

    return walk(cache, pending)


def serve_supported(cfg):
    """Capability probe for the continuous-batching slot-decode protocol.

    Returns (ok, detail): ``detail`` names the slot cache layout when
    servable, or the reason when not.
    """
    if not cfg.causal or cfg.continuous_inputs:
        return False, ("requires a causal token LM "
                       f"(causal={cfg.causal}, "
                       f"continuous_inputs={cfg.continuous_inputs})")
    if cfg.mla and cfg.window:
        return False, "MLA latent caches have no ring-buffer window layout"
    if cfg.mla:
        return True, "full MLA latent cache (O(max_len) per slot)"
    if cfg.window:
        return True, "ring-buffer window KV cache (O(window) per slot)"
    return True, "full KV cache (O(max_len) per slot)"


def paged_groups(cfg):
    """Slot-state protocol: every transformer cache group pages on its
    sequence axis — K/V for standard attention, the compressed ckv/kr
    latents for MLA (both share one S axis and one block table)."""
    leaves = ("ckv", "kr") if cfg.mla else ("k", "v")
    n_dense = cfg.moe_layer_start if cfg.moe else cfg.n_layers
    out = {}
    if n_dense:
        out["dense"] = ("seq", leaves)
    if cfg.n_layers - n_dense:
        out["moe"] = ("seq", leaves)
    return out


def slot_cache_layout(cfg):
    """Slot-pool layout tag for benchmarks/telemetry.  A ``+kernel``
    suffix marks configs whose slot decode / chunk verify runs through
    the Pallas kernel family (``cfg.decode_kernel != "jnp"``); MLA latent
    caches always use the jnp absorbed-weight path."""
    if cfg.mla:
        return "full-mla"
    base = "ring" if cfg.window else "full"
    if _kernel_mode(cfg) is not None:
        return base + "+kernel"
    return base


# ============================================================= param specs
def param_specs(cfg):
    """Pytree of logical-axis tuples matching ``init``'s output."""
    specs: dict[str, Any] = {}
    if cfg.continuous_inputs:
        specs["in_proj"] = (None, "embed")
    else:
        specs["embed"] = ("vocab", "embed")
    if cfg.learned_pos:
        specs["pos_embed"] = (None, "embed")

    def attn_specs():
        if cfg.mla:
            return {
                "w_dq": ("layers", "embed", "q_lora"),
                "q_norm": ("layers", "q_lora"),
                "w_uq": ("layers", "q_lora", "heads"),
                "w_dkv": ("layers", "embed", "kv_lora"),
                "kv_norm": ("layers", "kv_lora"),
                "w_kr": ("layers", "embed", None),
                "w_uk": ("layers", "kv_lora", "heads"),
                "w_uv": ("layers", "kv_lora", "heads"),
                "wo": ("layers", "heads", "embed"),
            }
        s = {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
        }
        if cfg.qkv_bias:
            s["bq"] = ("layers", "heads")
            s["bk"] = ("layers", "kv_heads")
            s["bv"] = ("layers", "kv_heads")
        if cfg.attn_out_bias:
            s["bo"] = ("layers", "embed")
        if cfg.qk_norm:
            s["q_norm"] = ("layers", "head_dim")
            s["k_norm"] = ("layers", "head_dim")
        return s

    def norm_specs():
        s = {"scale": ("layers", "embed")}
        if cfg.norm == "ln":
            s["bias"] = ("layers", "embed")
        return s

    def group_specs(moe):
        g = {"ln1": norm_specs(), "ln2": norm_specs(), "attn": attn_specs()}
        if moe:
            g["moe"] = moe_lib.moe_specs(cfg)
        else:
            g["mlp"] = ffn_lib.mlp_specs(cfg.act, cfg.mlp_bias)
        return g

    n_dense = cfg.moe_layer_start if cfg.moe else cfg.n_layers
    if n_dense:
        specs["dense_blocks"] = group_specs(False)
    if cfg.n_layers - n_dense:
        specs["moe_blocks"] = group_specs(True)

    fn = {"scale": ("embed",)}
    if cfg.norm == "ln":
        fn["bias"] = ("embed",)
    specs["final_norm"] = fn
    if cfg.head == "lm" and not cfg.tie_embeddings:
        specs["head"] = ("embed", "vocab")
    elif cfg.head == "cls":
        specs["cls_token"] = ("embed",)
        specs["head"] = ("embed", None)
    if cfg.mtp:
        specs["mtp"] = {
            "proj": (None, "embed"),
            "norm_h": {"scale": ("embed",)},
            "norm_e": {"scale": ("embed",)},
            "block": group_specs(False),
        }
        if cfg.norm == "ln":
            specs["mtp"]["norm_h"]["bias"] = ("embed",)
            specs["mtp"]["norm_e"]["bias"] = ("embed",)
    return specs


def cache_specs(cfg):
    n_dense = cfg.moe_layer_start if cfg.moe else cfg.n_layers
    n_moe = cfg.n_layers - n_dense

    def one():
        if cfg.mla:
            return {"ckv": ("layers", "batch", "cache_seq", "kv_lora"),
                    "kr": ("layers", "batch", "cache_seq", None)}
        return {"k": ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
                "v": ("layers", "batch", "cache_seq", "kv_heads", "head_dim")}

    c = {}
    if n_dense:
        c["dense"] = one()
    if n_moe:
        c["moe"] = one()
    return c
