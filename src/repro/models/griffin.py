"""Griffin / RecurrentGemma family (arXiv:2402.19427).

Block pattern 2 recurrent : 1 local-MQA-attention.  The recurrent temporal
block is: linear → causal depthwise conv(4) → RG-LRU, gated by a parallel
GeLU branch.  RG-LRU:

    r_t = sigmoid(W_a y_t + b_a)          (recurrence gate)
    i_t = sigmoid(W_i y_t + b_i)          (input gate)
    log a_t = -c * softplus(Λ) * r_t      (c = 8)
    h_t = a_t h_{t-1} + sqrt(1 - a_t^2) * (i_t * y_t)

Training/prefill runs the recurrence as a parallel prefix
(``lax.associative_scan``) — the jnp lowering analogue of the paper's custom
scan kernel; ``repro/kernels/rglru_scan.py`` is the Pallas TPU version.
Decode keeps O(1) state per layer: (h, conv tail) — this is why this arch
runs the ``long_500k`` cell.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import annotate
from repro.models import attention as attn_lib
from repro.models import ffn as ffn_lib
from repro.models.common import (
    apply_norm,
    freeze_rows,
    gelu,
    init_norm,
    keygen,
    trunc_normal,
)
from repro.models.rope import apply_rope

C_RGLRU = 8.0


def block_pattern(cfg):
    if cfg.block_pattern:
        return cfg.block_pattern
    # default recurrentgemma pattern: (rec, rec, attn) repeating
    pat = []
    for i in range(cfg.n_layers):
        pat.append("attn" if i % 3 == 2 else "rec")
    return tuple(pat)


# ------------------------------------------------------------------- init
def init(rng, cfg) -> dict:
    keys = keygen(rng)
    dtype = jnp.dtype(cfg.param_dtype)
    std = 0.02
    D, W = cfg.d_model, cfg.lru_width
    pat = block_pattern(cfg)
    n_rec = sum(1 for t in pat if t == "rec")
    n_attn = len(pat) - n_rec
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def shp(n, *s):
        return (n, *s)

    params: dict[str, Any] = {
        "embed": trunc_normal(next(keys), (cfg.vocab_size, D), std, dtype),
    }
    params["rec_blocks"] = {
        "ln1": init_norm(cfg.norm, D, n_rec, dtype),
        "ln2": init_norm(cfg.norm, D, n_rec, dtype),
        "w_x": trunc_normal(next(keys), shp(n_rec, D, W), std, dtype),
        "w_gate": trunc_normal(next(keys), shp(n_rec, D, W), std, dtype),
        "w_out": trunc_normal(next(keys), shp(n_rec, W, D), std, dtype),
        "conv_w": trunc_normal(next(keys), shp(n_rec, cfg.conv_width, W),
                               std, dtype),
        "conv_b": jnp.zeros(shp(n_rec, W), dtype),
        # RG-LRU gate projections are block-diagonal with n_heads blocks
        # (recurrentgemma's BlockDiagonalLinear)
        "w_a": trunc_normal(next(keys), shp(n_rec, H, W // H, W // H), std,
                            dtype),
        "b_a": jnp.zeros(shp(n_rec, W), dtype),
        "w_i": trunc_normal(next(keys), shp(n_rec, H, W // H, W // H), std,
                            dtype),
        "b_i": jnp.zeros(shp(n_rec, W), dtype),
        # Λ init so that a spans ~(0.9, 0.999) as in the paper
        "lam": jnp.asarray(
            jax.random.uniform(next(keys), (n_rec, W), jnp.float32,
                               0.0, 1.0) * 0.5 + 0.2, dtype),
        "mlp": ffn_lib.init_mlp(keys, D, cfg.d_ff, layers=n_rec, act=cfg.act,
                                dtype=dtype, std=std),
    }
    if n_attn:
        params["attn_blocks"] = {
            "ln1": init_norm(cfg.norm, D, n_attn, dtype),
            "ln2": init_norm(cfg.norm, D, n_attn, dtype),
            "wq": trunc_normal(next(keys), shp(n_attn, D, H * hd), std, dtype),
            "wk": trunc_normal(next(keys), shp(n_attn, D, KV * hd), std, dtype),
            "wv": trunc_normal(next(keys), shp(n_attn, D, KV * hd), std, dtype),
            "wo": trunc_normal(next(keys), shp(n_attn, H * hd, D), std, dtype),
            "mlp": ffn_lib.init_mlp(keys, D, cfg.d_ff, layers=n_attn,
                                    act=cfg.act, dtype=dtype, std=std),
        }
    params["final_norm"] = init_norm(cfg.norm, D, None, dtype)
    if not cfg.tie_embeddings:
        params["head"] = trunc_normal(next(keys), (D, cfg.vocab_size), std,
                                      dtype)
    return params


# ------------------------------------------------------------------ RG-LRU
def _block_diag(yf, w):
    """Block-diagonal linear: yf (B,S,W), w (H, W/H, W/H) -> (B,S,W)."""
    B, S, W = yf.shape
    H = w.shape[0]
    yh = yf.reshape(B, S, H, W // H)
    out = jnp.einsum("bshw,hwv->bshv", yh, w.astype(yf.dtype))
    return out.reshape(B, S, W)


def _rglru_gates(y, bp):
    """y: (B,S,W) post-conv activations -> (log_a, x_scaled) both f32."""
    yf = y.astype(jnp.float32)
    r = jax.nn.sigmoid(
        _block_diag(yf, bp["w_a"]) + bp["b_a"].astype(jnp.float32))
    i = jax.nn.sigmoid(
        _block_diag(yf, bp["w_i"]) + bp["b_i"].astype(jnp.float32))
    log_a = -C_RGLRU * jax.nn.softplus(bp["lam"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i * yf)
    return log_a, gated


def rglru_parallel(y, bp, h0=None, valid=None):
    """Parallel-prefix RG-LRU over the sequence. y: (B,S,W).

    ``h0``: optional (B,W) f32 initial state (multi-token prefill into an
    existing cache): h_t = (prod a_{0..t}) h0 + scan_t.  ``valid``:
    optional (B,S) bool — invalid positions are frozen to the identity
    element (a=1, b=0), so the recurrence carries h across the padded
    tails of bucketed admission prompts unchanged and the final state is
    exactly h_{plen-1}.  Returns (h (B,S,W) in y.dtype, h_last (B,W) f32).
    """
    log_a, b = _rglru_gates(y, bp)
    if valid is not None:
        log_a = jnp.where(valid[..., None], log_a, 0.0)
        b = jnp.where(valid[..., None], b, 0.0)
    a = jnp.exp(log_a)

    def op(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    prod_a, h = jax.lax.associative_scan(op, (a, b), axis=1)
    if h0 is not None:
        h = h + prod_a * h0[:, None]
    return h.astype(y.dtype), h[:, -1]


def rglru_step(y, h_prev, bp):
    """Single-step RG-LRU. y: (B,1,W); h_prev: (B,W) f32."""
    log_a, b = _rglru_gates(y, bp)
    h = jnp.exp(log_a[:, 0]) * h_prev + b[:, 0]
    return h.astype(y.dtype)[:, None], h


def _causal_conv(y, w, b, state=None, lengths=None):
    """Depthwise causal conv. y: (B,S,W); w: (K,W); state: (B,K-1,W)|None.

    ``lengths`` (B,): per-row true sequence lengths — the returned conv
    tail is then gathered at each row's own boundary (bucketed admission
    prompts are tail-padded, and the state handed to decode must be the
    last K-1 REAL inputs, not the padding).
    """
    K = w.shape[0]
    if state is None:
        ypad = jnp.pad(y, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        ypad = jnp.concatenate([state.astype(y.dtype), y], axis=1)
    out = sum(
        ypad[:, k:k + y.shape[1]] * w[k].astype(y.dtype) for k in range(K)
    ) + b.astype(y.dtype)
    if K == 1:
        new_state = None
    elif lengths is None:
        new_state = ypad[:, -(K - 1):]
    else:
        # ypad index of position t is t + (K-1): row b's tail covers
        # positions lengths[b]-(K-1) .. lengths[b]-1 -> ypad rows
        # lengths[b] .. lengths[b]+K-2 (identical to the static slice
        # when lengths[b] == S)
        idx = (lengths[:, None] + jnp.arange(K - 1)[None])[..., None]
        new_state = jnp.take_along_axis(ypad, idx, axis=1)
    return out, new_state


def _rec_temporal(x, bp, cfg, conv_state=None, h_state=None, plens=None):
    """Recurrent temporal block. Returns (out, new_conv_state, new_h).

    Single-token cached steps take the O(1) recurrence; every multi-token
    call (training, prefill — with or without an initial state) runs the
    parallel prefix.  ``plens`` marks a bucketed admission prefill: pad
    positions freeze the RG-LRU to identity and the conv tail is gathered
    at each row's true boundary.
    """
    y = jnp.einsum("bsd,dw->bsw", x, bp["w_x"].astype(x.dtype))
    g = gelu(jnp.einsum("bsd,dw->bsw", x, bp["w_gate"].astype(x.dtype)))
    y = annotate(y, ("batch", "seq", "lru"))
    y, new_conv = _causal_conv(y, bp["conv_w"], bp["conv_b"], conv_state,
                               lengths=plens)
    if h_state is not None and y.shape[1] == 1:
        h, new_h = rglru_step(y, h_state, bp)
    else:
        valid = None
        if plens is not None:
            valid = jnp.arange(y.shape[1])[None] < plens[:, None]
        h, new_h = rglru_parallel(y, bp, h0=h_state, valid=valid)
    out = jnp.einsum("bsw,wd->bsd", h * g, bp["w_out"].astype(x.dtype))
    return out, new_conv, new_h


# ------------------------------------------------------------------ blocks
def _rec_block(x, bp, cfg, cache=None, plens=None, done=None):
    h, new_conv, new_h = _rec_temporal(
        apply_norm(x, bp["ln1"], cfg.norm), bp, cfg,
        conv_state=None if cache is None else cache["conv"],
        h_state=None if cache is None else cache["h"],
        plens=plens)
    x = x + h
    x = x + ffn_lib.mlp(apply_norm(x, bp["ln2"], cfg.norm), bp["mlp"],
                        cfg.act)
    x = annotate(x, ("batch", "seq", "embed"))
    nc = None
    if cache is not None:
        nc = {"conv": new_conv, "h": new_h}
        if done is not None:
            nc = freeze_rows(cache, nc, done)
    return x, nc


def _attn_block(x, bp, cfg, positions, cache=None, q_offset=0,
                slot_positions=None, slot_done=None, plens=None):
    from repro.models import transformer as tf

    xin = apply_norm(x, bp["ln1"], cfg.norm)
    q = xin @ bp["wq"].astype(x.dtype)
    k = xin @ bp["wk"].astype(x.dtype)
    v = xin @ bp["wv"].astype(x.dtype)
    B, S, _ = x.shape
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, theta=cfg.rope_theta)
    k = apply_rope(k, positions, theta=cfg.rope_theta)
    nc = None
    if slot_positions is not None:
        # continuous-batching decode: every row is a slot at its own
        # length — write this step's K/V at the row's own ring slot and
        # attend by absolute position (the slot mirror of the S == 1
        # path); ``cfg.decode_kernel`` routes the attend through the
        # Pallas ring kernel.  A paged pool routes the write through the
        # row's block table instead of a private ring row.
        update = (attn_lib.paged_ring_slot_update_attend
                  if "bt" in cache else attn_lib.ring_slot_update_attend)
        out, nc = update(
            q, cache, k, v, slot_positions, window=cfg.window,
            done=slot_done, kernel=tf._kernel_mode(cfg))
    elif cache is not None:
        ck, cv = cache["k"], cache["v"]
        window = cfg.window
        ring = ck.shape[1]  # the ring modulus (>= window once padded)
        if plens is not None and S > 1:
            # bucketed admission prefill: fill each row's ring from its
            # TRUE prompt length by absolute position
            ck = attn_lib.ring_fill_rows(k, plens, ring, ck.dtype)
            cv = attn_lib.ring_fill_rows(v, plens, ring, cv.dtype)
            nc = {"k": ck, "v": cv}
            out = attn_lib.attention(q, k, v, causal=True, window=window,
                                     q_offset=q_offset,
                                     chunk_q=cfg.attn_chunk,
                                     unroll=cfg.unroll_scans)
        else:
            w_eff = min(S, ring)
            idx = (q_offset + S - w_eff + jnp.arange(w_eff)) % ring
            ck = ck.at[:, idx].set(k[:, -w_eff:].astype(ck.dtype))
            cv = cv.at[:, idx].set(v[:, -w_eff:].astype(cv.dtype))
            nc = {"k": ck, "v": cv}
            if S == 1:
                kpos_abs = tf._ring_positions(q_offset + S, ring)
                out = tf._ring_window_attend(q, ck.astype(x.dtype),
                                             cv.astype(x.dtype), kpos_abs,
                                             q_offset, cfg)
            else:
                out = attn_lib.attention(q, k, v, causal=True,
                                         window=cfg.window,
                                         q_offset=q_offset,
                                         chunk_q=cfg.attn_chunk,
                                         unroll=cfg.unroll_scans)
    else:
        out = attn_lib.attention(q, k, v, causal=True, window=cfg.window,
                                 q_offset=q_offset, chunk_q=cfg.attn_chunk,
                                 unroll=cfg.unroll_scans)
    out = out.reshape(B, S, -1)
    x = x + out @ bp["wo"].astype(x.dtype)
    x = x + ffn_lib.mlp(apply_norm(x, bp["ln2"], cfg.norm), bp["mlp"],
                        cfg.act)
    x = annotate(x, ("batch", "seq", "embed"))
    return x, nc


def _pattern_runs(pat):
    """[(type, start_idx_within_type, count), ...] contiguous runs."""
    runs = []
    counts = {"rec": 0, "attn": 0}
    i = 0
    while i < len(pat):
        j = i
        while j < len(pat) and pat[j] == pat[i]:
            j += 1
        runs.append((pat[i], counts[pat[i]], j - i))
        counts[pat[i]] += j - i
        i = j
    return runs


# ----------------------------------------------------------------- forward
def forward(params, batch, cfg):
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"].astype(cdt)[batch["tokens"]]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    B, S = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    x = _run_blocks(params, x, cfg, positions)
    x = apply_norm(x, params["final_norm"], cfg.norm)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(cdt))
    return annotate(logits, ("batch", "seq", "vocab")), {"moe_aux": 0.0}


def _run_blocks(params, x, cfg, positions, caches=None, q_offset=0,
                plens=None, slot_positions=None, slot_done=None):
    from repro.models.common import slice_layers, take_layer

    pat = block_pattern(cfg)
    new_caches = {"rec": [], "attn": []} if caches is not None else None
    for typ, start, count in _pattern_runs(pat):
        if typ == "rec":
            group = slice_layers(params["rec_blocks"], start, start + count)

            def body(carry, xs):
                xc = carry
                bp, cache_l = xs if caches is not None else (xs, None)
                xc, nc = _rec_block(xc, bp, cfg, cache=cache_l, plens=plens,
                                    done=slot_done)
                return xc, nc

            if cfg.remat == "block":
                body = jax.remat(body, prevent_cse=False)
            xs = group
            if caches is not None:
                xs = (group, slice_layers(caches["rec"], start, start + count))
            x, ncs = jax.lax.scan(body, x, xs, unroll=cfg.unroll_scans)
            if caches is not None:
                new_caches["rec"].append(ncs)
        else:
            for k in range(count):
                bp = take_layer(params["attn_blocks"], start + k)
                cache_l = (take_layer(caches["attn"], start + k)
                           if caches is not None else None)
                fn = _attn_block
                if cfg.remat == "block" and caches is None:
                    fn = jax.remat(_attn_block, static_argnums=(2,),
                                   prevent_cse=False)
                x, nc = fn(x, bp, cfg, positions, cache_l, q_offset,
                           slot_positions=slot_positions,
                           slot_done=slot_done, plens=plens)
                if caches is not None:
                    new_caches["attn"].append(
                        jax.tree.map(lambda a: a[None], nc))
    if caches is not None:
        out = {}
        out["rec"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, 0), *new_caches["rec"])
        if new_caches["attn"]:
            out["attn"] = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, 0), *new_caches["attn"])
        return x, out
    return x


# -------------------------------------------------------------------- serve
def init_cache(cfg, batch_size, max_len, dtype=None):
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    pat = block_pattern(cfg)
    n_rec = sum(1 for t in pat if t == "rec")
    n_attn = len(pat) - n_rec
    from repro.models.common import pad_cache_len
    wlen = pad_cache_len(min(max_len, cfg.window or max_len))
    cache = {
        "rec": {
            "conv": jnp.zeros((n_rec, batch_size, cfg.conv_width - 1,
                               cfg.lru_width), dtype),
            "h": jnp.zeros((n_rec, batch_size, cfg.lru_width), jnp.float32),
        }
    }
    if n_attn:
        cache["attn"] = {
            "k": jnp.zeros((n_attn, batch_size, wlen, cfg.n_kv_heads,
                            cfg.head_dim), dtype),
            "v": jnp.zeros((n_attn, batch_size, wlen, cfg.n_kv_heads,
                            cfg.head_dim), dtype),
        }
    return cache


def _forward_cached(params, batch, cfg, cache, q_offset, plens=None):
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"].astype(cdt)[batch["tokens"]]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    B, S = x.shape[:2]
    positions = q_offset + jnp.arange(S, dtype=jnp.int32)[None]
    positions = jnp.broadcast_to(positions, (B, S))
    x, new_cache = _run_blocks(params, x, cfg, positions, caches=cache,
                               q_offset=q_offset, plens=plens)
    if plens is not None:
        # admission: the head only at each row's last real position
        x = x[jnp.arange(B), plens - 1][:, None]
    x = apply_norm(x, params["final_norm"], cfg.norm)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(cdt))
    return (logits[:, 0] if plens is not None else logits), new_cache


def prefill(params, batch, cfg, cache):
    logits, cache = _forward_cached(params, batch, cfg, cache, 0)
    return logits[:, -1], cache


def decode_step(params, tokens, pos, cache, cfg):
    logits, cache = _forward_cached(
        params, {"tokens": tokens[:, None]}, cfg, cache, pos)
    return logits[:, -1], cache


def prefill_last(params, batch, cfg, cache):
    """Admission prefill: logits (B, V) at each row's last real prompt
    position + per-row final state.

    ``batch["plens"]`` (B,) carries each row's true prompt length: RG-LRU
    pad positions freeze to identity, conv tails are gathered at the row
    boundary, and ring window caches are filled per row by absolute
    position — so the returned cache is exactly the state after each
    row's REAL prompt, tail padding notwithstanding.
    """
    batch = dict(batch)
    plens = batch.pop("plens")
    return _forward_cached(params, batch, cfg, cache, 0, plens=plens)


def decode_step_slots(params, tokens, positions, cache, cfg, done=None):
    """Continuous-batching decode: one token per slot at per-slot lengths.

    tokens/positions: (B,) — each row's last token and current length.
    ``done`` rows FREEZE their recurrent state (conv tails, RG-LRU h —
    a recurrent update is irreversible, unlike a KV re-store) and their
    ring slots keep their old bytes; live rows advance the O(1)
    recurrence and write their ring slot at ``pos % ring``.
    Returns (logits (B, V), new_cache).
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"].astype(cdt)[tokens[:, None]]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    x, new_cache = _run_blocks(params, x, cfg, positions[:, None],
                               caches=cache, slot_positions=positions,
                               slot_done=done)
    x = apply_norm(x, params["final_norm"], cfg.norm)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(cdt))
    return logits[:, -1], new_cache


def verify_step_slots(params, tokens, positions, cache, cfg, done=None):
    """Speculative verify for the recurrent slot layout: one fused scan of
    the single-token slot decode over the chunk.  Only the genuinely O(1)
    recurrent state (rglru h, conv tails) is stacked per chunk position;
    the O(window) local-attention rings are NOT — they commit through an
    accept-masked restore instead, so verify memory stays O(state +
    window), not O(chunk * window).  Bit-identical to sequential decode
    by construction — each scan step runs the same (B, 1) arithmetic as
    the macro decode loop.
    """
    from repro.models.common import spec_verify_scan
    logits, stacked, final = spec_verify_scan(
        decode_step_slots, params, tokens, positions, cache, cfg,
        done=done, stack_filter=lambda c: {"rec": c["rec"]})
    pending = {"rec": stacked["rec"]}
    if "attn" in cache:
        pending["attn_new"] = final["attn"]
    return logits, pending


def commit_slots(params, tokens, positions, n_feed, cache, pending, cfg,
                 done=None):
    """Commit per leaf kind: recurrent state gathers the stacked verify
    snapshots at ``n_feed - 1`` per row (the ``freeze_rows``-style
    snapshot/restore a recurrence needs — its updates cannot be
    re-stored); local-attention rings keep the scan's accepted writes and
    restore pre-chunk bytes at rejected slots.  Rows with ``n_feed == 0``
    or flagged ``done`` keep their pre-chunk state wholesale."""
    from repro.models.common import (
        paged_spec_ring_restore,
        spec_commit_gather,
        spec_ring_restore,
    )
    del params
    if done is not None:
        n_feed = jnp.where(done, 0, n_feed)
    out = {"rec": spec_commit_gather(cache["rec"], pending["rec"], n_feed)}
    if "attn" in cache:
        restore = (paged_spec_ring_restore if "bt" in cache["attn"]
                   else spec_ring_restore)
        out["attn"] = restore(cache["attn"], pending["attn_new"],
                              positions, n_feed, tokens.shape[1])
    return out


def serve_supported(cfg):
    """Capability probe for the continuous-batching slot-decode protocol."""
    pat = block_pattern(cfg)
    has_attn = any(t == "attn" for t in pat)
    if has_attn and not cfg.window:
        return False, "griffin local-attention blocks require cfg.window"
    detail = "recurrent state (O(1) per slot: rglru h + conv tail)"
    if has_attn:
        detail += " + ring-buffer window KV (O(window) per slot)"
    return True, detail


def slot_cache_layout(cfg):
    has_attn = any(t == "attn" for t in block_pattern(cfg))
    if not has_attn:
        return "recurrent"
    if cfg.decode_kernel != "jnp":
        return "recurrent+ring+kernel"
    return "recurrent+ring"


def paged_groups(cfg):
    """Slot-state protocol: the local-attention ring K/V pages; the
    recurrent group (rglru h + conv tail, O(1)/slot) stays dense — there
    is no sequence axis to page and the state is already minimal."""
    if any(t == "attn" for t in block_pattern(cfg)):
        return {"attn": ("seq", ("k", "v"))}
    return {}


def cache_specs(cfg):
    pat = block_pattern(cfg)
    n_attn = sum(1 for t in pat if t == "attn")
    c = {"rec": {
        "conv": ("layers", "batch", None, "lru"),
        "h": ("layers", "batch", "lru"),
    }}
    if n_attn:
        c["attn"] = {"k": ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
                     "v": ("layers", "batch", "cache_seq", "kv_heads", "head_dim")}
    return c


# -------------------------------------------------------------- param specs
def param_specs(cfg):
    pat = block_pattern(cfg)
    n_attn = sum(1 for t in pat if t == "attn")
    L = ("layers",)
    rec = {
        "ln1": {"scale": L + ("embed",)},
        "ln2": {"scale": L + ("embed",)},
        "w_x": L + ("embed", "lru"),
        "w_gate": L + ("embed", "lru"),
        "w_out": L + ("lru", "embed"),
        "conv_w": L + (None, "lru"),
        "conv_b": L + ("lru",),
        "w_a": L + (None, None, None),
        "b_a": L + ("lru",),
        "w_i": L + (None, None, None),
        "b_i": L + ("lru",),
        "lam": L + ("lru",),
        "mlp": ffn_lib.mlp_specs(cfg.act, False),
    }
    specs = {"embed": ("vocab", "embed"), "rec_blocks": rec,
             "final_norm": {"scale": ("embed",)}}
    if n_attn:
        specs["attn_blocks"] = {
            "ln1": {"scale": L + ("embed",)},
            "ln2": {"scale": L + ("embed",)},
            "wq": L + ("embed", "heads"),
            "wk": L + ("embed", "kv_heads"),
            "wv": L + ("embed", "kv_heads"),
            "wo": L + ("heads", "embed"),
            "mlp": ffn_lib.mlp_specs(cfg.act, False),
        }
    if not cfg.tie_embeddings:
        specs["head"] = ("embed", "vocab")
    return specs
