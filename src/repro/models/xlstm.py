"""xLSTM family (arXiv:2405.04517): mLSTM (matrix memory) + sLSTM blocks.

Training/prefill run the mLSTM in *chunkwise-recurrent* form: within a chunk
the contribution is a decay-masked attention-like quadratic form; across
chunks a (dh x dh) matrix state C, normalizer n and stabilizer m are carried
— O(S * chunk) compute, O(1)-in-S state.  Decode is the pure recurrence
(O(1) per token), which is why this arch runs the ``long_500k`` cell.

The sequential recurrence (``mlstm_sequential``) doubles as the test oracle
for the chunkwise form.  sLSTM blocks (true recurrence via block-diagonal R)
run under ``lax.scan`` over time, as in the paper (not parallelizable).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import annotate
from repro.models.common import (
    apply_norm,
    freeze_rows,
    gelu,
    init_norm,
    keygen,
    trunc_normal,
)
from repro.models.griffin import _causal_conv


def block_types(cfg):
    """Per-layer type list: 'm' (mLSTM) or 's' (sLSTM)."""
    if cfg.block_pattern:
        return tuple(cfg.block_pattern)
    out = []
    for i in range(cfg.n_layers):
        if cfg.slstm_every and (i % cfg.slstm_every == cfg.slstm_every - 1):
            out.append("s")
        else:
            out.append("m")
    return tuple(out)


# ===================================================================== init
def init(rng, cfg) -> dict:
    keys = keygen(rng)
    dtype = jnp.dtype(cfg.param_dtype)
    std = 0.02
    D, NH = cfg.d_model, cfg.n_heads
    di = int(cfg.proj_factor * D)  # mLSTM inner dim
    types = block_types(cfg)
    n_m = sum(1 for t in types if t == "m")
    n_s = len(types) - n_m

    params: dict[str, Any] = {
        "embed": trunc_normal(next(keys), (cfg.vocab_size, D), std, dtype),
    }
    params["m_blocks"] = {
        "ln": init_norm(cfg.norm, D, n_m, dtype),
        "w_up": trunc_normal(next(keys), (n_m, D, 2 * di), std, dtype),
        "conv_w": trunc_normal(next(keys), (n_m, cfg.conv_width, di), std,
                               dtype),
        "conv_b": jnp.zeros((n_m, di), dtype),
        "w_q": trunc_normal(next(keys), (n_m, di, di), std, dtype),
        "w_k": trunc_normal(next(keys), (n_m, di, di), std, dtype),
        "w_v": trunc_normal(next(keys), (n_m, di, di), std, dtype),
        "w_if": trunc_normal(next(keys), (n_m, di, 2 * NH), std, dtype),
        "b_if": jnp.zeros((n_m, 2 * NH), dtype),
        "gn": jnp.ones((n_m, di), dtype),  # per-head group norm scale
        "w_down": trunc_normal(next(keys), (n_m, di, D), std, dtype),
    }
    if n_s:
        dh = D // NH
        pf = 4.0 / 3.0
        dff = int(pf * D)
        params["s_blocks"] = {
            "ln": init_norm(cfg.norm, D, n_s, dtype),
            "conv_w": trunc_normal(next(keys), (n_s, cfg.conv_width, D), std,
                                   dtype),
            "conv_b": jnp.zeros((n_s, D), dtype),
            "w_gates": trunc_normal(next(keys), (n_s, D, 4 * D), std, dtype),
            "r_gates": trunc_normal(next(keys), (n_s, NH, dh, 4 * dh),
                                    std, dtype),
            "b_gates": jnp.zeros((n_s, 4 * D), dtype),
            "gn": jnp.ones((n_s, D), dtype),
            "w_up1": trunc_normal(next(keys), (n_s, D, dff), std, dtype),
            "w_up2": trunc_normal(next(keys), (n_s, D, dff), std, dtype),
            "w_down": trunc_normal(next(keys), (n_s, dff, D), std, dtype),
        }
    params["final_norm"] = init_norm(cfg.norm, D, None, dtype)
    if not cfg.tie_embeddings:
        params["head"] = trunc_normal(next(keys), (D, cfg.vocab_size), std,
                                      dtype)
    return params


# ---------------------------------------------------- paged conv tails
def _conv_tail_gather(arena, bt):
    """Dense per-slot view of a paged conv tail (per layer).

    arena: (n_pages, K-1, d); bt: (B, 1) single-block table (the whole
    shift tail is one page).  Sentinel ids clamp to the last page — the
    garbage tail that produces belongs to rows whose state writes are
    dropped and whose logits the engine masks.
    """
    n_pages = arena.shape[0]
    return arena[jnp.minimum(bt[:, 0], n_pages - 1)]


def _conv_tail_scatter(arena, bt, tail, done=None):
    """Write each live row's new conv tail back to its page; ``done``
    rows (and never-allocated sentinel blocks) drop — the paged freeze."""
    n_pages = arena.shape[0]
    pid = bt[:, 0]
    if done is not None:
        pid = jnp.where(done, n_pages, pid)
    return arena.at[pid].set(tail.astype(arena.dtype), mode="drop")


# ============================================================== mLSTM cell
def _group_norm(x, scale, nh, eps=1e-6):
    """Per-head RMS-style groupnorm. x: (..., di)."""
    shp = x.shape
    xh = x.reshape(*shp[:-1], nh, shp[-1] // nh).astype(jnp.float32)
    var = jnp.mean(jnp.square(xh), axis=-1, keepdims=True)
    xh = xh * jax.lax.rsqrt(var + eps)
    return (xh.reshape(shp) * scale.astype(jnp.float32)).astype(x.dtype)


def mlstm_chunkwise(q, k, v, log_i, log_f, state=None, chunk=256,
                    unroll=False):
    """Chunkwise mLSTM.

    q,k,v: (B,NH,S,dh) — q pre-scaled by dh**-0.5.
    log_i, log_f: (B,NH,S) f32 gate log-activations.
    state: None or (C (B,NH,dh,dh), n (B,NH,dh), m (B,NH)) f32.
    Returns (h (B,NH,S,dh), final state).
    """
    B, NH, S, dh = q.shape
    if S % chunk != 0:
        chunk = S  # single chunk fallback
    nc = S // chunk

    def to_chunks(x):
        return x.reshape(B, NH, nc, chunk, *x.shape[3:]).transpose(
            2, 0, 1, 3, *range(4, x.ndim + 1))

    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)
    lic, lfc = to_chunks(log_i), to_chunks(log_f)

    if state is None:
        C0 = jnp.zeros((B, NH, dh, dh), jnp.float32)
        n0 = jnp.zeros((B, NH, dh), jnp.float32)
        m0 = jnp.full((B, NH), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state

    def chunk_body(carry, xs):
        C, n, m = carry
        qj, kj, vj, li, lf = xs  # (B,NH,T,...) / (B,NH,T)
        b = jnp.cumsum(lf, axis=-1)  # inclusive forget cumsum (B,NH,T)
        btot = b[..., -1]
        # per-step stabilizer: m_t = max(m_prev + b_t, b_t + max_{s<=t}(li_s - b_s))
        run_max = jax.lax.associative_scan(jnp.maximum, li - b, axis=-1)
        m_t = jnp.maximum(m[..., None] + b, b + run_max)
        m_intra = jnp.max(li - b, axis=-1)  # max_s (log_i_s - b_s)
        # inter-chunk part
        scale_inter = jnp.exp(m[..., None] + b - m_t)  # (B,NH,T)
        qf = qj.astype(jnp.float32)
        kf = kj.astype(jnp.float32)
        vf = vj.astype(jnp.float32)
        h_inter = jnp.einsum("bhtd,bhde->bhte", qf, C) * scale_inter[..., None]
        d_inter = jnp.einsum("bhtd,bhd->bht", qf, n) * scale_inter
        # intra-chunk decay matrix  D[t,s] = exp(b_t - b_s + li_s - m_t)
        dmat = b[..., :, None] - b[..., None, :] + li[..., None, :] \
            - m_t[..., :, None]
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        dmat = jnp.where(tri, dmat, -jnp.inf)
        dexp = jnp.exp(dmat)
        scores = jnp.einsum("bhtd,bhsd->bhts", qf, kf) * dexp
        h_intra = jnp.einsum("bhts,bhse->bhte", scores, vf)
        d_intra = jnp.sum(scores, axis=-1)
        denom = jnp.maximum(jnp.abs(d_inter + d_intra), jnp.exp(-m_t))
        h = (h_inter + h_intra) / denom[..., None]
        # state update to end of chunk
        m_next = jnp.maximum(m + btot, btot + m_intra)
        sc_old = jnp.exp(m + btot - m_next)  # (B,NH)
        sc_new = jnp.exp(btot[..., None] - b + li - m_next[..., None])
        C_new = (C * sc_old[..., None, None]
                 + jnp.einsum("bht,bhtd,bhte->bhde", sc_new, kf, vf))
        n_new = n * sc_old[..., None] + jnp.einsum("bht,bhtd->bhd", sc_new, kf)
        return (C_new, n_new, m_next), h.astype(q.dtype)

    (C, n, m), hs = jax.lax.scan(chunk_body, (C0, n0, m0),
                                 (qc, kc, vc, lic, lfc), unroll=unroll)
    h = hs.transpose(1, 2, 0, 3, 4).reshape(B, NH, S, dh)
    return h, (C, n, m)


def mlstm_sequential(q, k, v, log_i, log_f, state=None):
    """Step-by-step oracle (and decode path for S==1)."""
    B, NH, S, dh = q.shape
    if state is None:
        C = jnp.zeros((B, NH, dh, dh), jnp.float32)
        n = jnp.zeros((B, NH, dh), jnp.float32)
        m = jnp.full((B, NH), -1e30, jnp.float32)
    else:
        C, n, m = state

    def step(carry, xs):
        C, n, m = carry
        qt, kt, vt, li, lf = xs
        qt, kt, vt = (a.astype(jnp.float32) for a in (qt, kt, vt))
        m_new = jnp.maximum(lf + m, li)
        i_p = jnp.exp(li - m_new)
        f_p = jnp.exp(lf + m - m_new)
        C = f_p[..., None, None] * C + i_p[..., None, None] * (
            kt[..., :, None] * vt[..., None, :])
        n = f_p[..., None] * n + i_p[..., None] * kt
        num = jnp.einsum("bhd,bhde->bhe", qt, C)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qt, n)),
                          jnp.exp(-m_new))
        h = num / den[..., None]
        return (C, n, m_new), h

    xs = (q.transpose(2, 0, 1, 3), k.transpose(2, 0, 1, 3),
          v.transpose(2, 0, 1, 3), log_i.transpose(2, 0, 1),
          log_f.transpose(2, 0, 1))
    (C, n, m), hs = jax.lax.scan(step, (C, n, m), xs)
    return hs.transpose(1, 2, 0, 3).astype(q.dtype), (C, n, m)


def _mlstm_block(x, bp, cfg, cache=None, chunkwise=True, plens=None,
                 done=None):
    """x: (B,S,D). cache: {"conv": (B,K-1,di), "C","n","m"} or None.

    ``plens`` (B,): bucketed admission prefill — pad positions freeze the
    recurrence exactly (input gate -> exp(-inf) = 0 contribution, forget
    log -> 0 decay) and the conv tail is gathered at each row's true
    boundary, so the carried (C, n, m) is the state after the REAL prompt.
    ``done`` (B,): slot-decode rows whose state must not advance.
    """
    B, S, D = x.shape
    NH = cfg.n_heads
    di = int(cfg.proj_factor * D)
    dh = di // NH
    xin = apply_norm(x, bp["ln"], cfg.norm)
    up = jnp.einsum("bsd,du->bsu", xin, bp["w_up"].astype(x.dtype))
    xi, z = up[..., :di], up[..., di:]
    xi = annotate(xi, ("batch", "seq", "lru"))
    conv_state = None
    if cache is not None:
        conv_state = (_conv_tail_gather(cache["conv"], cache["bt"])
                      if "bt" in cache else cache["conv"])
    c, new_conv = _causal_conv(xi, bp["conv_w"], bp["conv_b"], conv_state,
                               lengths=plens)
    c = jax.nn.silu(c)
    q = jnp.einsum("bsu,uv->bsv", c, bp["w_q"].astype(x.dtype))
    k = jnp.einsum("bsu,uv->bsv", c, bp["w_k"].astype(x.dtype))
    v = jnp.einsum("bsu,uv->bsv", xi, bp["w_v"].astype(x.dtype))

    def heads(a):
        return a.reshape(B, S, NH, dh).transpose(0, 2, 1, 3)

    q, k, v = heads(q) * (dh ** -0.5), heads(k), heads(v)
    gates = jnp.einsum("bsu,ug->bsg", c.astype(jnp.float32),
                       bp["w_if"].astype(jnp.float32)) \
        + bp["b_if"].astype(jnp.float32)
    gates = gates.reshape(B, S, 2, NH).transpose(2, 0, 3, 1)  # (2,B,NH,S)
    log_i, log_f = gates[0], jax.nn.log_sigmoid(gates[1])
    if plens is not None:
        valid = (jnp.arange(S)[None] < plens[:, None])[:, None, :]  # (B,1,S)
        log_i = jnp.where(valid, log_i, -jnp.inf)
        log_f = jnp.where(valid, log_f, 0.0)

    state = None
    if cache is not None:
        state = (cache["C"], cache["n"], cache["m"])
    if S == 1 or not chunkwise:
        h, state = mlstm_sequential(q, k, v, log_i, log_f, state)
    else:
        h, state = mlstm_chunkwise(q, k, v, log_i, log_f, state,
                                   chunk=min(cfg.attn_chunk, 256),
                                   unroll=cfg.unroll_scans)
    h = h.transpose(0, 2, 1, 3).reshape(B, S, di)
    h = _group_norm(h, bp["gn"], NH)
    h = h * jax.nn.silu(z)
    out = jnp.einsum("bsu,ud->bsd", h, bp["w_down"].astype(x.dtype))
    x = annotate(x + out, ("batch", "seq", "embed"))
    nc = None
    if cache is not None:
        nc = {"conv": new_conv, "C": state[0], "n": state[1], "m": state[2]}
        if "bt" in cache:
            dense = {k: nc[k] for k in ("C", "n", "m")}
            if done is not None:
                dense = freeze_rows({k: cache[k] for k in dense}, dense,
                                    done)
            nc = {"conv": _conv_tail_scatter(cache["conv"], cache["bt"],
                                             new_conv, done=done),
                  "bt": cache["bt"], **dense}
        elif done is not None:
            nc = freeze_rows(cache, nc, done)
    return x, nc


# ============================================================== sLSTM cell
def _slstm_block(x, bp, cfg, cache=None, plens=None, done=None):
    """Sequential sLSTM block. x: (B,S,D).

    ``plens``: pad positions of a bucketed admission prefill freeze the
    carried (c, n, h, m) in-scan — the hidden-state recurrence would
    otherwise absorb the padding.  ``done``: slot rows frozen wholesale.
    """
    B, S, D = x.shape
    NH = cfg.n_heads
    dh = D // NH
    xin = apply_norm(x, bp["ln"], cfg.norm)
    conv_state = None
    if cache is not None:
        conv_state = (_conv_tail_gather(cache["conv"], cache["bt"])
                      if "bt" in cache else cache["conv"])
    c_in, new_conv = _causal_conv(xin, bp["conv_w"], bp["conv_b"], conv_state,
                                  lengths=plens)
    c_in = jax.nn.silu(c_in)
    # gate pre-activations from inputs (i,f from conv branch; z,o direct)
    wx = jnp.einsum("bsd,dg->bsg", xin.astype(jnp.float32),
                    bp["w_gates"].astype(jnp.float32))
    wc = jnp.einsum("bsd,dg->bsg", c_in.astype(jnp.float32),
                    bp["w_gates"].astype(jnp.float32))
    # use conv features for i,f; direct for z,o (xLSTM Fig. 11)
    pre = jnp.concatenate([wc[..., :2 * D], wx[..., 2 * D:]], -1) \
        + bp["b_gates"].astype(jnp.float32)
    pre = pre.reshape(B, S, 4, NH, dh)

    r = bp["r_gates"].astype(jnp.float32)  # (NH, dh, 4*dh)

    if cache is None:
        cs = jnp.zeros((B, NH, dh), jnp.float32)
        ns = jnp.zeros((B, NH, dh), jnp.float32)
        hs = jnp.zeros((B, NH, dh), jnp.float32)
        ms = jnp.full((B, NH, dh), -1e30, jnp.float32)
    else:
        cs, ns, hs, ms = cache["c"], cache["n"], cache["h"], cache["m"]

    def step(carry, xs):
        cs, ns, hs, ms = carry
        pre_t, valid_t = xs
        rec = jnp.einsum("bhd,hdg->bhg", hs, r).reshape(B, NH, 4, dh)
        rec = rec.transpose(0, 2, 1, 3)  # (B,4,NH,dh)
        g = pre_t.astype(jnp.float32) + rec
        li = g[:, 0]
        lf = jax.nn.log_sigmoid(g[:, 1])
        z = jnp.tanh(g[:, 2])
        o = jax.nn.sigmoid(g[:, 3])
        m_new = jnp.maximum(lf + ms, li)
        i_p = jnp.exp(li - m_new)
        f_p = jnp.exp(lf + ms - m_new)
        cs_n = f_p * cs + i_p * z
        ns_n = f_p * ns + i_p
        h = o * cs_n / jnp.maximum(ns_n, 1e-6)
        if valid_t is not None:  # freeze the carry across padded positions
            keep = valid_t[:, None, None]
            cs_n = jnp.where(keep, cs_n, cs)
            ns_n = jnp.where(keep, ns_n, ns)
            h_c = jnp.where(keep, h, hs)
            m_new = jnp.where(keep, m_new, ms)
            return (cs_n, ns_n, h_c, m_new), h
        return (cs_n, ns_n, h, m_new), h

    valid = None
    if plens is not None:
        valid = (jnp.arange(S)[None] < plens[:, None]).T  # (S,B)
    (cs, ns, hs, ms), hseq = jax.lax.scan(
        step, (cs, ns, hs, ms), (pre.transpose(1, 0, 2, 3, 4), valid))
    h = hseq.transpose(1, 0, 2, 3).reshape(B, S, D).astype(x.dtype)
    h = _group_norm(h, bp["gn"], NH)
    # gated up/down MLP (pf = 4/3)
    u1 = jnp.einsum("bsd,df->bsf", h, bp["w_up1"].astype(x.dtype))
    u2 = jnp.einsum("bsd,df->bsf", h, bp["w_up2"].astype(x.dtype))
    out = jnp.einsum("bsf,fd->bsd", gelu(u1) * u2,
                     bp["w_down"].astype(x.dtype))
    x = annotate(x + out, ("batch", "seq", "embed"))
    nc = None
    if cache is not None:
        nc = {"conv": new_conv, "c": cs, "n": ns, "h": hs, "m": ms}
        if "bt" in cache:
            dense = {k: nc[k] for k in ("c", "n", "h", "m")}
            if done is not None:
                dense = freeze_rows({k: cache[k] for k in dense}, dense,
                                    done)
            nc = {"conv": _conv_tail_scatter(cache["conv"], cache["bt"],
                                             new_conv, done=done),
                  "bt": cache["bt"], **dense}
        elif done is not None:
            nc = freeze_rows(cache, nc, done)
    return x, nc


# ================================================================= forward
def _run_blocks(params, x, cfg, caches=None, plens=None, done=None):
    from repro.models.common import slice_layers

    types = block_types(cfg)
    new_caches = {"m": [], "s": []} if caches is not None else None
    runs = []
    counts = {"m": 0, "s": 0}
    i = 0
    while i < len(types):
        j = i
        while j < len(types) and types[j] == types[i]:
            j += 1
        runs.append((types[i], counts[types[i]], j - i))
        counts[types[i]] += j - i
        i = j

    valid = None
    if plens is not None:
        # bucketed admission prefill: pad positions of the residual stream
        # are zeroed after every block — the mLSTM stabilizer degenerates
        # on all-masked pad queries (inf denominators), and a NaN at a pad
        # position must never reach the next block's K/V products (where
        # 0 * NaN would poison the carried state)
        valid = (jnp.arange(x.shape[1])[None] < plens[:, None])[..., None]

    for typ, start, count in runs:
        key = "m_blocks" if typ == "m" else "s_blocks"
        group = slice_layers(params[key], start, start + count)
        fn = _mlstm_block if typ == "m" else _slstm_block

        def body(carry, xs, fn=fn):
            xc = carry
            if caches is None:
                bp, cache_l = xs, None
            else:
                bp, cache_l = xs
            xc, nc = fn(xc, bp, cfg, cache=cache_l, plens=plens, done=done)
            if valid is not None:
                xc = jnp.where(valid, xc, 0.0)
            return xc, nc

        if cfg.remat == "block":
            body = jax.remat(body, prevent_cse=False)
        xs = group
        if caches is not None:
            ckey = typ
            xs = (group, slice_layers(caches[ckey], start, start + count))
        x, ncs = jax.lax.scan(body, x, xs, unroll=cfg.unroll_scans)
        if caches is not None:
            new_caches[typ].append(ncs)

    if caches is not None:
        out = {}
        for t in ("m", "s"):
            if new_caches[t]:
                out[t] = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, 0), *new_caches[t])
        return x, out
    return x


def forward(params, batch, cfg):
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"].astype(cdt)[batch["tokens"]]
    x = _run_blocks(params, x, cfg)
    x = apply_norm(x, params["final_norm"], cfg.norm)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(cdt))
    return annotate(logits, ("batch", "seq", "vocab")), {"moe_aux": 0.0}


def init_cache(cfg, batch_size, max_len, dtype=None):
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    types = block_types(cfg)
    n_m = sum(1 for t in types if t == "m")
    n_s = len(types) - n_m
    D, NH = cfg.d_model, cfg.n_heads
    di = int(cfg.proj_factor * D)
    dh = di // NH
    K = cfg.conv_width
    cache = {
        "m": {
            "conv": jnp.zeros((n_m, batch_size, K - 1, di), dtype),
            "C": jnp.zeros((n_m, batch_size, NH, dh, dh), jnp.float32),
            "n": jnp.zeros((n_m, batch_size, NH, dh), jnp.float32),
            "m": jnp.full((n_m, batch_size, NH), -1e30, jnp.float32),
        }
    }
    if n_s:
        dhs = D // NH
        cache["s"] = {
            "conv": jnp.zeros((n_s, batch_size, K - 1, D), dtype),
            "c": jnp.zeros((n_s, batch_size, NH, dhs), jnp.float32),
            "n": jnp.zeros((n_s, batch_size, NH, dhs), jnp.float32),
            "h": jnp.zeros((n_s, batch_size, NH, dhs), jnp.float32),
            "m": jnp.full((n_s, batch_size, NH, dhs), -1e30, jnp.float32),
        }
    return cache


def _forward_cached(params, batch, cfg, cache, q_offset, plens=None,
                    done=None):
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"].astype(cdt)[batch["tokens"]]
    B = x.shape[0]
    x, new_cache = _run_blocks(params, x, cfg, caches=cache, plens=plens,
                               done=done)
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

    ``batch["plens"]`` (B,) carries each row's true prompt length: pad
    positions contribute exp(-inf) = 0 to the mLSTM state with unit
    forget decay, sLSTM carries freeze in-scan, and conv tails are
    gathered at the row boundary — the returned (C, n, m, conv, ...)
    is the state after each row's REAL prompt.
    """
    batch = dict(batch)
    plens = batch.pop("plens")
    return _forward_cached(params, batch, cfg, cache, 0, plens=plens)


def decode_step_slots(params, tokens, positions, cache, cfg, done=None):
    """Continuous-batching decode: one token per slot, O(1) state per row.

    ``positions`` is accepted for protocol uniformity but unused — the
    xLSTM recurrence is position-free.  Rows flagged ``done`` FREEZE
    their entire per-slot state (C/n/m, sLSTM carries, conv tails): a
    recurrent update is irreversible, so the macro-step loop's no-op
    steps must not advance it.  Returns (logits (B, V), new_cache).
    """
    del positions
    logits, new_cache = _forward_cached(
        params, {"tokens": tokens[:, None]}, cfg, cache, 0, done=done)
    return logits[:, -1], new_cache


def _dense_state_view(cache):
    """Per-slot dense view of a (possibly paged) xlstm slot cache: paged
    conv arenas gather back to (L, B, K-1, d) through their single-block
    tables; everything else passes through.  The speculative hooks stack
    and gather THIS view — per-slot snapshots, not per-page arenas."""
    out = {}
    for gk, gv in cache.items():
        if "bt" in gv:
            n_pages = gv["conv"].shape[1]
            pid = jnp.minimum(gv["bt"][0][:, 0], n_pages - 1)
            dense = {k: v for k, v in gv.items() if k != "bt"}
            dense["conv"] = gv["conv"][:, pid]
            out[gk] = dense
        else:
            out[gk] = gv
    return out


def verify_step_slots(params, tokens, positions, cache, cfg, done=None):
    """Speculative verify for the recurrent slot layout: one fused scan of
    the single-token slot decode over the chunk, stacking the per-step
    O(1) slot state (mLSTM C/n/m, sLSTM carries, conv tails — every xlstm
    leaf is O(1)/slot, so stacking all of them is cheap) so
    ``commit_slots`` can roll every row back to its accepted boundary.
    Paged pools stack the per-slot DENSE view (conv tails gathered
    through the block table) — snapshots are per slot, never per page.
    Bit-identical to sequential decode by construction."""
    from repro.models.common import spec_verify_scan
    paged = any("bt" in g for g in cache.values())
    logits, stacked, _ = spec_verify_scan(
        decode_step_slots, params, tokens, positions, cache, cfg,
        done=done, stack_filter=_dense_state_view if paged else None)
    return logits, stacked


def commit_slots(params, tokens, positions, n_feed, cache, pending, cfg,
                 done=None):
    """Commit = gather the stacked verify states at ``n_feed - 1`` per row;
    rows with ``n_feed == 0`` or flagged ``done`` keep their pre-chunk
    state (a recurrent update cannot be re-stored, so rollback is a
    snapshot gather, not a truncation).  Paged pools gather in the dense
    per-slot view, then scatter the committed conv tails back to their
    pages (kept rows re-store their own gathered bytes; evicted rows'
    sentinel blocks drop)."""
    from repro.models.common import spec_commit_gather
    del params, tokens, positions
    if not any("bt" in g for g in cache.values()):
        return spec_commit_gather(cache, pending, n_feed, done=done)
    committed = spec_commit_gather(_dense_state_view(cache), pending,
                                   n_feed, done=done)
    out = {}
    for gk, gv in cache.items():
        grp = dict(committed[gk])
        if "bt" in gv:
            grp["conv"] = jax.vmap(_conv_tail_scatter)(
                gv["conv"], gv["bt"], grp["conv"])
            grp["bt"] = gv["bt"]
        out[gk] = grp
    return out


def serve_supported(cfg):
    """Capability probe for the continuous-batching slot-decode protocol."""
    return True, ("recurrent state (O(1) per slot: mLSTM C/n/m + conv "
                  "tails, sLSTM c/n/h/m)")


def slot_cache_layout(cfg):
    return "recurrent"


def paged_groups(cfg):
    """Slot-state protocol: the conv shift tails page (one single-entry
    block per slot — the tail has no sequence axis, so the whole K-1
    window is its page); the mLSTM C/n/m and sLSTM carries stay
    dense-per-slot (O(1) matrix/vector state, nothing to page)."""
    types = block_types(cfg)
    out = {}
    if any(t == "m" for t in types):
        out["m"] = ("slot", ("conv",))
    if any(t == "s" for t in types):
        out["s"] = ("slot", ("conv",))
    return out


def cache_specs(cfg):
    types = block_types(cfg)
    n_s = sum(1 for t in types if t == "s")
    c = {"m": {
        "conv": ("layers", "batch", None, "lru"),
        "C": ("layers", "batch", None, None, "lru"),
        "n": ("layers", "batch", None, "lru"),
        "m": ("layers", "batch", None),
    }}
    if n_s:
        c["s"] = {"conv": ("layers", "batch", None, "embed"),
                  "c": ("layers", "batch", None, None),
                  "n": ("layers", "batch", None, None),
                  "h": ("layers", "batch", None, None),
                  "m": ("layers", "batch", None, None)}
    return c


# ============================================================== param specs
def param_specs(cfg):
    types = block_types(cfg)
    n_s = sum(1 for t in types if t == "s")
    L = ("layers",)

    def norm_spec(layered=True):
        s = {"scale": (L + ("embed",)) if layered else ("embed",)}
        if cfg.norm == "ln":
            s["bias"] = s["scale"]
        return s

    specs = {
        "embed": ("vocab", "embed"),
        "m_blocks": {
            "ln": norm_spec(),
            "w_up": L + ("embed", "lru"),
            "conv_w": L + (None, "lru"),
            "conv_b": L + ("lru",),
            "w_q": L + ("lru", "lru"),
            "w_k": L + ("lru", "lru"),
            "w_v": L + ("lru", "lru"),
            "w_if": L + ("lru", None),
            "b_if": L + (None,),
            "gn": L + ("lru",),
            "w_down": L + ("lru", "embed"),
        },
        "final_norm": norm_spec(layered=False),
    }
    if n_s:
        specs["s_blocks"] = {
            "ln": norm_spec(),
            "conv_w": L + (None, "embed"),
            "conv_b": L + ("embed",),
            "w_gates": L + ("embed", "mlp"),
            "r_gates": L + (None, None, None),
            "b_gates": L + ("mlp",),
            "gn": L + ("embed",),
            "w_up1": L + ("embed", "mlp"),
            "w_up2": L + ("embed", "mlp"),
            "w_down": L + ("mlp", "embed"),
        }
    if not cfg.tie_embeddings:
        specs["head"] = ("embed", "vocab")
    return specs
