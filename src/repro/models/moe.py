"""Mixture-of-Experts: a dropless layer over the experts held, and
GSPMD-style capacity dispatch for the training forward.

The dropless layer (``dropless_moe``) is what every serving path runs.
A chip holds ``cfg.n_experts_held`` experts starting at
``cfg.experts_offset`` (all of them by default); the gate routes every
token over all ``cfg.n_experts``, and the layer computes, with no drops,
the part of the result that the held experts give: token-expert pairs
sorted by expert and run through grouped matmuls
(``jax.lax.ragged_dot``), plus the shared expert once.  A row's output
never depends on the other rows.  What absent experts would add is left
out; on a chip of an expert-parallel deployment the exchange would bring
it.  The gate follows DeepSeek-V3's ``noaux_tc`` (arXiv:2412.19437):
sigmoid scores, a correction bias that only steers selection, group-
limited selection and a routed scale; softmax scoring is the plain top-k.

The capacity layer (``moe_mlp``), the training forward's: the same gate,
its top-k realized as the classic one-hot dispatch/combine einsum
formulation (GShard/Switch, arXiv:2006.16668): the
expert axis is sharded over the ``model`` mesh axis (expert parallelism) and
the partitioner inserts the all-to-alls on the (groups, experts, capacity, d)
dispatched tensor automatically.  Memory of the dispatch tensors is
O(tokens * E * C / (dp * ep)) per device — checked against v5e HBM in the
roofline report.

It supports softmax top-k (Switch/Mixtral/phi-3.5-MoE) and sigmoid
scoring with top-k renormalization + shared experts, auxiliary
load-balance loss, capacity-factor token dropping.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import annotate
from repro.models.common import trunc_normal
from repro.serve import tracing


def dispatch_combine(weights, idx, n_experts, capacity,
                     dtype=jnp.float32):
    """Build dispatch (bool) and combine tensors, (B,S,E,C) in ``dtype``.

    Position-in-expert via cumulative sum over the flattened (S) token axis
    per batch group (groups == batch rows), tokens over capacity are dropped
    (standard capacity-factor semantics).
    """
    B, S, K = idx.shape
    onehot = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)  # (B,S,K,E)
    # NOTE: position-in-expert cumsum stays f32 (exact small integers);
    # the big (B,S,E,C) one-hots downstream may be cast via
    # cfg.moe_dispatch_dtype (bf16 holds integers < 257 exactly, and
    # capacities here are < 2^8, so bf16 dispatch is lossless for disp and
    # rounds only combine *weights*).
    # priority: lower k first, then earlier tokens
    flat = onehot.transpose(0, 2, 1, 3).reshape(B, K * S, n_experts)
    pos = jnp.cumsum(flat, axis=1) - flat  # (B, K*S, E) position in expert
    pos = pos.reshape(B, K, S, n_experts).transpose(0, 2, 1, 3)  # (B,S,K,E)
    keep = (pos < capacity) * onehot
    # a token routes to a given expert at most once => reduce over K *before*
    # expanding the capacity one-hot (keeps peak tensor at (B,S,E,C), never
    # (B,S,K,E,C)).
    keep_e = keep.sum(2)  # (B,S,E) in {0,1}
    pos_e = (pos * keep).sum(2)  # (B,S,E)
    w_e = (weights[..., None] * keep).sum(2)  # (B,S,E)
    cap_oh = jax.nn.one_hot(pos_e.astype(jnp.int32), capacity,
                            dtype=dtype)  # (B,S,E,C)
    disp = keep_e[..., None].astype(dtype) * cap_oh
    comb = w_e[..., None].astype(dtype) * cap_oh
    return disp, comb


MOE_GROUP_SIZE = 512  # dispatch-group tokens (GShard-style): bounds the
#                       (G, S_g, E, C) one-hot at S_g^2 * K * cf per group


def moe_mlp(x, p, cfg):
    """Routed-experts MLP.  x: (B,S,D).

    Routing is the dropless layer's gate (``router_scores``, ``select``),
    so where capacity drops no token the two compute one model.

    Tokens are re-grouped into dispatch groups of ``MOE_GROUP_SIZE`` before
    the capacity one-hot is built: the dispatch/combine tensors are then
    (G, S_g, E, C) with C = S_g*K/E*cf, i.e. O(S_g * K * cf) per token
    instead of O(S * K * cf) — the difference between 10s of GB and 10s of
    TB at deepseek scale.  Capacity (and dropping) applies per group, the
    standard GShard/Switch semantics.

    p: w_router (D,E); experts: w_up/w_gate (E,D,F), w_down (E,F,D);
       optional shared expert: shared_w_up/gate/down (D,Fs)/(Fs,D).
    Returns (y, aux_loss).
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    if cfg.n_experts_held != E:
        raise ValueError(
            f"{cfg.name!r} holds {cfg.n_experts_held} of {E} experts: "
            "only the dropless serving layer computes a share")
    s = router_scores(x.reshape(B * S, D), p, cfg)
    weights, idx = select(s, p, cfg)
    aux = load_balance_loss(s, idx, cfg)
    sg = min(MOE_GROUP_SIZE, S) if S > 1 else 1
    assert S % sg == 0, (S, sg)
    G = B * (S // sg)
    xg = x.reshape(G, sg, D)
    wg = weights.reshape(G, sg, K)
    ig = idx.reshape(G, sg, K)

    capacity = max(int(sg * K / E * cfg.capacity_factor), 1)
    ddt = jnp.dtype(cfg.moe_dispatch_dtype)
    disp, comb = dispatch_combine(wg, ig, E, capacity, dtype=ddt)
    disp = annotate(disp.astype(x.dtype),
                    ("moe_group", "seq", "experts", None))
    comb = annotate(comb.astype(ddt),
                    ("moe_group", "seq", "experts", None))

    xe = jnp.einsum("gsec,gsd->gecd", disp, xg)
    xe = annotate(xe, ("moe_group", "experts", None, "embed"))
    h = jnp.einsum("gecd,edf->gecf", xe, p["w_up"].astype(x.dtype))
    g = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"].astype(x.dtype))
    h = jax.nn.silu(g) * h
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(x.dtype))
    ye = annotate(ye, ("moe_group", "experts", None, "embed"))
    y = jnp.einsum("gsec,gecd->gsd", comb.astype(x.dtype), ye)
    y = y.reshape(B, S, D)

    if "shared_w_up" in p:
        y = y + _shared(x, p)
    return y, aux


MOE_CHUNK = 2048  # tokens per grouped-matmul pass of the dropless layer
EXPERT_WEIGHTS = ("w_up", "w_gate", "w_down")


def router_scores(x, p, cfg):
    """x: (T, D) -> router scores (T, E) in float32: sigmoid or softmax
    of x W_r."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["w_router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_score == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def select(s, p, cfg):
    """Top-k of router scores s (T, E) -> (weights (T, K) f32, expert
    ids (T, K) int32).  Selection ranks s + the correction bias (where
    ``cfg.router_bias``) among the ``cfg.topk_group`` best of
    ``cfg.n_group`` groups (with sigmoid scores, as DeepSeek-V3's
    ``noaux_tc``, a group scores the sum of its two best; with softmax,
    its best); weights are the selected s renormalized and scaled by
    ``cfg.routed_scale``.
    """
    T = s.shape[0]
    E, K, G = cfg.n_experts, cfg.top_k, cfg.n_group
    if cfg.router_bias:
        c = s + p["e_score_correction_bias"].astype(jnp.float32)
    else:
        c = s
    if G > 1:
        grouped = c.reshape(T, G, E // G)
        score = (jax.lax.top_k(grouped, 2)[0].sum(-1)
                 if cfg.router_score == "sigmoid" else grouped.max(-1))
        keep = jax.lax.top_k(score, cfg.topk_group)[1]
        kept = jnp.zeros((T, G), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        c = jnp.where(jnp.repeat(kept, E // G, axis=1), c, -jnp.inf)
    idx = jax.lax.top_k(c, K)[1]
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / jnp.clip(w.sum(-1, keepdims=True), 1e-20) * cfg.routed_scale
    return w, idx.astype(jnp.int32)


def gate(x, p, cfg):
    """Top-k routing of x (T, D) over all ``cfg.n_experts``, in float32:
    ``select`` over ``router_scores``."""
    return select(router_scores(x, p, cfg), p, cfg)


def load_balance_loss(s, idx, cfg):
    """Switch aux loss E * sum_e f_e * p_e / K over T tokens: f the share
    routed to each expert (before capacity), p its mean probability
    (sigmoid scores renormalized per token)."""
    E = cfg.n_experts
    probs = s / jnp.clip(s.sum(-1, keepdims=True), 1e-9)
    f = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1).mean(0)
    return E * jnp.sum(f * probs.mean(0)) / cfg.top_k


def _held_part(x, live, p, cfg):
    """The held experts' part of the routed output for T tokens.

    x: (T, D); live: (T,) bool (rows whose pairs are computed).
    Pairs routed to a held expert are sorted by expert and run through
    grouped matmuls; the rest stay past the groups' end and are never
    computed.  The expert weights may hold several layers' experts
    (G, ...), G a multiple of the held count: this layer's are groups
    ``p["group0"]`` on, and the other groups stay empty.  Returns
    (y (T, D), [pairs computed, held experts hit]).
    """
    T, D = x.shape
    K, H = cfg.top_k, cfg.n_experts_held
    M = T * K
    with tracing.scope("moe.route"):
        w, idx = gate(x, p, cfg)
        e = idx - cfg.experts_offset
        held = (e >= 0) & (e < H) & live[:, None]
        key = jnp.where(held, e, H).reshape(M)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((H + 1,), jnp.int32).at[key].add(1)[:H]
        groups = sizes
        if p["w_up"].shape[0] != H:
            groups = jax.lax.dynamic_update_slice(
                jnp.zeros((p["w_up"].shape[0],), jnp.int32), sizes,
                (p.get("group0", 0),))
        xs = x[order // K]
    with tracing.scope("moe.experts"):
        g = jax.lax.ragged_dot(xs, p["w_gate"].astype(x.dtype), groups)
        u = jax.lax.ragged_dot(xs, p["w_up"].astype(x.dtype), groups)
        ys = jax.lax.ragged_dot(jax.nn.silu(g) * u,
                                p["w_down"].astype(x.dtype), groups)
        # rows past the groups are not written by the grouped matmul
        n = sizes.sum()
        ys = jnp.where((jnp.arange(M) < n)[:, None], ys, 0)
        back = jnp.zeros((M,), jnp.int32).at[order].set(
            jnp.arange(M, dtype=jnp.int32))
        y = jnp.einsum("tk,tkd->td", jnp.where(held, w, 0.0),
                       ys[back].reshape(T, K, D).astype(jnp.float32))
    return y.astype(x.dtype), jnp.stack([n, (sizes > 0).sum()])


def dropless_moe(x, p, cfg, live=None):
    """The dropless expert layer over the experts held.  x: (B, S, D);
    live: (B, S) bool or None — rows whose routed pairs are computed
    (the rest, idle slots and prompt padding, get only the shared
    expert).  Tokens run through the grouped matmuls ``MOE_CHUNK`` at a
    time, which bounds the sorted (tokens x top_k, D) buffer.

    Returns (y, stats): stats (2,) int32 = [token-expert pairs computed,
    held experts that got at least one pair], summed over chunks.
    """
    B, S, D = x.shape
    T = B * S
    n = min(T, MOE_CHUNK)
    pad = -T % n
    xt = jnp.pad(x.reshape(T, D), ((0, pad), (0, 0)))
    lv = jnp.ones((T,), bool) if live is None else live.reshape(T)
    lv = jnp.pad(lv, (0, pad))
    if T + pad == n:
        y, stats = _held_part(xt, lv, p, cfg)
    else:
        y, stats = jax.lax.map(
            lambda a: _held_part(a[0], a[1], p, cfg),
            (xt.reshape(-1, n, D), lv.reshape(-1, n)))
        y, stats = y.reshape(-1, D), stats.sum(0)
    y = y[:T].reshape(B, S, D)
    if "shared_w_up" in p:
        with tracing.scope("moe.shared"):
            y = y + _shared(x, p)
    return y, stats


def _shared(x, p):
    hs = jnp.einsum("bsd,df->bsf", x, p["shared_w_up"].astype(x.dtype))
    gs = jnp.einsum("bsd,df->bsf", x, p["shared_w_gate"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gs) * hs,
                      p["shared_w_down"].astype(x.dtype))


def init_moe(keys, cfg, *, layers, dtype=jnp.float32, std=0.02):
    D, F, E = cfg.d_model, cfg.expert_d_ff, cfg.n_experts_held

    def shp(*s):
        return s if layers is None else (layers, *s)

    p = {
        "w_router": trunc_normal(next(keys), shp(D, cfg.n_experts), std,
                                 dtype),
        "w_up": trunc_normal(next(keys), shp(E, D, F), std, dtype),
        "w_gate": trunc_normal(next(keys), shp(E, D, F), std, dtype),
        "w_down": trunc_normal(next(keys), shp(E, F, D), std, dtype),
    }
    if cfg.router_bias:
        p["e_score_correction_bias"] = jnp.zeros(shp(cfg.n_experts),
                                                 jnp.float32)
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        p["shared_w_up"] = trunc_normal(next(keys), shp(D, Fs), std, dtype)
        p["shared_w_gate"] = trunc_normal(next(keys), shp(D, Fs), std, dtype)
        p["shared_w_down"] = trunc_normal(next(keys), shp(Fs, D), std, dtype)
    return p


def moe_specs(cfg, layers=True):
    L = ("layers",) if layers else ()
    s = {
        "w_router": L + ("embed", None),
        "w_up": L + ("experts", "embed", "expert_mlp"),
        "w_gate": L + ("experts", "embed", "expert_mlp"),
        "w_down": L + ("experts", "expert_mlp", "embed"),
    }
    if cfg.router_bias:
        s["e_score_correction_bias"] = L + (None,)
    if cfg.n_shared_experts:
        s["shared_w_up"] = L + ("embed", "mlp")
        s["shared_w_gate"] = L + ("embed", "mlp")
        s["shared_w_down"] = L + ("mlp", "embed")
    return s
