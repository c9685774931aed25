"""Plain DeepSeek-V3 reference, at one chip's share of its experts.

Written from the published description (arXiv:2412.19437 and the HF
``deepseek_v3`` config keys) in straightforward ``jax.numpy`` at float32
with ``jax.default_matmul_precision("highest")``; it imports nothing of
the program under test.  It reads the weights in the program's parameter
layout (a dict tree: ``embed``; ``dense_blocks`` and ``moe_blocks``
stacked over layers, each with ``ln1``/``ln2`` {scale} and ``attn``
{w_dq, q_norm, w_uq, w_dkv, kv_norm, w_kr, w_uk, w_uv, wo}; dense
``mlp`` {w_up, w_gate, w_down}; expert ``moe`` {w_router,
e_score_correction_bias where served, w_up, w_gate, w_down over the
experts held, shared_w_up, shared_w_gate, shared_w_down}; ``final_norm`` and ``head``), which the
benchmark fills from the seed.

The model, as the configuration file states it:
  * multi-head latent attention, not absorbed: q through the q low-rank
    projection and its RMSNorm; keys and values expanded from the
    normalized kv latent; a 64-wide rotary key shared by the heads;
  * YaRN rotary frequencies (factor, original length, beta_fast and
    beta_slow from ``rope_scaling``) and the softmax scale
    1/sqrt(nope + rope) times mscale(factor, mscale_all_dim) squared;
  * the first ``first_k_dense_replace`` layers dense (SwiGLU), the rest
    expert layers: the ``noaux_tc`` gate in float32 (sigmoid scores s,
    s + correction bias (zero where the file's ``correction_bias`` says
    "zero") ranked within the ``topk_group`` best of
    ``n_group`` groups, each group scored by its two best; weights s of
    the chosen ``num_experts_per_tok`` renormalized and scaled by
    ``routed_scaling_factor``) over all ``experts_routed_over`` experts,
    the part of the routed output that the experts held here give
    (``n_routed_experts`` of them from ``experts_held_first``), and the
    shared expert;
  * RMSNorm (``rms_norm_eps``) and an untied LM head.

Departures, each with no effect on the comparison:
  * rotary pairs are (i, i + d/2) (rotate-half), where the published
    code rotates interleaved pairs (2i, 2i+1): a fixed permutation of
    the rope columns of ``w_uq`` and ``w_kr``, which random weights do
    not see;
  * the MTP module is not on the serving path and is left out.

Weights are cast to float32 one layer at a time, inside the jitted layer,
so the reference runs beside the program's resident bf16 weights; the
head is applied in vocabulary slices; sequences are padded to a multiple
of ``SEQ_QUANTUM`` so that few lengths compile.

``precision`` selects what is computed in: "float32" (the reference),
"bfloat16" (activations and weights rounded to bf16) or "fp8" (matmul
inputs scaled per tensor into float8_e4m3, products in float32): the
controls one precision step below a float32 and a bfloat16
configuration.

The useful work (``prefill_flops`` / ``decode_flops``) is this chip's:
latent attention as absorbed decode (queries folded into the latent
space) and as non-absorbed prefill, the dense layer, the router, the
shared expert and the head once per token (once per prompt for a
prefill), and the routed experts at their expected share here:
``num_experts_per_tok * held / routed_over`` experts per token per
expert layer (8 * 8 / 256 = 0.25).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QBLOCK = 256  # query block of the attention
SEQ_QUANTUM = 512  # sequences are padded to a multiple (fewer compiles)
VOCAB_SLICE = 16384  # head columns cast to float32 at a time


def describe(c):
    """Plain description of the model from its configuration file."""
    if c.get("model_type") != "deepseek_v3":
        raise ValueError(
            f"no reference for model_type {c.get('model_type')!r}")
    if (c["scoring_func"], c["topk_method"]) != ("sigmoid", "noaux_tc"):
        raise ValueError("the reference gate is sigmoid noaux_tc")
    rs = c["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError("the reference rope is YaRN")
    return dict(
        n_layers=c["num_hidden_layers"], n_dense=c["first_k_dense_replace"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        q_lora=c["q_lora_rank"], kv_lora=c["kv_lora_rank"],
        nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
        v_dim=c["v_head_dim"], d_ff=c["intermediate_size"],
        expert_ff=c["moe_intermediate_size"],
        routed_over=c["experts_routed_over"], held=c["n_routed_experts"],
        held_first=c["experts_held_first"],
        top_k=c["num_experts_per_tok"], n_group=c["n_group"],
        topk_group=c["topk_group"], norm_topk=c["norm_topk_prob"],
        routed_scale=float(c["routed_scaling_factor"]),
        n_shared=c["n_shared_experts"], vocab=c["vocab_size"],
        norm_eps=c["rms_norm_eps"], rope_theta=float(c["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        correction_bias=c["correction_bias"] != "zero",
        tied=c["tie_word_embeddings"], dtype=c["torch_dtype"])


def program_widths(spec):
    """The fields the program's configuration must hold."""
    return dict(
        n_layers=spec["n_layers"], moe_layer_start=spec["n_dense"],
        d_model=spec["d_model"], n_heads=spec["n_heads"], mla=True,
        q_lora_rank=spec["q_lora"], kv_lora_rank=spec["kv_lora"],
        qk_nope_dim=spec["nope"], qk_rope_dim=spec["rope"],
        v_head_dim=spec["v_dim"], d_ff=spec["d_ff"], moe=True,
        expert_d_ff=spec["expert_ff"], n_experts=spec["routed_over"],
        n_experts_held=spec["held"], experts_offset=spec["held_first"],
        top_k=spec["top_k"], n_group=spec["n_group"],
        topk_group=spec["topk_group"], router_score="sigmoid",
        router_bias=spec["correction_bias"],
        routed_scale=spec["routed_scale"],
        n_shared_experts=spec["n_shared"], vocab_size=spec["vocab"],
        rope_theta=spec["rope_theta"], yarn_factor=spec["yarn_factor"],
        yarn_original_max_pos=spec["yarn_original"],
        yarn_beta_fast=spec["yarn_beta_fast"],
        yarn_beta_slow=spec["yarn_beta_slow"],
        yarn_mscale=spec["yarn_mscale"],
        yarn_mscale_all_dim=spec["yarn_mscale_all_dim"],
        tie_embeddings=spec["tied"], param_dtype=spec["dtype"])


# ------------------------------------------------------------ useful work
def _routed_share(spec):
    """Expected routed experts computed here per token per expert layer."""
    return spec["top_k"] * spec["held"] / spec["routed_over"]


def _mlp_params(spec):
    """Weights a token passes through outside attention: dense MLPs,
    router, shared and (expected) routed experts, the head excluded."""
    D = spec["d_model"]
    n_moe = spec["n_layers"] - spec["n_dense"]
    Fe = spec["expert_ff"]
    per_moe = (D * spec["routed_over"] + 3 * D * Fe * spec["n_shared"]
               + 3 * D * Fe * _routed_share(spec))
    return spec["n_dense"] * 3 * D * spec["d_ff"] + n_moe * per_moe


def _mla_params(spec):
    """Latent attention's weights per token: the projections, and H x dn x
    R + H x R x dv more, whether absorbed (q_nope into the latent space,
    the latent output back out) or not (each key and value expanded from
    its latent)."""
    D, H, R = spec["d_model"], spec["n_heads"], spec["kv_lora"]
    dn, dr, dv, ql = spec["nope"], spec["rope"], spec["v_dim"], \
        spec["q_lora"]
    proj = D * ql + ql * H * (dn + dr) + D * R + D * dr + H * dv * D
    return proj + H * dn * R + H * R * dv


def prefill_flops(spec, n):
    """Forward over an ``n``-token prompt (causal, not absorbed), with the
    head at its last position."""
    H = spec["n_heads"]
    keys = n * (n + 1) // 2
    attn = 2 * H * (spec["nope"] + spec["rope"] + spec["v_dim"]) * keys
    return (2 * n * (spec["n_layers"] * _mla_params(spec)
                     + _mlp_params(spec))
            + spec["n_layers"] * attn + 2 * spec["d_model"] * spec["vocab"])


def decode_flops(spec, kv_len):
    """Forward of one token that attends ``kv_len`` cached latents
    (absorbed: scores over kv_lora + rope, values over kv_lora)."""
    H, R = spec["n_heads"], spec["kv_lora"]
    attn = 2 * H * (2 * R + spec["rope"]) * kv_len
    return (2 * (spec["n_layers"] * _mla_params(spec)
                 + _mlp_params(spec) + spec["d_model"] * spec["vocab"])
            + spec["n_layers"] * attn)


# ------------------------------------------------------------- arithmetic
def _quant8(x):
    """Round to float8_e4m3 with one per-tensor scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _dt(precision):
    return jnp.bfloat16 if precision == "bfloat16" else jnp.float32


def _mm(a, w, precision):
    if precision == "fp8":
        return _quant8(a.astype(jnp.float32)) @ _quant8(w.astype(
            jnp.float32))
    dt = _dt(precision)
    return jnp.matmul(a.astype(dt), w.astype(dt), preferred_element_type=dt)


def _norm(x, scale, spec, precision):
    """RMSNorm."""
    dt = _dt(precision)
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + spec["norm_eps"])
    return (y * scale.astype(jnp.float32)).astype(dt)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(spec):
    """YaRN inverse frequencies of the rotary dims, in float64: the
    published interpolation (frequency / factor) below the beta_slow band,
    extrapolation (the plain frequency) above beta_fast, a linear ramp
    over the dims between."""
    d, base = spec["rope"], spec["rope_theta"]

    def dim_of(n_rot):
        return d * math.log(spec["yarn_original"] / (n_rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(spec["yarn_beta_fast"])), 0)
    high = min(math.ceil(dim_of(spec["yarn_beta_slow"])), d - 1)
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / spec["yarn_factor"]
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return inter * ramp + extra * (1.0 - ramp)


def _rope(x, pos, spec):
    """Rotate-half YaRN rotary embedding. x: (S, ..., d)."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(spec), jnp.float32)
    m = _mscale(spec["yarn_factor"], spec["yarn_mscale"]) / _mscale(
        spec["yarn_factor"], spec["yarn_mscale_all_dim"])
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., :d // 2].astype(jnp.float32), \
        x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v, scale, precision):
    """Causal attention. q, k: (S, H, dq); v: (S, H, dv)."""
    S = q.shape[0]
    dt = _dt(precision)
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK, 0)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        if precision == "fp8":
            s = jnp.einsum("qhd,khd->hqk", _quant8(qb), _quant8(k))
        else:
            s = jnp.einsum("qhd,khd->hqk", qb.astype(dt), k.astype(dt),
                           preferred_element_type=dt)
        s = s.astype(jnp.float32) * scale
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(dt)
        if precision == "fp8":
            return jnp.einsum("hqk,khd->qhd", _quant8(w), _quant8(v))
        return jnp.einsum("hqk,khd->qhd", w, v.astype(dt),
                          preferred_element_type=dt)

    out = jax.lax.map(block, jnp.arange(S // QBLOCK))
    return out.reshape(S, -1)


def _mla(x, a, pos, spec, precision):
    S = x.shape[0]
    H, dn, dr, dv = spec["n_heads"], spec["nope"], spec["rope"], \
        spec["v_dim"]
    cq = _norm(_mm(x, a["w_dq"], precision), a["q_norm"], spec, precision)
    q = _mm(cq, a["w_uq"], precision).reshape(S, H, dn + dr)
    q_rope = _rope(q[..., dn:], pos, spec)
    ckv = _norm(_mm(x, a["w_dkv"], precision), a["kv_norm"], spec,
                precision)
    kr = _rope(_mm(x, a["w_kr"], precision), pos, spec)
    k_nope = _mm(ckv, a["w_uk"], precision).reshape(S, H, dn)
    v = _mm(ckv, a["w_uv"], precision).reshape(S, H, dv)
    qf = jnp.concatenate([q[..., :dn], q_rope], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(
        kr[:, None, :], (S, H, dr)).astype(k_nope.dtype)], -1)
    m = _mscale(spec["yarn_factor"], spec["yarn_mscale_all_dim"])
    scale = (dn + dr) ** -0.5 * m * m
    return _mm(_attention(qf, kf, v, scale, precision), a["wo"], precision)


def _swiglu(x, up, gate, down, precision):
    g = _mm(x, gate, precision).astype(jnp.float32)
    u = _mm(x, up, precision).astype(jnp.float32)
    return _mm((jax.nn.silu(g) * u).astype(x.dtype), down, precision)


def gate(x, w_router, bias, spec, precision="float32"):
    """The noaux_tc gate over all routed experts.  x: (S, D) ->
    (weights (S, K) float32, expert ids (S, K))."""
    S = x.shape[0]
    E, G = spec["routed_over"], spec["n_group"]
    s = jax.nn.sigmoid(_mm(x, w_router, precision).astype(jnp.float32))
    c = s if bias is None else s + bias.astype(jnp.float32)
    grp = jnp.sort(c.reshape(S, G, E // G), axis=-1)[..., -2:].sum(-1)
    keep = jax.lax.top_k(grp, spec["topk_group"])[1]
    kept = (jnp.arange(G)[None, :, None] == keep[:, None, :]).any(-1)
    c = jnp.where(jnp.repeat(kept, E // G, axis=1), c, -jnp.inf)
    idx = jax.lax.top_k(c, spec["top_k"])[1]
    w = jnp.take_along_axis(s, idx, axis=1)
    if spec["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return w * spec["routed_scale"], idx


def _experts(x, m, spec, precision):
    """Routed output of the experts held, plus the shared expert."""
    w, idx = gate(x, m["w_router"], m.get("e_score_correction_bias"), spec,
                  precision)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(spec["held"]):
        we = jnp.where(idx == spec["held_first"] + e, w, 0.0).sum(-1)
        y = y + we[:, None] * _swiglu(x, m["w_up"][e], m["w_gate"][e],
                                      m["w_down"][e], precision).astype(
                                          jnp.float32)
    shared = _swiglu(x, m["shared_w_up"], m["shared_w_gate"],
                     m["shared_w_down"], precision)
    return (y + shared.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("spec", "precision", "moe"))
def _layer(x, group, i, pos, spec, precision, moe):
    """Layer ``i`` of a stacked group (its weights sliced and cast here)."""
    spec = dict(spec)
    dt = _dt(precision)
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), group)
    h = _norm(x, p["ln1"]["scale"], spec, precision)
    x = (x + _mla(h, p["attn"], pos, spec, precision)).astype(dt)
    h = _norm(x, p["ln2"]["scale"], spec, precision)
    if moe:
        f = _experts(h, p["moe"], spec, precision)
    else:
        f = _swiglu(h, p["mlp"]["w_up"], p["mlp"]["w_gate"],
                    p["mlp"]["w_down"], precision)
    return (x + f).astype(dt)


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed(embed, tokens, precision):
    return embed[tokens].astype(_dt(precision))


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _head(x, final_norm, head, spec, precision):
    spec = dict(spec)
    h = _norm(x, final_norm["scale"], spec, precision)
    V = head.shape[1]
    return jnp.concatenate(
        [_mm(h, head[:, i:i + VOCAB_SLICE].astype(jnp.float32),
             precision).astype(jnp.float32)
         for i in range(0, V, VOCAB_SLICE)], axis=-1)


def logits_at(spec, params, tokens, positions, precision="float32"):
    """Logits (len(positions), vocab) in float32 of the prefix ending at
    each position of one sequence ``tokens`` (causal)."""
    tokens = np.asarray(tokens, np.int32)
    S = len(tokens)
    padded = -(-S // SEQ_QUANTUM) * SEQ_QUANTUM
    toks = np.zeros((padded,), np.int32)
    toks[:S] = tokens
    key = tuple(sorted(spec.items()))
    pos = jnp.arange(padded, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(toks), precision)
        for layer in range(spec["n_layers"]):
            moe = layer >= spec["n_dense"]
            group = params["moe_blocks" if moe else "dense_blocks"]
            i = layer - spec["n_dense"] if moe else layer
            x = _layer(x, group, jnp.int32(i), pos, key, precision, moe)
        x = x[jnp.asarray(np.asarray(positions, np.int32))]
        return _head(x, params["final_norm"], params["head"], key,
                     precision)
