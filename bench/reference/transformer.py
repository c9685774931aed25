"""Plain decoder reference.

Written from the published descriptions, in straightforward ``jax.numpy``
at float32 with ``jax.default_matmul_precision("highest")``; it imports
nothing of the program under test.  It reads the weights in the
program's parameter layout (a dict tree: ``embed``, ``dense_blocks``
stacked over layers with ``ln1``, ``ln2``, ``attn`` {wq, wk, wv, wo,
bq, bk, bv} and ``mlp`` {w_up, w_gate, w_down}, ``final_norm`` and, if
untied, ``head``), which the benchmark fills from the seed.

Features of the Qwen2 family, as the configuration file states them:
  * rotary positions (rotate-half, theta from the file);
  * RMSNorm (``rms_norm_eps``);
  * SwiGLU MLP (silu gate);
  * biases on q, k, v and a tied or untied LM head;
  * causal softmax attention, one query block at a time.

``precision`` selects what is computed in: "float32" (the reference),
"bfloat16" (every activation and weight rounded to bf16), or "fp8"
(matmul inputs scaled per tensor into float8_e4m3, products in float32)
— the latter two are the controls one precision step below a float32
and a bfloat16 configuration.

The module keeps the interface of ``bench.common.load_reference``; its
useful work is that of ``bench/flops.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops

QBLOCK = 512  # query block of the attention
SEQ_QUANTUM = 512  # sequences are padded to a multiple (fewer compiles)


def describe(c):
    """Plain description of a decoder from its configuration file (HF
    Qwen2 keys)."""
    if c.get("model_type") != "qwen2":
        raise ValueError(
            f"no reference for model_type {c.get('model_type')!r}")
    D, H = c["hidden_size"], c["num_attention_heads"]
    return dict(
        n_layers=c["num_hidden_layers"], d_model=D, n_heads=H,
        n_kv_heads=c["num_key_value_heads"], head_dim=D // H,
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        norm_eps=c["rms_norm_eps"], tied=c["tie_word_embeddings"],
        rope_theta=c["rope_theta"], max_pos=c["max_position_embeddings"],
        dtype=c["torch_dtype"])


def program_widths(spec):
    """The widths the program's configuration must hold."""
    return dict(n_layers=spec["n_layers"], d_model=spec["d_model"],
                n_heads=spec["n_heads"], n_kv_heads=spec["n_kv_heads"],
                head_dim=spec["head_dim"], d_ff=spec["d_ff"],
                vocab_size=spec["vocab"], param_dtype=spec["dtype"])


def prefill_flops(spec, n):
    return flops.prefill_flops(spec, n)


def decode_flops(spec, kv_len):
    return flops.decode_flops(spec, kv_len)


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


def _norm(x, p, spec, precision):
    """RMSNorm."""
    dt = _dt(precision)
    x = x.astype(dt)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + spec["norm_eps"]).astype(dt)
    return y * p["scale"].astype(dt)


def _rope(x, pos, theta):
    """Rotate-half rotary embedding. x: (S, H, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2].astype(jnp.float32), \
        x[..., hd // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v, precision):
    """Causal attention. q: (S, H, hd), k/v: (S, KV, hd)."""
    S, H, hd = q.shape
    KV = k.shape[1]
    dt = _dt(precision)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK, 0)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        if precision == "fp8":
            s = jnp.einsum("qhd,khd->hqk", _quant8(qb), _quant8(k))
        else:
            s = jnp.einsum("qhd,khd->hqk", qb.astype(dt), k.astype(dt),
                           preferred_element_type=dt)
        s = s.astype(jnp.float32) / math.sqrt(hd)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(dt)
        if precision == "fp8":
            return jnp.einsum("hqk,khd->qhd", _quant8(w), _quant8(v))
        return jnp.einsum("hqk,khd->qhd", w, v.astype(dt),
                          preferred_element_type=dt)

    out = jax.lax.map(block, jnp.arange(S // QBLOCK))
    return out.reshape(S, H, hd)


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _layer(x, p, pos, spec, precision):
    spec = dict(spec)
    dt = _dt(precision)
    H, KV, hd = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    S = x.shape[0]
    a = p["attn"]
    h = _norm(x, p["ln1"], spec, precision)
    q = _mm(h, a["wq"], precision)
    k = _mm(h, a["wk"], precision)
    v = _mm(h, a["wv"], precision)
    q = q + a["bq"].astype(q.dtype)
    k = k + a["bk"].astype(k.dtype)
    v = v + a["bv"].astype(v.dtype)
    q, k, v = (q.reshape(S, H, hd), k.reshape(S, KV, hd),
               v.reshape(S, KV, hd))
    q = _rope(q, pos, spec["rope_theta"])
    k = _rope(k, pos, spec["rope_theta"])
    o = _attention(q, k, v, precision).reshape(S, H * hd)
    x = (x + _mm(o, a["wo"], precision)).astype(dt)
    h = _norm(x, p["ln2"], spec, precision)
    m = p["mlp"]
    g = _mm(h, m["w_gate"], precision)
    u = _mm(h, m["w_up"], precision)
    f = (jax.nn.silu(g.astype(jnp.float32)) *
         u.astype(jnp.float32)).astype(dt)
    return (x + _mm(f, m["w_down"], precision)).astype(dt)


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed(params, tokens, precision):
    return params["embed"][tokens].astype(_dt(precision))


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _head(params, x, spec, precision):
    spec = dict(spec)
    h = _norm(x, params["final_norm"], spec, precision)
    w = params["embed"].T if spec["tied"] else params["head"]
    return _mm(h, w, precision).astype(jnp.float32)


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
        x = _embed(params, jnp.asarray(toks), precision)
        blocks = params["dense_blocks"]
        for layer in range(spec["n_layers"]):
            p = jax.tree.map(lambda a: a[layer], blocks)
            x = _layer(x, p, pos, key, precision)
        x = x[jnp.asarray(np.asarray(positions, np.int32))]
        return _head(params, x, key, precision)
