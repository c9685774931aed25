"""Plain Qwen3 decoder reference, a second model family for the tests.

The discovery test copies this file into a benchmark root as
``bench/reference/qwen3.py``: a family is a reference module, a
configuration file and nothing else.  It keeps the interface of
``bench.common.load_reference`` and borrows the arithmetic of the Qwen2
reference; what Qwen3 adds is an RMSNorm over each head of q and k
before RoPE, a ``head_dim`` of its own (not ``hidden_size /
num_attention_heads``) and no q/k/v biases.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.reference import transformer as base


def describe(c):
    if c.get("model_type") != "qwen3":
        raise ValueError(
            f"no reference for model_type {c.get('model_type')!r}")
    return dict(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        norm_eps=c["rms_norm_eps"], tied=c["tie_word_embeddings"],
        rope_theta=c["rope_theta"], max_pos=c["max_position_embeddings"],
        dtype=c["torch_dtype"])


def program_widths(spec):
    return dict(n_layers=spec["n_layers"], d_model=spec["d_model"],
                n_heads=spec["n_heads"], n_kv_heads=spec["n_kv_heads"],
                head_dim=spec["head_dim"], d_ff=spec["d_ff"],
                vocab_size=spec["vocab"], param_dtype=spec["dtype"],
                qk_norm=True, qkv_bias=False)


def prefill_flops(spec, n):
    return flops.prefill_flops(spec, n)


def decode_flops(spec, kv_len):
    return flops.decode_flops(spec, kv_len)


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _layer(x, p, pos, spec, precision):
    spec = dict(spec)
    dt = base._dt(precision)
    H, KV, hd = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    S = x.shape[0]
    a = p["attn"]
    h = base._norm(x, p["ln1"], spec, precision)
    q = base._mm(h, a["wq"], precision).reshape(S, H, hd)
    k = base._mm(h, a["wk"], precision).reshape(S, KV, hd)
    v = base._mm(h, a["wv"], precision).reshape(S, KV, hd)
    q = base._norm(q, {"scale": a["q_norm"]}, spec, precision)
    k = base._norm(k, {"scale": a["k_norm"]}, spec, precision)
    q = base._rope(q, pos, spec["rope_theta"])
    k = base._rope(k, pos, spec["rope_theta"])
    o = base._attention(q, k, v, precision).reshape(S, H * hd)
    x = (x + base._mm(o, a["wo"], precision)).astype(dt)
    h = base._norm(x, p["ln2"], spec, precision)
    m = p["mlp"]
    g = base._mm(h, m["w_gate"], precision)
    u = base._mm(h, m["w_up"], precision)
    f = (jax.nn.silu(g.astype(jnp.float32)) *
         u.astype(jnp.float32)).astype(dt)
    return (x + base._mm(f, m["w_down"], precision)).astype(dt)


def logits_at(spec, params, tokens, positions, precision="float32"):
    tokens = np.asarray(tokens, np.int32)
    padded = -(-len(tokens) // base.SEQ_QUANTUM) * base.SEQ_QUANTUM
    toks = np.zeros((padded,), np.int32)
    toks[:len(tokens)] = tokens
    key = tuple(sorted(spec.items()))
    pos = jnp.arange(padded, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = base._embed(params, jnp.asarray(toks), precision)
        for layer in range(spec["n_layers"]):
            p = jax.tree.map(lambda a: a[layer], params["dense_blocks"])
            x = _layer(x, p, pos, key, precision)
        x = x[jnp.asarray(np.asarray(positions, np.int32))]
        return base._head(params, x, key, precision)
