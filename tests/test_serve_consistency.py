"""Prefill+decode must reproduce teacher-forced forward logits.

This is the core serving invariant: running the prompt through ``prefill``
and then stepping ``decode_step`` token by token must give the same logits
as one full ``forward`` pass (up to accumulation-order noise).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, get_config
from repro.models import get_family

# 8 unjitted decode steps after the prefill: each eager step costs real
# dispatch time, and 8 steps already cross every cache-write boundary the
# 16-step sweep did (tier-1 time audit)
B, S = 2, 16
PROMPT = 8


def _run(cfg, atol=2e-4, tweak=None):
    fam = get_family(cfg)
    rng = jax.random.PRNGKey(0)
    params = fam.init(rng, cfg)
    if tweak is not None:
        params = tweak(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)

    full_logits, _ = fam.forward(params, {"tokens": tokens}, cfg)

    cache = fam.init_cache(cfg, B, S)
    logits_p, cache = fam.prefill(params, {"tokens": tokens[:, :PROMPT]},
                                  cfg, cache)
    np.testing.assert_allclose(
        np.asarray(logits_p, np.float32),
        np.asarray(full_logits[:, PROMPT - 1], np.float32),
        atol=atol, rtol=1e-3)

    for t in range(PROMPT, S):
        logits_t, cache = fam.decode_step(params, tokens[:, t - 1] * 0 +
                                          tokens[:, t], jnp.int32(t), cache,
                                          cfg)
        # feed ground-truth token t, compare against forward position t
        np.testing.assert_allclose(
            np.asarray(logits_t, np.float32),
            np.asarray(full_logits[:, t], np.float32),
            atol=atol, rtol=1e-3, err_msg=f"step {t}")


def test_dense_gqa():
    cfg = ModelConfig(name="d", n_layers=3, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=97, qkv_bias=True,
                      qk_norm=True, attn_chunk=8)
    _run(cfg)


def _draw_correction_bias(params):
    m = params["moe_blocks"]["moe"]
    b = m["e_score_correction_bias"]
    m["e_score_correction_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), b.shape, b.dtype)
    return params


@pytest.mark.parametrize("arch", [
    pytest.param("generic", marks=pytest.mark.slow),
    "deepseek-v3-671b-smoke"])
def test_moe(arch):
    """The forward's capacity layer with generous capacity drops nothing:
    it then matches the dropless layer that serving runs, which also
    checks that both route by one gate.  On the DeepSeek-V3 twin the
    groups (2 of 4 kept), a drawn correction bias and the routed scale
    2.5 all change which experts run and their weights (capacity 4 holds
    every pair: 16 tokens x 2 of 8 experts)."""
    if arch == "generic":
        cfg = ModelConfig(name="m", n_layers=4, d_model=64, n_heads=4,
                          n_kv_heads=4, d_ff=128, vocab_size=97, moe=True,
                          n_experts=4, top_k=2, expert_d_ff=64,
                          moe_layer_start=2, n_shared_experts=1,
                          capacity_factor=4.0, attn_chunk=8)
        _run(cfg, atol=5e-4)
        return
    cfg = get_config(arch).replace(capacity_factor=4.0)
    assert cfg.router_bias and cfg.n_group > 1 and cfg.routed_scale != 1
    _run(cfg, tweak=_draw_correction_bias)


def test_mla():
    cfg = ModelConfig(name="a", n_layers=3, d_model=64, n_heads=4,
                      n_kv_heads=4, d_ff=128, vocab_size=97, mla=True,
                      q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16,
                      qk_rope_dim=8, v_head_dim=16, attn_chunk=8)
    _run(cfg)


@pytest.mark.slow
def test_local_window():
    cfg = ModelConfig(name="w", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=1, d_ff=128, vocab_size=97, window=6,
                      attn_chunk=8)
    _run(cfg)


@pytest.mark.slow
def test_griffin():
    cfg = ModelConfig(name="g", family="griffin", n_layers=5, d_model=64,
                      n_heads=4, n_kv_heads=1, d_ff=128, vocab_size=97,
                      lru_width=64, window=6, act="geglu", attn_chunk=8,
                      scale_embeddings=True)
    _run(cfg, atol=5e-4)


@pytest.mark.slow
def test_xlstm():
    cfg = ModelConfig(name="x", family="xlstm", n_layers=4, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=97,
                      proj_factor=2.0, slstm_every=4, attn_chunk=8)
    _run(cfg, atol=1e-3)
