"""Chunked attention vs oracle (property-swept) + MoE dispatch semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.attention import attention, reference_attention
from repro.models.moe import dispatch_combine, moe_mlp, router
from repro.configs.base import ModelConfig


# ---------------------------------------------------------------- attention
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("kv", [1, 2, 4])
def test_chunked_attention_matches_reference(causal, window, kv):
    if window is not None and not causal:
        pytest.skip("look-back windows are causal by construction")
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, hd = 2, 64, 4, 16
    q = jax.random.normal(keys[0], (B, S, H, hd))
    k = jax.random.normal(keys[1], (B, S, kv, hd))
    v = jax.random.normal(keys[2], (B, S, kv, hd))
    out = attention(q, k, v, causal=causal, window=window, chunk_q=16)
    ref = reference_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ragged_tail_padding():
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, hd = 1, 63, 2, 16  # 63 % 16 != 0 -> pad path
    q = jax.random.normal(keys[0], (B, S, H, hd))
    k = jax.random.normal(keys[1], (B, S, 2, hd))
    v = jax.random.normal(keys[2], (B, S, 2, hd))
    out = attention(q, k, v, causal=True, chunk_q=16)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(s=st.sampled_from([32, 48, 64]), chunk=st.sampled_from([8, 16]),
       q_offset=st.integers(0, 16))
def test_attention_chunk_invariance(s, chunk, q_offset):
    """Output must not depend on the chunk size (pure scheduling knob)."""
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (1, s, 2, 8))
    k = jax.random.normal(keys[1], (1, s + q_offset, 2, 8))
    v = jax.random.normal(keys[2], (1, s + q_offset, 2, 8))
    a = attention(q, k, v, causal=True, q_offset=q_offset, chunk_q=chunk)
    b = attention(q, k, v, causal=True, q_offset=q_offset, chunk_q=s)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


# --------------------------------------------------------------------- MoE
def test_router_topk_and_aux():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 4))
    for score in ("softmax", "sigmoid"):
        wts, idx, aux = router(x, w, top_k=2, score=score)
        assert wts.shape == (2, 16, 2) and idx.shape == (2, 16, 2)
        np.testing.assert_allclose(np.asarray(wts.sum(-1)), 1.0, atol=1e-5)
        # top-k indices are distinct per token
        assert (np.asarray(idx[..., 0]) != np.asarray(idx[..., 1])).all()
        assert float(aux) > 0


def test_dispatch_respects_capacity():
    B, S, K, E, C = 1, 16, 1, 2, 3
    # route every token to expert 0 -> only C survive
    idx = jnp.zeros((B, S, K), jnp.int32)
    wts = jnp.ones((B, S, K))
    disp, comb = dispatch_combine(wts, idx, E, C)
    assert disp.shape == (B, S, E, C)
    assert float(disp.sum()) == C  # capacity-truncated
    # earlier tokens win
    assert float(disp[0, :C, 0].sum()) == C


def test_dispatch_combine_identity_when_unconstrained():
    """With ample capacity, combine(dispatch(x)) == sum_k w_k * x."""
    B, S, K, E = 2, 8, 2, 4
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (B, S, E))
    probs = jax.nn.softmax(logits)
    wts, idx = jax.lax.top_k(probs, K)
    wts = wts / wts.sum(-1, keepdims=True)
    disp, comb = dispatch_combine(wts, idx, E, capacity=S)
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, 3))
    xe = jnp.einsum("bsec,bsd->becd", disp, x)
    y = jnp.einsum("bsec,becd->bsd", comb, xe)  # identity experts
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-5,
                               atol=1e-5)


def test_moe_mlp_group_reshape_consistency():
    """Group size must not change results when capacity is ample."""
    from repro.models import moe as moe_lib

    cfg = ModelConfig(name="m", n_layers=1, d_model=16, n_heads=2,
                      n_kv_heads=2, d_ff=32, vocab_size=11, moe=True,
                      n_experts=4, top_k=2, expert_d_ff=32,
                      capacity_factor=8.0)
    p = moe_lib.init_moe(iter(jax.random.split(jax.random.PRNGKey(0), 10)),
                         cfg, layers=None)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    y1, _ = moe_lib.moe_mlp(x, p, cfg)
    old = moe_lib.MOE_GROUP_SIZE
    try:
        moe_lib.MOE_GROUP_SIZE = 4
        y2, _ = moe_lib.moe_mlp(x, p, cfg)
    finally:
        moe_lib.MOE_GROUP_SIZE = old
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)
