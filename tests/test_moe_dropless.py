"""The dropless expert layer, its gate, YaRN, and admission's
last-position head.

Tolerances: these configs compute in float32 on the CPU, so two paths of
the same arithmetic differ only by the order of accumulation, ~1e-6 of
the values compared; 1e-5 absolute is loose for that and far below what
one bfloat16 rounding of the inputs moves (~4e-3 relative), which each
test checks it would catch.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import get_family
from repro.models import moe as moe_lib
from repro.models import rope as rope_lib
from repro.models import transformer

TOL = 1e-5


def _moe_params(cfg, key=0):
    cfg = cfg.replace(router_bias=True)
    p = moe_lib.init_moe(iter(jax.random.split(jax.random.PRNGKey(key), 8)),
                         cfg, layers=None)
    # a correction bias and weights away from zero, as the benchmark draws
    return jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(7),
                                               a.shape), p)


def _uncut(x, p, cfg):
    """The whole layer, straight from its definition: every routed expert
    of the top-k, weighted, plus the shared expert."""
    w, idx = moe_lib.gate(x, p, cfg)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(cfg.n_experts):
        we = jnp.where(idx == e, w, 0.0).sum(-1)
        h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        y = y + we[:, None] * (h @ p["w_down"][e])
    h = jax.nn.silu(x @ p["shared_w_gate"]) * (x @ p["shared_w_up"])
    return y + h @ p["shared_w_down"]


def test_held_shares_add_up_to_the_uncut_layer():
    """Each chip's share (its held experts' routed part) over every
    offset, with the shared expert counted once, adds up to the whole
    layer: the router sees all experts on every chip."""
    full = get_config("deepseek-v3-ep32-smoke").replace(experts_held=0,
                                                        router_bias=True)
    H = get_config("deepseek-v3-ep32-smoke").experts_held
    p = _moe_params(full)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, full.d_model))
    routed = {k: v for k, v in p.items() if not k.startswith("shared")}
    total = moe_lib._shared(x, p)  # what every chip computes alike: once
    for off in range(0, full.n_experts, H):
        cfg = full.replace(experts_held=H, experts_offset=off)
        share = dict(routed, **{w: p[w][off:off + H]
                                for w in moe_lib.EXPERT_WEIGHTS})
        y, stats = moe_lib.dropless_moe(x, share, cfg)
        total = total + y
        assert int(stats[0]) <= x.shape[0] * x.shape[1] * cfg.top_k
    want = _uncut(x.reshape(-1, full.d_model), p, full).reshape(x.shape)
    np.testing.assert_allclose(total, want, atol=TOL)
    # one bf16 rounding of the layer's input already fails the tolerance
    xb = x.astype(jnp.bfloat16).astype(jnp.float32)
    bad = _uncut(xb.reshape(-1, full.d_model), p, full).reshape(x.shape)
    assert float(jnp.max(jnp.abs(bad - want))) > 10 * TOL


def test_dropless_rows_do_not_depend_on_each_other():
    """No drops: a row's output is the same alone, in a batch, and in
    chunks of the grouped matmul; idle rows get the shared expert only
    and count no pairs."""
    cfg = get_config("deepseek-v3-ep32-smoke")
    p = _moe_params(cfg, key=3)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 6, cfg.d_model))
    y, stats = moe_lib.dropless_moe(x, p, cfg)
    for b in range(4):
        yb, _ = moe_lib.dropless_moe(x[b:b + 1], p, cfg)
        np.testing.assert_allclose(yb[0], y[b], atol=TOL)
    old = moe_lib.MOE_CHUNK
    moe_lib.MOE_CHUNK = 5  # 24 tokens: five passes, the last one padded
    try:
        yc, sc = moe_lib.dropless_moe(x, p, cfg)
    finally:
        moe_lib.MOE_CHUNK = old
    np.testing.assert_allclose(yc, y, atol=TOL)
    assert int(sc[0]) == int(stats[0])
    live = jnp.zeros((4, 6), bool).at[1].set(True)
    yl, sl = moe_lib.dropless_moe(x, p, cfg, live=live)
    np.testing.assert_allclose(yl[1], y[1], atol=TOL)
    np.testing.assert_allclose(yl[0], moe_lib._shared(x, p)[0], atol=TOL)
    _, s1 = moe_lib.dropless_moe(x[1:2], p, cfg)
    assert int(sl[0]) == int(s1[0]) > 0
    assert 0 < int(sl[1]) <= cfg.experts_held


def _hand_gate(scores, bias, **kw):
    """The gate over hand-built logits: one token per row of ``scores``
    (the router is the identity, so logits are the given rows)."""
    scores = jnp.asarray(scores, jnp.float32)
    T, E = scores.shape
    cfg = get_config("deepseek-v3-671b-smoke").replace(
        d_model=E, n_experts=E, **kw)
    p = {"w_router": jnp.eye(E),
         "e_score_correction_bias": jnp.asarray(bias, jnp.float32)}
    logits = jnp.log(scores / (1 - scores))  # sigmoid(logits) == scores
    w, idx = moe_lib.gate(logits, p, cfg)
    return np.asarray(w), np.sort(np.asarray(idx), -1), cfg


def test_gate_groups_and_correction_bias():
    # 8 experts in 4 groups of 2; keep 2 groups, then the top 2 experts.
    # Expert 0 scores highest alone, but its group (0, 1) sums lowest of
    # the groups: it is outvoted, and experts from groups (4,5), (6,7) win.
    s = [[0.95, 0.01, 0.30, 0.30, 0.60, 0.50, 0.70, 0.45]]
    zero = np.zeros(8)
    w, idx, cfg = _hand_gate(s, zero, n_group=4, topk_group=2, top_k=2,
                             routed_scale=2.5)
    np.testing.assert_array_equal(idx, [[4, 6]])
    np.testing.assert_allclose(w.sum(), 2.5, rtol=1e-6)
    np.testing.assert_allclose(sorted(w[0]), np.array([0.6, 0.7]) / 1.3 *
                               2.5, rtol=1e-6)
    # without groups the lone high score wins
    _, idx, _ = _hand_gate(s, zero, n_group=1, topk_group=1, top_k=2)
    np.testing.assert_array_equal(idx, [[0, 6]])
    # a correction bias on expert 2 lifts group (2, 3) over the others and
    # picks expert 2 over 4: selection follows s + bias, but the weights
    # stay the plain scores s of the chosen experts
    bias = zero.copy()
    bias[2] = 0.6
    w, idx, _ = _hand_gate(s, bias, n_group=4, topk_group=2, top_k=2,
                           routed_scale=1.0)
    np.testing.assert_array_equal(idx, [[2, 6]])
    np.testing.assert_allclose(sorted(w[0]), np.array([0.3, 0.7]) / 1.0,
                               rtol=1e-6)


def test_yarn_frequencies_match_the_closed_form():
    """DeepSeek-V3's rope: d 64, theta 1e4, factor 40 over 4096."""
    cfg = get_config("deepseek-v3-ep32")
    d, theta, s, L0 = 64, 10000.0, 40.0, 4096
    low = math.floor(d * math.log(L0 / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(d * math.log(L0 / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (10, 23)
    assert rope_lib.yarn_bounds(d, theta, L0, 32, 1) == (low, high)
    i = np.arange(d // 2)
    extra = theta ** (-2 * i / d)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = extra / s * ramp + extra * (1 - ramp)
    freqs, mscale = rope_lib.cfg_rope(cfg, cfg.qk_rope_dim)
    np.testing.assert_allclose(np.asarray(freqs), want, rtol=1e-6)
    assert mscale == 1.0  # mscale == mscale_all_dim: cos, sin unscaled
    np.testing.assert_allclose(
        transformer._mla_scale(cfg) * math.sqrt(192),
        (0.1 * math.log(40) + 1) ** 2, rtol=1e-12)
    assert abs((0.1 * math.log(40) + 1) ** 2 - 1.8739) < 1e-4
    # dims below low keep their frequency, dims past high are divided by s
    assert np.allclose(want[:low + 1], extra[:low + 1])
    assert np.allclose(want[high:], extra[high:] / s)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b-smoke",
                                  "deepseek-v3-ep32-smoke"])
def test_admission_head_only_at_the_last_prompt_position(arch):
    """``prefill_last`` equals the full-logits forward gathered at each
    row's last real position, and leaves the same cache."""
    cfg = get_config(arch)
    fam = get_family(cfg)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0,
                                cfg.vocab_size)
    plens = jnp.asarray([16, 9, 3], jnp.int32)
    cache = fam.init_cache(cfg, 3, 32)
    got, got_cache = jax.jit(fam.prefill_last, static_argnums=2)(
        params, {"tokens": tokens, "plens": plens}, cfg, cache)
    full, full_cache = jax.jit(transformer._forward_cached,
                               static_argnums=(2, 4))(
        params, {"tokens": tokens}, cfg, cache, 0)
    assert got.shape == (3, cfg.vocab_size)
    want = full[jnp.arange(3), plens - 1]
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))
    if not cfg.moe:  # expert layers compute no pairs for pad positions
        jax.tree.map(np.testing.assert_array_equal, got_cache, full_cache)


def test_engine_counts_expert_work_on_the_block_readback():
    """The decode loop's expert counts ride each block's readback: pairs
    computed and held experts hit, summed over steps and layers, with no
    extra host sync; a model without experts counts blocks and zeros."""
    from repro.serve import ContinuousBatchingEngine, Request

    def serve(arch):
        cfg = get_config(arch)
        params = get_family(cfg).init(jax.random.PRNGKey(0), cfg)
        eng = ContinuousBatchingEngine(cfg, params, capacity=3, max_len=48,
                                       prefill_bucket=8, k=4, pool="paged")
        rng = np.random.default_rng(0)
        eng.run([Request(uid=i, prompt=rng.integers(
            0, cfg.vocab_size, 5 + 3 * i).astype(np.int32),
            max_new_tokens=9) for i in range(5)])
        return cfg, eng.lifetime_totals()

    cfg, c = serve("deepseek-v3-ep32-smoke")
    n_moe = cfg.n_layers - cfg.moe_layer_start
    assert c["n_blocks_read"] == c["n_decode_dispatches"] > 0
    assert 0 < c["n_moe_experts_hit"] <= c["n_moe_pairs"]
    steps = c["n_blocks_read"] * 4 * n_moe  # block x k x expert layers
    assert c["n_moe_experts_hit"] <= steps * cfg.experts_held
    assert c["n_moe_pairs"] <= steps * 3 * cfg.top_k  # live rows only
    # one blocking read per block and per admission group, as before
    assert c["n_host_syncs"] == c["n_blocks_read"] + c["n_prefills"]
    _, c = serve("qwen1.5-0.5b-smoke")
    assert c["n_blocks_read"] > 0
    assert c["n_moe_pairs"] == c["n_moe_experts_hit"] == 0
