"""Family-agnostic slot-decode protocol invariants.

PR 1/2 proved token-exactness of continuous batching for the transformer
family's full KV / MLA caches.  These tests extend the same contract to
the rest of the zoo through the slot-state protocol:

  * griffin / xlstm — O(1)-per-slot recurrent state (rglru h + conv
    tails; mLSTM C/n/m + sLSTM carries), scattered/gathered per slot and
    FROZEN exactly by the macro-step ``done`` mask (a recurrence update is
    irreversible, so eos firing mid-block must stop the state, not just
    the token);
  * ring-buffer window caches — a sliding-window config's slot pool is
    O(window) per slot (asserted on the pool shape), positions wrap, and
    decode stays token-exact both inside the window (where it must equal
    the FULL-cache model) and far beyond it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, get_config
from repro.data.synthetic import lm_batch
from repro.launch.serve import generate
from repro.models import get_family, serve_supported
from repro.serve import ContinuousBatchingEngine, Request

MAX_LEN = 32


def griffin_cfg():
    # window (6) far below MAX_LEN so attention ring slots genuinely wrap;
    # the pattern carries both recurrent and local-attention state
    return ModelConfig(name="griffin-serve", family="griffin", n_layers=3,
                       d_model=48, n_heads=4, n_kv_heads=1, d_ff=96,
                       vocab_size=97, lru_width=48, window=6, act="geglu",
                       attn_chunk=8, scale_embeddings=True,
                       block_pattern=("rec", "rec", "attn"))


def xlstm_cfg():
    # one mLSTM + one sLSTM block: every recurrent state kind is carried
    return ModelConfig(name="xlstm-serve", family="xlstm", n_layers=2,
                       d_model=48, n_heads=4, n_kv_heads=4, d_ff=0,
                       vocab_size=97, proj_factor=2.0, attn_chunk=8,
                       block_pattern=("m", "s"))


def window_cfg():
    # sliding-window transformer: ring-buffer slot pool
    return ModelConfig(name="win-serve", n_layers=2, d_model=48, n_heads=4,
                       n_kv_heads=2, d_ff=96, vocab_size=97, window=8,
                       attn_chunk=8)


FAMILY_CFGS = {"griffin": griffin_cfg, "xlstm": xlstm_cfg}


def _params(cfg):
    return get_family(cfg).init(jax.random.PRNGKey(0), cfg)


def _requests(cfg, specs, *, uid0=0, seed0=50):
    return [Request(uid=uid0 + i,
                    prompt=lm_batch(cfg.vocab_size, 1, p, seed=seed0 + i)[0],
                    max_new_tokens=g)
            for i, (p, g) in enumerate(specs)]


def _sequential(cfg, params, reqs):
    return {r.uid: np.asarray(generate(
        cfg, params, jnp.asarray(r.prompt)[None],
        max_new_tokens=r.max_new_tokens, max_len=MAX_LEN)[0])
        for r in reqs}


# k=1 is the degenerate per-token case of the same macro-loop code path;
# k=4 exercises everything it does plus in-scan freezing, so the k=1
# sweep rides the slow tier (tier-1 time audit)
@pytest.mark.parametrize(
    "k", [pytest.param(1, marks=pytest.mark.slow), 4])
@pytest.mark.parametrize("family", sorted(FAMILY_CFGS))
def test_recurrent_slot_decode_matches_sequential(family, k):
    """Recurrent-state slot decode is token-exact vs sequential
    ``generate()`` through admission bucketing (tail-padded prompts),
    per-slot macro stepping, retirement, and slot recycling."""
    cfg = FAMILY_CFGS[family]()
    params = _params(cfg)
    specs = [(3, 6), (9, 2), (5, 8), (12, 4), (4, 7)]
    reqs = _requests(cfg, specs)
    engine = ContinuousBatchingEngine(cfg, params, capacity=3,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      k=k)
    got = engine.run(reqs)
    want = _sequential(cfg, params, reqs)
    assert set(got) == set(want)
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid],
                                      err_msg=f"{family} uid {uid}")
    # the pool really was oversubscribed: recurrent slots were recycled
    assert len(reqs) > engine.capacity


@pytest.mark.parametrize("family", sorted(FAMILY_CFGS))
def test_recurrent_eos_mid_block_freezes_state(family):
    """An eos firing strictly inside a macro block must freeze the row's
    RECURRENT state mid-scan: the remaining no-op steps advance neither
    conv tails nor h/C/n/m, and the neighbour row's tokens stay exact."""
    k = 4
    cfg = FAMILY_CFGS[family]()
    params = _params(cfg)
    # seed chosen so both families' greedy traces emit a token at block
    # index 1 or 2 whose first occurrence is there (a usable mid-block eos)
    reqs = _requests(cfg, [(6, 12), (8, 12)], seed0=31)
    base = _sequential(cfg, params, reqs)
    # choose an eos whose FIRST occurrence lands inside the first macro
    # block (index in [1, k-1)): the row then dies mid-scan
    eos, stop = None, None
    for i in range(1, min(k - 1, len(base[0]))):
        cand = int(base[0][i])
        if int(np.argmax(base[0] == cand)) == i:
            eos, stop = cand, i + 1
            break
    assert eos is not None, "trace has no mid-block eos candidate"
    reqs[0].eos_id = eos
    engine = ContinuousBatchingEngine(cfg, params, capacity=2,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      k=k)
    got = engine.run(reqs)
    np.testing.assert_array_equal(got[0], base[0][:stop])
    np.testing.assert_array_equal(got[1], base[1])
    assert 1 < stop < k + 1  # really fired inside one block's scan


def test_ring_window_pool_shape_and_exactness_inside_window():
    """A sliding-window config serves from a ring-buffer slot pool whose
    KV footprint is O(window) — asserted on the pool shape — and inside
    the window its tokens equal the FULL-cache model's (the window mask
    is invisible until a sequence outgrows it)."""
    cfg_win = window_cfg()
    cfg_full = cfg_win.replace(window=None)
    params = _params(cfg_full)  # same param pytree for both configs
    engine = ContinuousBatchingEngine(cfg_win, params, capacity=2,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      k=4)
    kleaf = engine.pool["dense"]["k"]
    assert kleaf.shape[2] == cfg_win.window < MAX_LEN  # O(window), not O(max_len)
    # prompt + gen <= window: ring never wraps, full-cache tokens match
    reqs = _requests(cfg_win, [(3, 4), (5, 3), (2, 5), (4, 4)], seed0=60)
    got = engine.run(reqs)
    want = _sequential(cfg_full, params, reqs)
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid],
                                      err_msg=f"uid {uid}")


@pytest.mark.parametrize(
    "k", [pytest.param(1, marks=pytest.mark.slow), 4])
def test_ring_window_wrap_matches_sequential(k):
    """Sequences far beyond the window: ring slots wrap (positions
    overwrite ``pos % window``) and slot decode stays token-exact vs the
    sequential ring decode of the SAME windowed config."""
    cfg = window_cfg()
    params = _params(cfg)
    specs = [(3, 12), (10, 8), (6, 14), (12, 4), (5, 9)]
    reqs = _requests(cfg, specs, seed0=80)
    engine = ContinuousBatchingEngine(cfg, params, capacity=3,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      k=k)
    got = engine.run(reqs)
    want = _sequential(cfg, params, reqs)
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid],
                                      err_msg=f"uid {uid}")


@pytest.mark.parametrize("family", sorted(FAMILY_CFGS))
def test_done_rows_freeze_recurrent_state_exactly(family):
    """Protocol contract, tested at the family level: decode_step_slots
    with ``done`` set must leave EVERY cache leaf of those rows
    bit-identical — mLSTM and sLSTM carries, conv tails, rglru h, and
    ring KV alike.  (Regression: the sLSTM block once advanced its
    carries on done rows.)"""
    cfg = FAMILY_CFGS[family]()
    params = _params(cfg)
    fam = get_family(cfg)
    prompts = jnp.asarray(np.stack([lm_batch(cfg.vocab_size, 1, 5,
                                             seed=7 + i)[0]
                                    for i in range(2)]))
    cache = fam.init_cache(cfg, 2, 16)
    _, cache = fam.prefill_last(params, {"tokens": prompts,
                                         "plens": jnp.asarray([5, 5])},
                                cfg, cache)  # non-trivial state
    _, nc = fam.decode_step_slots(params, jnp.asarray([1, 2], jnp.int32),
                                  jnp.asarray([5, 5], jnp.int32), cache,
                                  cfg, done=jnp.asarray([True, True]))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        cache, nc)


def test_slot_decode_specs_match_engine_state():
    """launch/specs.py's abstract slot-decode specs must track the real
    engine state (shape + dtype), or dry-run lowering drifts silently."""
    from repro.launch import specs as specs_lib
    cfg = window_cfg()
    engine = ContinuousBatchingEngine(cfg, _params(cfg), capacity=2,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      k=4)
    spec = specs_lib.slot_decode_specs(cfg, engine.capacity, engine.max_len)
    names = ("tokens", "positions", "remaining", "eos_ids", "done", "keys")
    # leaf-count drift must fail loudly — zip would silently truncate
    assert len(names) == len(engine._state)
    for name, arr in zip(names, engine._state):
        assert (spec[name].shape, spec[name].dtype) == (arr.shape, arr.dtype)
    assert jax.tree.map(lambda s: (s.shape, str(s.dtype)), spec["pool"]) \
        == jax.tree.map(lambda a: (a.shape, str(a.dtype)), engine.pool)


def test_capability_probe():
    """The probe — not a hard-coded family check — gates the engine, with
    an actionable reason for unservable configs."""
    ok, why = serve_supported(get_config("hubert-xlarge-smoke"))
    assert not ok and "causal" in why
    with pytest.raises(NotImplementedError, match="causal"):
        ContinuousBatchingEngine(get_config("hubert-xlarge-smoke"), {},
                                 capacity=1, max_len=16)
    # griffin local attention without a window is probed out, not crashed
    ok, why = serve_supported(griffin_cfg().replace(window=None))
    assert not ok and "window" in why
    # every family in the zoo has a servable representative
    for arch in ("qwen1.5-0.5b-smoke", "deepseek-v3-671b-smoke",
                 "recurrentgemma-2b-smoke", "xlstm-1.3b-smoke"):
        ok, why = serve_supported(get_config(arch))
        assert ok, f"{arch}: {why}"
