"""Paged slot-pool invariants: block-table KV + copy-on-write prefix cache.

The paged pool re-lays every cache group a family DECLARES pageable
(``models.paged_groups``) over ONE shared page arena plus per-slot block
tables (``pool="paged"``).  Its contract mirrors the dense pool's:
*token-exactness* — for any trace, greedy tokens equal both the dense
engine's and the sequential ``generate()`` loop's, across transformer
full-KV, MLA latent, ring-window, griffin, xlstm slot-tail, and
speculative chunk-verify serving (griffin pairs included), in the jnp
path and the Pallas interpreter path alike.  On top of that sit the
pool's own invariants: all-or-nothing page allocation with backpressure
(never a partial admission), refcounted page release on eviction across
the draft/target namespaces of a shared arena, prefix-cache hits — full
KV, ring tail-restore, and sampled replay — that skip re-prefill without
changing a single token, and the allocator conservation law (free +
held + LRU-retained == n_pages, live block tables only ever referencing
held pages).
"""
import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.data.synthetic import lm_batch
from repro.launch.serve import generate
from repro.serve import ContinuousBatchingEngine, Request, SpeculativeConfig
from repro.serve.paged import PageAllocator, PoolMeta, prefix_digests

MAX_LEN = 32


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_caches():
    """Release this module's jitted executables when it finishes.

    The engine parity tests here compile ~15 distinct engine variants
    (paged/dense x family x kernel).  Those executables stay pinned by
    ``_jitted_engine_fns``'s unbounded lru_cache and jax's global jit
    caches for the rest of the pytest process, and the cumulative XLA
    state has been observed to push later unrelated compiles into a
    segfault on small containers.  Dropping the caches at module teardown
    keeps the suite's peak compiled-state bounded.
    """
    yield
    from repro.serve.engine import _jitted_engine_fns
    _jitted_engine_fns.cache_clear()
    jax.clear_caches()


def _requests(cfg, specs, *, uid0=0, seed0=50):
    return [Request(uid=uid0 + i,
                    prompt=lm_batch(cfg.vocab_size, 1, p, seed=seed0 + i)[0],
                    max_new_tokens=g)
            for i, (p, g) in enumerate(specs)]


def _clone(reqs, *, uid0=0):
    return [Request(uid=uid0 + r.uid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens, arrival=r.arrival)
            for r in reqs]


def _sequential(cfg, params, reqs):
    return {r.uid: np.asarray(generate(
        cfg, params, jnp.asarray(r.prompt)[None],
        max_new_tokens=r.max_new_tokens, max_len=MAX_LEN)[0])
        for r in reqs}


def _run_both(cfg, params, reqs, *, capacity=3, k=4, pages=None, **kw):
    """Run the same trace through a dense and a paged engine; return
    (dense tokens, paged tokens, paged engine)."""
    dense = ContinuousBatchingEngine(cfg, params, capacity=capacity,
                                     max_len=MAX_LEN, prefill_bucket=4,
                                     k=k, pool="dense", **kw)
    paged = ContinuousBatchingEngine(cfg, params, capacity=capacity,
                                     max_len=MAX_LEN, prefill_bucket=4,
                                     k=k, pool="paged", pages=pages, **kw)
    got_d = dense.run(_clone(reqs))
    got_p = paged.run(_clone(reqs))
    return got_d, got_p, paged


def _assert_equal(got_d, got_p, want=None):
    assert set(got_d) == set(got_p)
    for uid in got_d:
        np.testing.assert_array_equal(got_p[uid], got_d[uid],
                                      err_msg=f"uid {uid} paged vs dense")
        if want is not None:
            np.testing.assert_array_equal(got_p[uid], want[uid],
                                          err_msg=f"uid {uid} vs generate")


def _window_cfg():
    return ModelConfig(name="win-paged", n_layers=2, d_model=48, n_heads=4,
                       n_kv_heads=2, d_ff=96, vocab_size=97, window=8,
                       attn_chunk=8)


def _griffin_cfg():
    return ModelConfig(name="griffin-paged", family="griffin", n_layers=3,
                       d_model=48, n_heads=4, n_kv_heads=1, d_ff=96,
                       vocab_size=97, lru_width=48, window=6, act="geglu",
                       attn_chunk=8, scale_embeddings=True,
                       block_pattern=("rec", "rec", "attn"))


def _griffin_rec_cfg():
    """All-recurrent griffin: servable, but with NO pageable cache group
    (the one remaining honest dense-fallback case in the zoo)."""
    return ModelConfig(name="griffin-rec-only", family="griffin",
                       n_layers=2, d_model=48, n_heads=4, n_kv_heads=1,
                       d_ff=96, vocab_size=97, lru_width=48, window=6,
                       act="geglu", attn_chunk=8, scale_embeddings=True,
                       block_pattern=("rec", "rec"))


def _xlstm_cfg():
    return ModelConfig(name="xlstm-paged", family="xlstm", n_layers=2,
                       d_model=48, n_heads=4, n_kv_heads=4, d_ff=0,
                       vocab_size=97, proj_factor=2.0, attn_chunk=8,
                       block_pattern=("m", "s"))


def _window9_cfg():
    """window=9 over a 16-deep ring (page 8, nblk 2): the smallest
    geometry where the ring retains one full UNCLOBBERED page —
    ``(nblk-1)*page + 1 >= window`` — so ring prefix sharing can fire
    (window=8/ring=8/nblk=1 can never hit: the prompt's partial tail
    page always overwrites the only ring page)."""
    return ModelConfig(name="win9-paged", n_layers=2, d_model=48,
                       n_heads=4, n_kv_heads=2, d_ff=96, vocab_size=97,
                       window=9, attn_chunk=8)


def _arena_invariants(engine):
    """The allocator conservation law, checked against device state:
    free ∪ held ∪ LRU-retained partitions the page-id space, pages
    pending a zeroing scatter are already free, and every non-sentinel
    block-table entry of a LIVE slot references a held page."""
    alloc = engine._alloc
    n = alloc.meta.n_pages
    free, lru = set(alloc.free), set(alloc.lru)
    held = {p for p in range(n) if alloc.refcount[p].sum() > 0}
    assert len(alloc.free) == len(free)  # no duplicate free entries
    assert not (free & held) and not (free & lru) and not (held & lru)
    assert free | held | lru == set(range(n))
    assert set(engine._zero_pending) <= free
    live = set()
    for pool, meta in zip(engine._pools, engine._metas):
        if meta is None:
            continue
        for g in meta.groups:
            bt = np.asarray(pool[g.path[0]]["bt"][0])
            for slot in engine.active:
                live |= {int(x) for x in bt[slot] if int(x) < n}
    assert live <= held, (live, held)


def _params(cfg):
    from repro.models import get_family
    return get_family(cfg).init(jax.random.PRNGKey(0), cfg)


def test_paged_matches_dense_and_sequential(qwen_smoke_cfg,
                                            qwen_smoke_params):
    """Full-KV transformer serving through the paged pool is token-exact
    vs the dense pool AND vs sequential ``generate()`` across admission
    bucketing, slot recycling, and macro stepping."""
    cfg, params = qwen_smoke_cfg, qwen_smoke_params
    specs = [(3, 6), (9, 2), (5, 8), (12, 4), (4, 7), (7, 1), (6, 5)]
    reqs = _requests(cfg, specs)
    got_d, got_p, engine = _run_both(cfg, params, reqs)
    assert engine.pool_kind == "paged"
    _assert_equal(got_d, got_p, _sequential(cfg, params, reqs))
    assert len(reqs) > engine.capacity  # slots really were recycled


def test_paged_ring_window_wrap_parity():
    """Ring-buffer window slots through the paged pool: sequences far
    beyond the window wrap their (single-page) ring exactly as dense."""
    cfg = _window_cfg()
    params = _params(cfg)
    specs = [(3, 12), (10, 8), (6, 14), (12, 4), (5, 9)]
    reqs = _requests(cfg, specs, seed0=80)
    got_d, got_p, engine = _run_both(cfg, params, reqs)
    assert engine.pool_kind == "paged"
    _assert_equal(got_d, got_p, _sequential(cfg, params, reqs))


def test_paged_griffin_mixed_groups():
    """Griffin pools page the local-attention KV group while the
    recurrent-state group stays dense — both ride the same admission,
    decode, and eviction paths, token-exact vs dense and sequential."""
    cfg = _griffin_cfg()
    params = _params(cfg)
    specs = [(3, 6), (9, 2), (5, 8), (12, 4), (4, 7)]
    reqs = _requests(cfg, specs)
    got_d, got_p, engine = _run_both(cfg, params, reqs)
    assert engine.pool_kind == "paged"
    # the recurrent group really is dense alongside the paged attn group
    paged_groups = [g for g in engine.pool.values()
                    if isinstance(g, dict) and "bt" in g]
    assert paged_groups and len(paged_groups) < len(engine.pool)
    _assert_equal(got_d, got_p, _sequential(cfg, params, reqs))


def test_paged_xlstm_slot_groups_parity():
    """xlstm pages its conv-tail SLOT groups (one whole tail per page,
    nblk=1) while the mLSTM/sLSTM carries stay dense-per-slot — the
    family serves paged now instead of silently flipping dense —
    token-exact vs the dense pool and sequential generate()."""
    cfg = _xlstm_cfg()
    params = _params(cfg)
    reqs = _requests(cfg, [(3, 6), (9, 2), (5, 8), (12, 4), (4, 7)])
    got_d, got_p, engine = _run_both(cfg, params, reqs, capacity=2)
    assert engine.pool_kind == "paged"
    assert engine.pool_fallback_reason is None
    # both blocks page their conv tails; carries stay dense in-place
    paged_groups = [g for g in engine.pool.values()
                    if isinstance(g, dict) and "bt" in g]
    assert len(paged_groups) == 2
    assert all(len(g) > 2 for g in paged_groups)  # dense carries ride along
    _assert_equal(got_d, got_p, _sequential(cfg, params, reqs))


def _teacher_forced_slot_logits(cfg, params, reqs, forced, pool, steps):
    """Admit ``reqs`` through an engine with ``pool``, then run ``steps``
    slot-decode steps over its pool, each row fed ``forced[uid]`` (the
    tokens after its prompt).  Returns {uid: (steps, V) logits}."""
    from repro.models import get_family
    eng = ContinuousBatchingEngine(cfg, params, capacity=len(reqs),
                                   max_len=MAX_LEN, prefill_bucket=4, k=4,
                                   pool=pool)
    assert eng.pool_kind == pool
    for r in _clone(reqs):
        eng.submit(r)
    eng._admit_batch(None)
    slot = {seq.req.uid: s for s, seq in eng.active.items()}
    step = jax.jit(get_family(cfg).decode_step_slots, static_argnums=4)
    cache, out = eng._pools[0], {r.uid: [] for r in reqs}
    for j in range(steps):
        toks = np.zeros(len(reqs), np.int32)
        pos = np.zeros(len(reqs), np.int32)
        for r in reqs:
            toks[slot[r.uid]] = forced[r.uid][j]
            pos[slot[r.uid]] = len(r.prompt) + j
        logits, cache = step(params, jnp.asarray(toks), jnp.asarray(pos),
                             cache, cfg)
        for r in reqs:
            out[r.uid].append(np.asarray(logits[slot[r.uid]]))
    return {uid: np.stack(v) for uid, v in out.items()}


def test_paged_mla_latent_parity():
    """MLA pages its latent caches (ckv/kr): absorbed decode through the
    paged latents gives the logits of the dense pool and of sequential
    decoding, step by step, fed the same tokens.

    Logits, not tokens: random weights leave near-ties, which a rounding
    flips.  (Token comparisons here once failed for another reason: the
    expert layer dropped tokens over capacity, and which it dropped
    depended on how the paths batched them; serving is dropless now.)
    Tolerance 1e-5: the smoke config computes in float32, so the paths
    differ by accumulation order only (~1e-7 of these logits), while
    one bfloat16 rounding moves them by ~1e-3."""
    from repro.configs.base import get_config
    from repro.models import get_family
    cfg = get_config("deepseek-v3-671b-smoke")
    fam = get_family(cfg)
    params = _params(cfg)
    steps = 6  # within every request's budget, so its pages are there
    reqs = _requests(cfg, [(3, 8), (9, 8), (5, 8), (11, 8)])
    forced = {r.uid: lm_batch(cfg.vocab_size, 1, steps, seed=90 + r.uid)[0]
              for r in reqs}
    want = {}
    for r in reqs:  # sequential: prefill, then decode_step fed the same
        cache = fam.init_cache(cfg, 1, MAX_LEN)
        _, cache = fam.prefill(params, {"tokens": jnp.asarray(r.prompt)[None]},
                               cfg, cache)
        rows = []
        for j in range(steps):
            lg, cache = fam.decode_step(
                params, jnp.asarray(forced[r.uid][j:j + 1]),
                jnp.int32(len(r.prompt) + j), cache, cfg)
            rows.append(np.asarray(lg[0]))
        want[r.uid] = np.stack(rows)
    for pool in ("dense", "paged"):
        got = _teacher_forced_slot_logits(cfg, params, reqs, forced, pool,
                                          steps)
        for r in reqs:
            np.testing.assert_allclose(got[r.uid], want[r.uid], atol=1e-5,
                                       err_msg=f"{pool} uid {r.uid}")
    scale = max(float(np.abs(w).max()) for w in want.values())
    assert scale * 2 ** -8 > 1e-5  # bf16's rounding step would show


def test_unpageable_config_serves_dense_with_named_reason():
    """A config with no pageable cache group (all-recurrent griffin:
    O(1) state only) degrades to the dense pool WITH a named
    ``pool_fallback_reason`` — the silent ``pool_kind`` flip is retired —
    and still serves token-exactly."""
    cfg = _griffin_rec_cfg()
    params = _params(cfg)
    reqs = _requests(cfg, [(3, 6), (9, 2), (5, 8)])
    engine = ContinuousBatchingEngine(cfg, params, capacity=2,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      k=4, pool="paged")
    assert engine.pool_kind == "dense"
    assert "no pageable cache groups" in engine.pool_fallback_reason
    got = engine.run(reqs)
    want = _sequential(cfg, params, reqs)
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])


def test_paged_speculative_chunk_verify(gpt_micro_cfg, gpt_micro_big_cfg):
    """Speculative serving allocates BOTH pools (draft + target) from
    page arenas; chunk-verify over block tables accepts/rejects exactly
    as the dense pools do."""
    from repro.models import get_family
    cfg_t, cfg_d = gpt_micro_big_cfg, gpt_micro_cfg
    params_t = get_family(cfg_t).init(jax.random.PRNGKey(0), cfg_t)
    params_d = get_family(cfg_d).init(jax.random.PRNGKey(1), cfg_d)
    reqs = _requests(cfg_t, [(4, 6), (9, 3), (6, 5)], seed0=70)
    got_d, got_p, engine = _run_both(
        cfg_t, params_t, reqs, capacity=2, k=2,
        speculative=SpeculativeConfig(cfg_d, params_d, d=2))
    assert engine.pool_kind == "paged"
    _assert_equal(got_d, got_p, _sequential(cfg_t, params_t, reqs))


@pytest.mark.parametrize("d", [2, 4])
def test_paged_griffin_speculative_parity(d):
    """Griffin + speculative no longer forces dense: the paged
    ``spec_ring_restore`` twin commits/rolls back verify blocks directly
    in the paged local-attention rings.  Token-exact vs the dense spec
    engine and sequential generate() at both depths, with generations
    long enough to wrap the window ring several times."""
    cfg = _griffin_cfg()
    params = _params(cfg)
    from repro.models import get_family
    cfg_d = _griffin_cfg().replace(name="griffin-draft")
    # a DISAGREEING draft (different init): rejections exercise the paged
    # ring rollback, not just the all-accept fast path
    params_d = get_family(cfg_d).init(jax.random.PRNGKey(3), cfg_d)
    # window 6 -> an 8-deep ring: gens of 12-14 wrap it repeatedly
    specs = [(3, 14), (10, 8), (6, 12), (12, 4)]
    reqs = _requests(cfg, specs, seed0=85)
    got_d, got_p, engine = _run_both(
        cfg, params, reqs, capacity=2, k=2,
        speculative=SpeculativeConfig(cfg_d, params_d, d=d))
    assert engine.pool_kind == "paged"
    assert engine.pool_fallback_reason is None
    _assert_equal(got_d, got_p, _sequential(cfg, params, reqs))


@pytest.mark.parametrize("window", [None, 8])
def test_paged_kernel_interpret_parity(gpt_micro_cfg, window):
    """The paged Pallas kernels (block-table indirection in the index
    map, scalar-prefetched bt) are token-exact vs the jnp paged path in
    interpreter mode, full-KV and ring alike."""
    cfg = gpt_micro_cfg if window is None else \
        gpt_micro_cfg.replace(name="gpt-micro-win", window=window)
    params = _params(gpt_micro_cfg)
    reqs = _requests(cfg, [(4, 6), (9, 4)], seed0=90)
    jnp_engine = ContinuousBatchingEngine(cfg, params, capacity=2,
                                          max_len=MAX_LEN, prefill_bucket=4,
                                          k=2, pool="paged")
    kcfg = cfg.replace(decode_kernel="interpret")
    k_engine = ContinuousBatchingEngine(kcfg, params, capacity=2,
                                        max_len=MAX_LEN, prefill_bucket=4,
                                        k=2, pool="paged")
    got_j = jnp_engine.run(_clone(reqs))
    got_k = k_engine.run(_clone(reqs))
    assert k_engine.pool_kind == "paged"
    _assert_equal(got_j, got_k)


def test_page_exhaustion_backpressure(qwen_smoke_cfg, qwen_smoke_params):
    """With fewer pages than the trace wants at once, admission applies
    backpressure (requests wait for released pages) instead of partially
    admitting — every request still finishes with exact tokens, and the
    arena high-water never exceeds the budget."""
    cfg, params = qwen_smoke_cfg, qwen_smoke_params
    specs = [(9, 8), (10, 7), (11, 6), (9, 5), (12, 4), (10, 8)]
    reqs = _requests(cfg, specs, seed0=120)
    # each request needs 3 pages (8-token quantum); 4 pages admit only
    # one at a time even though 4 slots are free
    got_d, got_p, engine = _run_both(cfg, params, reqs, capacity=4,
                                     pages=4)
    assert engine.pages_highwater <= 4
    assert set(got_p) == {r.uid for r in reqs}  # nobody starved
    _assert_equal(got_d, got_p)


def test_prefix_hit_skips_prefill_token_exact(qwen_smoke_cfg,
                                              qwen_smoke_params):
    """Requests sharing a prompt prefix: after the first admission wave
    registers its prefill pages, later requests hit the prefix cache —
    fewer prefill dispatches, shared pages referenced copy-on-write —
    with tokens exactly equal to the dense engine's and generate()'s."""
    cfg, params = qwen_smoke_cfg, qwen_smoke_params
    prefix = lm_batch(cfg.vocab_size, 1, 18, seed=200)[0]
    reqs = []
    for i in range(4):
        tail = lm_batch(cfg.vocab_size, 1, 2 + i, seed=210 + i)[0]
        reqs.append(Request(uid=i, prompt=np.concatenate([prefix, tail]),
                            max_new_tokens=5))
    # capacity 1 forces one admission wave per request, so waves 2-4 can
    # hit the pages wave 1 registered
    got_d, got_p, engine = _run_both(cfg, params, reqs, capacity=1)
    dense = ContinuousBatchingEngine(cfg, params, capacity=1,
                                     max_len=MAX_LEN, prefill_bucket=4,
                                     k=4, pool="dense")
    dense.run(_clone(reqs, uid0=100))
    assert engine.n_prefix_hits == 3 and engine.n_prefix_misses == 1
    assert engine.n_prefills < dense.n_prefills  # re-prefill skipped
    assert engine.prefix_hit_rate == pytest.approx(0.75)
    # hits allocate only tail pages: strictly fewer than a miss would
    assert engine.n_pages_allocated < 4 * 3
    _assert_equal(got_d, got_p, _sequential(cfg, params, reqs))


def test_prefix_hit_under_pressure_pins_resident_pages(
        qwen_smoke_cfg, qwen_smoke_params, monkeypatch):
    """Regression: a prefix-hit admission must pin (incref) the resident
    pages BEFORE allocating its tail.  With a dry free list, alloc()
    reclaims zero-ref LRU-retained pages — previously including the very
    pages the lookup just returned, so one physical page served as both
    shared prefix and private tail of the same slot (pids like
    ``[3, 4, 5, 3]``) and tail writes aliased the prefix KV.  The fixed
    path stalls the hit (telemetry: ``n_prefix_stalls``, not a registry
    miss) until pages free up; no admission record may ever book the
    same page twice."""
    cfg, params = qwen_smoke_cfg, qwen_smoke_params
    prefix = lm_batch(cfg.vocab_size, 1, 17, seed=600)[0]
    long_runner = Request(uid=0,
                          prompt=lm_batch(cfg.vocab_size, 1, 9,
                                          seed=601)[0],
                          max_new_tokens=15)  # 3 pages, held for many steps
    registrar = Request(uid=1, prompt=prefix, max_new_tokens=1)  # 3 pages
    hitter = Request(uid=2,
                     prompt=np.concatenate(
                         [prefix[:16], lm_batch(cfg.vocab_size, 1, 1,
                                                seed=602)[0]]),
                     max_new_tokens=14)  # hit: 2 resident + 2 tail pages
    reqs = [long_runner, registrar, hitter]
    # arena of 6: wave 1 (long_runner + registrar) takes all 6 pages; by
    # the hitter's admission only ONE page is free while the 2 resident
    # pages sit zero-ref in the LRU — exactly the reclaim-aliasing setup
    engine = ContinuousBatchingEngine(cfg, params, capacity=2,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      k=4, pool="paged", pages=6)
    orig = ContinuousBatchingEngine._alloc_request
    double_booked = []

    def checked(self, req):
        info = orig(self, req)
        if info is not None:
            pids = list(info["pids"]) + list(info.get("resident") or [])
            if pids and len(set(pids)) != len(pids):
                double_booked.append((req.uid, pids))
        return info

    monkeypatch.setattr(ContinuousBatchingEngine, "_alloc_request",
                        checked)
    got = engine.run(_clone(reqs))
    assert not double_booked  # the direct aliasing signature
    assert engine.n_prefix_hits == 1
    assert engine.n_prefix_stalls >= 1  # the hit waited, pages pinned
    assert engine.n_prefix_misses == 2  # stalls are NOT misses
    assert engine.pages_highwater <= 6
    want = _sequential(cfg, params, reqs)
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid],
                                      err_msg=f"uid {uid}")


def test_unservable_page_budget_rejected_not_livelocked(qwen_smoke_cfg,
                                                        qwen_smoke_params):
    """Regression: a request whose page need exceeds the whole arena used
    to bounce off admission forever (run() livelocked re-queueing it).
    submit() must reject it up front — recorded, uid reusable — while
    requests the arena CAN hold keep serving."""
    cfg, params = qwen_smoke_cfg, qwen_smoke_params
    engine = ContinuousBatchingEngine(cfg, params, capacity=2,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      k=4, pool="paged", pages=2)
    reqs = [Request(uid=0,
                    prompt=lm_batch(cfg.vocab_size, 1, 9, seed=700)[0],
                    max_new_tokens=8),   # 3 pages > 2-page arena
            Request(uid=1,
                    prompt=lm_batch(cfg.vocab_size, 1, 4, seed=701)[0],
                    max_new_tokens=3)]   # 1 page: servable
    got = engine.run(reqs)
    assert "pages" in engine.rejected[0] and 0 not in got
    np.testing.assert_array_equal(
        got[1], _sequential(cfg, params, reqs[1:])[1])


def test_cow_divergence_and_refcount_release(qwen_smoke_cfg,
                                             qwen_smoke_params):
    """Copy-on-write: two live requests share resident prefix pages but
    write their decode tokens to private tail pages — divergent suffixes
    never cross-contaminate — and eviction drops refcounts so the arena
    returns to zero pages in use."""
    cfg, params = qwen_smoke_cfg, qwen_smoke_params
    prefix = lm_batch(cfg.vocab_size, 1, 17, seed=300)[0]
    reqs = [Request(uid=i,
                    prompt=np.concatenate(
                        [prefix, lm_batch(cfg.vocab_size, 1, 3 + i,
                                          seed=310 + i)[0]]),
                    max_new_tokens=6) for i in range(3)]
    engine = ContinuousBatchingEngine(cfg, params, capacity=1,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      k=4, pool="paged")
    got = engine.run(_clone(reqs))
    assert engine.n_prefix_hits >= 1
    want = _sequential(cfg, params, reqs)
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid],
                                      err_msg=f"uid {uid}")
    # all requests retired: flush releases every slot's pages; only
    # zero-ref registered pages may linger (LRU-retained for reuse)
    engine._flush_evictions()
    alloc = engine._alloc
    assert engine.pages_in_use == 0
    assert not engine._slot_pages
    # and the retained pages are reclaimable: a fresh burst fits
    got2 = engine.run(_clone(reqs, uid0=100))
    for uid in want:
        np.testing.assert_array_equal(got2[100 + uid], want[uid])
    assert alloc.highwater <= alloc.meta.n_pages


def test_page_allocator_refcounts_and_lru_reclaim():
    """PageAllocator unit contract: all-or-nothing alloc, refcounted
    release, digest registry lookups, and LRU reclaim of zero-ref
    registered pages when the free list runs dry."""
    alloc = PageAllocator(PoolMeta(page=8, nblk=2, n_pages=4))
    a = alloc.alloc(3)
    assert len(a) == 3 and alloc.pages_in_use() == 3
    assert alloc.alloc(2) is None  # only 1 free: all-or-nothing refusal
    assert alloc.pages_in_use() == 3  # the refused alloc grabbed nothing
    # register two of them under a digest chain, then fully release
    digs = prefix_digests(np.arange(16, dtype=np.int32), 8)
    alloc.register(digs, a[:2])
    assert alloc.lookup(digs) == a[:2]
    zero = alloc.release(a)
    # registered pages are retained (no zeroing) for future hits;
    # the unregistered page is returned for zeroing
    assert zero == [a[2]] and alloc.pages_in_use() == 0
    assert alloc.lookup(digs) == a[:2]
    # a hit increfs resident pages without touching the free list
    alloc.incref(a[:2])
    assert alloc.pages_in_use() == 2
    alloc.release(a[:2])
    # demand exceeding the free list reclaims the LRU retained pages
    b = alloc.alloc(4)
    assert b is not None and sorted(b) == sorted(range(4))
    assert alloc.lookup(digs) is None  # reclaim evicted the registry entry
    assert alloc.highwater == 4


def test_select_admissions_linear_not_quadratic(qwen_smoke_cfg,
                                                qwen_smoke_params):
    """Regression guard for the admission-scan bugfix: selecting from a
    deep waiting queue must scale ~linearly (one scan + one rebuild per
    wave), not quadratically (per-take deque deletes)."""
    cfg, params = qwen_smoke_cfg, qwen_smoke_params
    engine = ContinuousBatchingEngine(cfg, params, capacity=4,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      policy="spf")
    prompt = np.ones(4, np.int32)

    def timed(n):
        reqs = [Request(uid=i, prompt=prompt, max_new_tokens=1,
                        arrival=float(i % 7)) for i in range(n)]
        best = float("inf")
        for _ in range(3):
            engine.waiting = collections.deque(reqs)
            t0 = time.perf_counter()
            take = engine._select_admissions(now=1e9)
            best = min(best, time.perf_counter() - t0)
            assert len(take) == engine.capacity
        return best

    t_small, t_big = timed(500), timed(4000)
    # 8x the queue: linear ≈ 8x, the old quadratic path ≈ 64x.  The
    # bound sits far above linear noise and far below quadratic.
    assert t_big < 24 * max(t_small, 1e-5), (t_small, t_big)


def test_drain_resets_window_keeps_lifetime(qwen_smoke_cfg,
                                            qwen_smoke_params):
    """Regression: drain() used to clear results but leave the telemetry
    counters accumulating forever, so per-window rates (bench traces,
    acceptance checks) were polluted by history.  drain() must zero the
    window counters, fold them into lifetime totals, and clear the
    rejection log."""
    cfg, params = qwen_smoke_cfg, qwen_smoke_params
    engine = ContinuousBatchingEngine(cfg, params, capacity=2,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      k=4, pool="paged")
    engine.submit(Request(uid=999, prompt=np.zeros(MAX_LEN, np.int32),
                          max_new_tokens=4))  # rejected, not raised
    engine.run(_requests(cfg, [(4, 5), (6, 3)], seed0=400))
    w1 = {c: getattr(engine, c) for c in ("n_tokens", "n_prefills",
                                          "n_decode_dispatches")}
    assert w1["n_tokens"] == 8 and engine.rejected
    engine.drain()
    for c in w1:
        assert getattr(engine, c) == 0, c  # window reset
        assert engine.lifetime[c] == w1[c], c  # history kept
    assert not engine.rejected
    # a second window accumulates independently; totals = both windows
    engine.run(_requests(cfg, [(5, 2)], uid0=10, seed0=410))
    assert engine.n_tokens == 2
    assert engine.lifetime_totals()["n_tokens"] == w1["n_tokens"] + 2


def test_paged_pool_specs_match_engine(qwen_smoke_cfg, qwen_smoke_params):
    """launch/specs.py's abstract paged-pool specs must track the real
    engine pool (shape + dtype), or dry-run lowering drifts silently;
    unpageable configs must report None, matching the dense fallback."""
    from repro.launch import specs as specs_lib
    cfg, params = qwen_smoke_cfg, qwen_smoke_params
    engine = ContinuousBatchingEngine(cfg, params, capacity=2,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      pool="paged")
    spec = specs_lib.paged_slot_pool_specs(cfg, 2, MAX_LEN)
    assert jax.tree.map(lambda s: (s.shape, str(s.dtype)), spec) \
        == jax.tree.map(lambda a: (a.shape, str(a.dtype)), engine.pool)
    # slot-group families (xlstm conv tails) page too, and the abstract
    # specs track their engine pools the same way
    xcfg = _xlstm_cfg().replace(name="xlstm-spec")
    xengine = ContinuousBatchingEngine(xcfg, _params(xcfg), capacity=2,
                                       max_len=MAX_LEN, prefill_bucket=4,
                                       pool="paged")
    xspec = specs_lib.paged_slot_pool_specs(xcfg, 2, MAX_LEN)
    assert jax.tree.map(lambda s: (s.shape, str(s.dtype)), xspec) \
        == jax.tree.map(lambda a: (a.shape, str(a.dtype)), xengine.pool)
    # a config with nothing pageable reports None, matching the engine's
    # named dense fallback
    assert specs_lib.paged_slot_pool_specs(
        _griffin_rec_cfg(), 2, MAX_LEN) is None


def test_ring_prefix_hit_tail_restore_token_exact():
    """Windowed prefix sharing: admission registers absolute-position
    copies of the ring's registrable tail pages, and later identical
    prefixes HIT — the new slot's ring is reconstructed from the resident
    pages plus a short tail replay, skipping the full prefill.  Fewer
    prefill batches, hit rate > 0, tokens exactly equal to the dense
    engine's and generate()'s."""
    cfg = _window9_cfg()
    params = _params(cfg)
    prompt = lm_batch(cfg.vocab_size, 1, 13, seed=800)[0]
    reqs = [Request(uid=i, prompt=prompt.copy(), max_new_tokens=6)
            for i in range(6)]
    got_d, got_p, engine = _run_both(cfg, params, reqs, capacity=2,
                                     pages=8)
    assert engine.pool_kind == "paged" and engine._windowed
    assert engine.n_prefix_hits > 0
    assert engine.prefix_hit_rate > 0
    dense = ContinuousBatchingEngine(cfg, params, capacity=2,
                                     max_len=MAX_LEN, prefill_bucket=4,
                                     k=4, pool="dense")
    dense.run(_clone(reqs, uid0=100))
    assert engine.n_prefills < dense.n_prefills  # prefill batches drop
    _arena_invariants(engine)
    _assert_equal(got_d, got_p, _sequential(cfg, params, reqs))


def test_ring_too_tight_for_sharing_stays_exact():
    """window=8 over an 8-deep single-page ring can NEVER serve a prefix
    hit (the prompt's partial tail page always clobbers the one ring
    page) — the slack gate must keep sharing off rather than serve
    garbage, and the trace stays token-exact."""
    cfg = _window_cfg()
    params = _params(cfg)
    prompt = lm_batch(cfg.vocab_size, 1, 13, seed=810)[0]
    reqs = [Request(uid=i, prompt=prompt.copy(), max_new_tokens=6)
            for i in range(4)]
    got_d, got_p, engine = _run_both(cfg, params, reqs, capacity=2,
                                     pages=8)
    assert not engine._prefix_ok  # (nblk-1)*page + 1 < window
    assert engine.n_prefix_hits == 0
    _assert_equal(got_d, got_p, _sequential(cfg, params, reqs))


def test_sampled_prefix_hit_chain_exact_replay(qwen_smoke_cfg,
                                               qwen_smoke_params):
    """Prefix hits no longer require greedy decode: a sampled admission
    replays the skipped prefill's PRNG chain (one advance per sampled
    prompt-tail draw, exactly as ``prefill_sampled`` would have), so a
    hit emits the very token sequence a miss would have — asserted
    against a dense SAMPLED engine and hit rate > 0."""
    from repro.serve.sampling import SamplingParams
    cfg, params = qwen_smoke_cfg, qwen_smoke_params
    sp = SamplingParams(temperature=0.9, top_k=12, seed=11)
    prompt = lm_batch(cfg.vocab_size, 1, 19, seed=820)[0]
    reqs = [Request(uid=i, prompt=prompt.copy(), max_new_tokens=6)
            for i in range(6)]
    got_d, got_p, engine = _run_both(cfg, params, reqs, capacity=2,
                                     pages=16, sampling=sp)
    assert engine.n_prefix_hits > 0 and engine.prefix_hit_rate > 0
    dense = ContinuousBatchingEngine(cfg, params, capacity=2,
                                     max_len=MAX_LEN, prefill_bucket=4,
                                     k=4, pool="dense", sampling=sp)
    dense.run(_clone(reqs, uid0=100))
    assert engine.n_prefills < dense.n_prefills
    # per-uid chains: identical prompts still sample DISTINCT sequences
    outs = {tuple(np.asarray(got_p[u]).tolist()) for u in got_p}
    assert len(outs) > 1
    _assert_equal(got_d, got_p)


def test_shared_arena_draft_target_trade_pages(gpt_micro_cfg):
    """Speculative serving allocates from ONE physical arena: a request
    books its worst-case page count once, holding a reference in BOTH
    engine namespaces, and pages freed when draft+target retire a slot
    are immediately reusable by the next admission — a tight ``--pages``
    budget that a static split would deadlock serves the whole trace
    without backpressure."""
    from repro.models import get_family
    cfg = gpt_micro_cfg
    params = get_family(cfg).init(jax.random.PRNGKey(0), cfg)
    params_d = get_family(cfg).init(jax.random.PRNGKey(7), cfg)
    specs = [(9, 8), (10, 7), (11, 6), (9, 5), (12, 4), (10, 8)]
    reqs = _requests(cfg, specs, seed0=830)
    # 3 pages per request shared across both pools; 6 pages run 2 slots
    got_d, got_p, engine = _run_both(
        cfg, params, reqs, capacity=2, k=2, pages=6,
        speculative=SpeculativeConfig(cfg.replace(name="gpt-micro-draft"),
                                      params_d, d=2))
    assert engine.pool_kind == "paged"
    assert engine._alloc.namespaces == 2
    assert engine.pages_highwater <= 6
    assert set(got_p) == {r.uid for r in reqs}  # nobody starved
    # page ids were RECYCLED across waves (one id space, not a split)
    assert engine.n_pages_allocated > 6
    # both pools' block tables resolved the SAME page ids while live
    # (checked post-hoc via the allocator: every page that was ever
    # allocated carried a reference in both namespaces)
    _arena_invariants(engine)
    engine._flush_evictions()
    assert engine.pages_in_use == 0
    _assert_equal(got_d, got_p, _sequential(cfg, params, reqs))


def test_shared_arena_namespace_release_contract():
    """Allocator-level twin of the trade test: a page allocated into
    both namespaces survives the draft's release (still held by the
    target), frees + zeroes only on the LAST namespace's release, and is
    immediately reallocatable."""
    alloc = PageAllocator(PoolMeta(page=8, nblk=2, n_pages=4),
                          namespaces=2)
    a = alloc.alloc(3, ns=(0, 1))
    assert len(a) == 3 and alloc.pages_in_use() == 3
    assert alloc.release(a, ns=1) == []  # draft retires: target holds on
    assert alloc.pages_in_use() == 3
    zero = alloc.release(a, ns=0)        # target retires: free + zero
    assert sorted(zero) == sorted(a) and alloc.pages_in_use() == 0
    b = alloc.alloc(4, ns=(0,))          # every page immediately reusable
    assert b is not None and alloc.alloc(1) is None
    assert alloc.highwater == 4


def test_page_allocator_conservation_property():
    """Property-style sweep: under a random interleaving of alloc /
    release / register / incref / flush ops, the allocator never
    violates conservation (free ∪ held ∪ LRU-retained partitions the id
    space, disjointly) and the registry stays a bijection onto resident
    pages."""
    rng = np.random.default_rng(0)
    meta = PoolMeta(page=8, nblk=4, n_pages=16)
    alloc = PageAllocator(meta, namespaces=2)
    digs = prefix_digests(np.arange(64 * 8, dtype=np.int32), 8)
    held = []

    def check():
        n = meta.n_pages
        free, lru = set(alloc.free), set(alloc.lru)
        in_use = {p for p in range(n) if alloc.refcount[p].sum() > 0}
        assert len(alloc.free) == len(free)
        assert not (free & in_use) and not (free & lru)
        assert not (lru & in_use)
        assert free | in_use | lru == set(range(n))
        assert (alloc.refcount >= 0).all()
        for pid, d in alloc.page_key.items():
            assert alloc.registry.get(d) == pid
        assert len(alloc.registry) == len(alloc.page_key)
        assert alloc.pages_in_use() == len(in_use)

    for step in range(400):
        op = int(rng.integers(5))
        if op == 0:
            ns = ((0,), (0, 1))[int(rng.integers(2))]
            got = alloc.alloc(int(rng.integers(1, 5)), ns=ns)
            if got is not None:
                held.append((got, ns))
                if rng.integers(2):
                    j = int(rng.integers(len(digs) - len(got)))
                    alloc.register(digs[j:j + len(got)], got)
        elif op == 1 and held:
            pids, ns = held.pop(int(rng.integers(len(held))))
            for i in ns:
                alloc.release(pids, ns=i)
        elif op == 2 and alloc.lru:
            pid = next(iter(alloc.lru))
            alloc.incref([pid])
            held.append(([pid], (0,)))
        elif op == 3 and not rng.integers(8):
            alloc.flush_registry()
        check()
    for pids, ns in held:  # drain: everything comes back
        for i in ns:
            alloc.release(pids, ns=i)
    alloc.flush_registry()
    assert alloc.pages_in_use() == 0
    assert len(alloc.free) == meta.n_pages


def test_oversize_rejection_is_resubmittable(qwen_smoke_cfg,
                                             qwen_smoke_params):
    """A rejected request is not burned: its uid stays reusable, the
    reason is recorded, and the trace around it keeps serving."""
    cfg, params = qwen_smoke_cfg, qwen_smoke_params
    engine = ContinuousBatchingEngine(cfg, params, capacity=1,
                                      max_len=MAX_LEN, prefill_bucket=4,
                                      pool="paged")
    engine.submit(Request(uid=0, prompt=np.zeros(30, np.int32),
                          max_new_tokens=8))  # 30 + 8 > 32
    assert "exceeds max_len" in engine.rejected[0]
    # resubmit the same uid with a servable budget: accepted this time
    got = engine.run(_requests(cfg, [(4, 3)], seed0=500))
    assert set(got) == {0} and len(got[0]) == 3


@pytest.mark.parametrize("kernel", ["jnp", "interpret"])
@pytest.mark.parametrize("window", [None, 16])
def test_paged_slot_loop_layer_offsets(gpt_micro_cfg, window, kernel):
    """The decode loop carries each paged arena whole through the layer
    scan and reaches layer ``l``'s pages through an offset block table.
    Over a pool of random bytes with one live row, one done row and one
    live row whose table holds sentinel entries where it writes: every
    page no live row owns keeps its bytes in every layer (a sentinel or a
    done row's write shifted onto another layer would show), the block
    table comes back unchanged, and the fully-paged live row decodes the
    same tokens as the dense pool holding the same bytes."""
    from repro.models import attention as attn_lib
    from repro.models import get_family
    from repro.serve.paged import build_paged_pool
    from repro.train.steps import make_slot_decode_loop

    cfg = gpt_micro_cfg.replace(decode_kernel=kernel, window=window)
    fam = get_family(cfg)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    pool, meta = build_paged_pool(fam, cfg, 3, MAX_LEN)
    n, nblk, S = meta.n_pages, meta.nblk, meta.nblk * meta.page
    grp = pool["dense"]
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    for key, lk in zip(keys, ("k", "v")):
        grp[lk] = jax.random.normal(key, grp[lk].shape, grp[lk].dtype)
    sent = meta.sentinel
    if window is None:  # page 8, 4 blocks: positions 9.., 10, 7..
        bt = [[5, 6, 7, 8], [1, 2, sent, sent], [3, sent, sent, sent]]
        positions, live_pages, partial = [9, 10, 7], {5, 6, 7, 8, 3}, 3
    else:  # a 16-deep ring of 2 pages: row 0 has wrapped
        bt = [[2, 3], [4, 5], [1, sent]]
        positions, live_pages, partial = [20, 10, 5], {2, 3, 1}, 1
    assert len(bt[0]) == nblk
    grp["bt"] = jnp.broadcast_to(jnp.asarray(bt, jnp.int32),
                                 grp["bt"].shape)
    free = sorted(set(range(n)) - live_pages)
    assert 0 in free  # where a shifted sentinel write would land
    before = jax.tree.map(np.asarray, grp)

    def run(cache):
        loop = jax.jit(make_slot_decode_loop(cfg, 4))
        tokens = jnp.asarray([11, 12, 13], jnp.int32)
        out = loop(params, tokens, jnp.asarray(positions, jnp.int32),
                   jnp.full((3,), 100, jnp.int32),
                   jnp.full((3,), -1, jnp.int32),
                   jnp.asarray([False, True, False]), cache)
        return np.asarray(out[0]), out[-1]

    dense = {"dense": {lk: jax.vmap(attn_lib.paged_gather)(grp[lk], grp["bt"])
                       for lk in ("k", "v")}}
    assert dense["dense"]["k"].shape[2] == S
    got, new = run(pool)
    want, _ = run(dense)
    np.testing.assert_array_equal(got[:, 0], want[:, 0])
    after = jax.tree.map(np.asarray, new["dense"])
    np.testing.assert_array_equal(after["bt"], before["bt"])
    for lk in ("k", "v"):
        np.testing.assert_array_equal(after[lk][:, free],
                                      before[lk][:, free], err_msg=lk)
        # the row with sentinel entries wrote its one real page
        assert not np.array_equal(after[lk][:, partial],
                                  before[lk][:, partial])
