"""Serving benchmark: naive lock-step vs per-token vs macro-step engines,
swept over model families (transformer / griffin / xlstm).

A Poisson arrival trace of mixed-length requests is replayed against
wall-clock time through three serving paths:

  * naive      — requests are collected into fixed batches; each batch
                 waits for all its members to arrive, then runs prefill +
                 lock-step decode to the LONGEST request's length
                 (``launch/serve.generate``); the next batch waits behind;
  * per-token  — the slot-pool engine with K=1 and no readback pipeline:
                 one jitted decode dispatch AND one blocking host sync per
                 generated token (the PR 1 engine's host-interaction
                 pattern);
  * macro-step — the slot-pool engine with K>1: K decode steps run on
                 device under one ``lax.scan`` dispatch, readback is
                 double-buffered, and admission is batched — the host
                 syncs ~1/K times per token.

The arrival rate is set high enough that the engines (not the trace) are
the bottleneck, so tok/s compares engine speed.  Reported per engine:
total tok/s, per-request completion-latency percentiles (p50/p99, seconds
from arrival to last token), and host syncs per generated token.  Results
are also written to ``BENCH_serve_engine.json`` at the repo root; every
entry records its ``family`` and slot-pool ``cache_layout`` (full KV vs
ring-buffer window vs recurrent state) so the perf trajectory
distinguishes transformer, griffin, and xlstm serving.

The transformer family runs the full comparison (naive + per-token +
macro K-sweep); the recurrent families run per-token vs one macro point —
enough to track their serving speed without tripling the bench runtime.

A ``--speculate`` sweep benches the speculative engine on the paper's
own pair: the SOURCE model (gpt-micro) is pretrained on the synthetic
task, the target (gpt-micro-big) is grown from it with a Mango operator
trained for a few steps (Eq. 7), and the source then drafts for its
grown target.  Entries record ``acceptance_rate`` plus the draft/target
config names next to tok/s, so the perf trajectory ties speedup to
draft quality.  A ``--pool`` sweep benches dense-vs-paged pairs per
family (transformer mixed + shared-prefix traces, griffin ring pages,
xlstm slot-tail pages) plus two prefix-sharing traces that used to be
gated off — a window-9 ring (tail-restore hits) and a seeded sampled
trace (chain-replay hits) — recording pages-in-use high-water,
prefix-cache hit rate, and pages-per-request next to tok/s: each
dense-vs-paged pair is the direct measure of the paged pool's
reservation and re-prefill savings.  A ``--chaos`` sweep benches the
fault-tolerance layer: the
``chaos_faultfree`` entry pins the journaling overhead (its
``host_syncs_per_token`` must match the plain macro entry — flushes
ride existing readbacks), ``chaos_injected`` records survival rate
under a seeded nan/oom/slow/malformed plan with every survivor
token-checked against the fault-free run, and ``chaos_crash`` kills
the engine mid-trace and records the journal-restart recovery latency.
A ``--mesh`` sweep benches sharded serving on 4 visible devices
(2x2 data x model, dense and paged) against single-device on the same
trace, asserting token-exactness and that ``host_syncs_per_token``
does not regress; every JSON entry records ``mesh_shape``/``n_devices``
(pre-sharding entries backfill as 1x1 so the schema stays uniform).
Partial runs (``--family``, ``--speculate``, ``--pool``, ``--chaos``,
``--mesh``) MERGE into ``BENCH_serve_engine.json`` — they never clobber
the other sections' trajectory entries.

Run:  PYTHONPATH=src:. python benchmarks/bench_serve_engine.py [--quick]
          [--family transformer|griffin|xlstm|all|none] [--speculate]
          [--pool] [--chaos] [--mesh]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import write_bench_json
from repro.configs.base import get_config
from repro.data.synthetic import lm_batch
from repro.launch.serve import generate
from repro.models import get_family, slot_cache_layout
from repro.serve import ContinuousBatchingEngine, Request, SpeculativeConfig

K_SWEEP = (4, 8, 16)

# one smoke arch per family; recurrentgemma's window (32) is smaller than
# the bench max_len (48), so its slots genuinely wrap the ring buffer
FAMILY_ARCHS = {
    "transformer": "qwen1.5-0.5b-smoke",
    "griffin": "recurrentgemma-2b-smoke",
    "xlstm": "xlstm-1.3b-smoke",
}

# the speculative pair: pretrained source drafts for its grown target
SPEC_DRAFT = "gpt-micro"
SPEC_TARGET = "gpt-micro-big"
SPEC_D_SWEEP = (2, 4)
SPEC_K = 2  # speculative blocks per dispatch (each commits up to d+1 tok)


def poisson_trace(cfg, n, *, rate_hz, seed=0, max_prompt=24, max_gen=16):
    """n requests with exponential inter-arrival gaps at ``rate_hz``."""
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for uid in range(n):
        t += float(rng.exponential(1.0 / rate_hz))
        plen = int(rng.integers(4, max_prompt + 1))
        gen = int(rng.integers(2, max_gen + 1))
        prompt = lm_batch(cfg.vocab_size, 1, plen, seed=300 + uid)[0]
        reqs.append(Request(uid=uid, prompt=prompt, max_new_tokens=gen,
                            arrival=t))
    return reqs


def prefix_trace(cfg, n, *, rate_hz, seed=0, prefix_len=18, max_gen=12):
    """n requests that all share one ``prefix_len``-token prompt prefix
    (distinct short tails), arriving at ``rate_hz``.  Against the paged
    pool's copy-on-write prefix cache, every request after the first
    admission wave hits resident pages and skips its prefix prefill."""
    rng = np.random.default_rng(seed)
    prefix = lm_batch(cfg.vocab_size, 1, prefix_len, seed=701)[0]
    t = 0.0
    reqs = []
    for uid in range(n):
        t += float(rng.exponential(1.0 / rate_hz))
        tail = lm_batch(cfg.vocab_size, 1, 2 + uid % 3, seed=900 + uid)[0]
        gen = int(rng.integers(2, max_gen + 1))
        reqs.append(Request(uid=uid,
                            prompt=np.concatenate([prefix, tail]),
                            max_new_tokens=gen, arrival=t))
    return reqs


def _pctl(lat):
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 99)))


def warm_naive(cfg, params, reqs, batch):
    """Compile every (chunk, pmax, gmax) shape the naive loop will hit, so
    the timed comparison measures serving, not XLA retraces."""
    for i in range(0, len(reqs), batch):
        chunk = reqs[i:i + batch]
        pmax = max(len(r.prompt) for r in chunk)
        gmax = max(r.max_new_tokens for r in chunk)
        generate(cfg, params, jnp.zeros((len(chunk), pmax), jnp.int32),
                 max_new_tokens=gmax)


def warm_engine(cfg, params, reqs, *, capacity, max_len, k,
                speculative=None, pool="dense", pages=None, sampling=None):
    """Compile every shape a (cfg, k) engine can hit on this trace: the
    macro (or speculative) loop, and each (pow2 admission-group size,
    prefill bucket) prefill/scatter pair.  With ``pool='paged'`` the
    uniform warm prompts also hit the prefix cache after the first wave,
    compiling the hit-admission scan."""
    warm = ContinuousBatchingEngine(cfg, params, capacity=capacity,
                                    max_len=max_len, k=k,
                                    speculative=speculative, pool=pool,
                                    pages=pages, sampling=sampling)
    buckets = sorted({warm._bucketed(len(r.prompt)) for r in reqs})
    uid = -1
    n = 1
    while n <= capacity:
        for b in buckets:
            # distinct prompt CONTENT per request: identical prompts
            # would hit the paged prefix cache after the first wave and
            # skip the miss-path prefill this loop exists to compile
            warm.run([Request(
                uid=uid - i,
                prompt=np.full(b, (i - uid) % (cfg.vocab_size - 1) + 1,
                               np.int32),
                max_new_tokens=2) for i in range(n)])
            uid -= n
        n *= 2
    if getattr(warm, "pool_kind", "dense") == "paged":
        # now the opposite: IDENTICAL prompts, so waves past the first
        # hit resident prefix pages and compile the hit-admission scan
        shared = np.zeros(max(buckets), np.int32)
        n = 1
        while n <= capacity:
            warm.run([Request(uid=uid - i, prompt=shared,
                              max_new_tokens=2) for i in range(n)])
            uid -= n
            n *= 2
    return warm


def bench_naive(cfg, params, reqs, batch):
    t0 = time.monotonic()
    lat = []
    n_tok = 0
    for i in range(0, len(reqs), batch):
        chunk = reqs[i:i + batch]
        wait = max(r.arrival for r in chunk) - (time.monotonic() - t0)
        if wait > 0:  # the whole batch must have arrived before it can run
            time.sleep(wait)
        pmax = max(len(r.prompt) for r in chunk)
        gmax = max(r.max_new_tokens for r in chunk)
        prompts = np.zeros((len(chunk), pmax), np.int32)
        for j, r in enumerate(chunk):
            prompts[j, pmax - len(r.prompt):] = r.prompt  # left-pad
        toks = generate(cfg, params, jnp.asarray(prompts),
                        max_new_tokens=gmax)
        jax.block_until_ready(toks)
        done = time.monotonic() - t0
        for r in chunk:
            lat.append(done - r.arrival)
            n_tok += r.max_new_tokens
    tput = n_tok / (time.monotonic() - t0)
    p50, p99 = _pctl(lat)
    return {"tok_per_s": tput, "p50_s": p50, "p99_s": p99}


def bench_engine(cfg, params, reqs, *, capacity, max_len, k, pipeline,
                 speculative=None, pool="dense", pages=None, sampling=None):
    engine = ContinuousBatchingEngine(cfg, params, capacity=capacity,
                                      max_len=max_len, k=k,
                                      speculative=speculative, pool=pool,
                                      pages=pages, sampling=sampling)
    t0 = time.monotonic()
    engine.run(reqs, realtime=True, pipeline=pipeline)
    dt = time.monotonic() - t0
    n_tok = sum(len(v) for v in engine.finished.values())
    by_uid = {r.uid: r for r in reqs}
    # t_done stamps are absolute monotonic times; arrivals are trace offsets
    lat = [(s.t_done - t0) - by_uid[s.req.uid].arrival
           for s in engine.retired]
    p50, p99 = _pctl(lat)
    assert n_tok == engine.n_tokens  # engine accounting matches outputs
    out = {"tok_per_s": n_tok / dt, "p50_s": p50, "p99_s": p99,
           "host_syncs_per_token": engine.n_host_syncs / max(n_tok, 1),
           "decode_dispatches": engine.n_decode_dispatches,
           "prefill_batches": engine.n_prefills, "k": k,
           "decode_kernel": engine.decode_kernel}
    if speculative is not None:
        out["acceptance_rate"] = engine.acceptance_rate
        out["d"] = speculative.d
        out["draft"] = speculative.cfg.name
    out["pool"] = engine.pool_kind
    if engine.pool_kind == "paged":
        meta = engine._metas[0]
        out["pages_highwater"] = engine.pages_highwater
        out["prefix_hit_rate"] = engine.prefix_hit_rate
        out["pages_per_request"] = (engine.n_pages_allocated
                                    / max(len(reqs), 1))
        # what one slot reserves under the dense pool, in page units —
        # the over-reservation the paged pool avoids
        out["dense_reservation_pages"] = meta.nblk
        out["rejected"] = len(engine.rejected)
    return out


def _bench_family(family: str, quick: bool):
    """One family's sweep.  The transformer (the original trajectory)
    keeps its naive/pertoken/macro-K comparison and top-level keys; the
    recurrent families run pertoken vs one macro point under
    ``<family>_``-prefixed keys."""
    cfg = get_config(FAMILY_ARCHS[family])
    fam = get_family(cfg)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    primary = family == "transformer"
    n = (12 if quick else 64) if primary else (8 if quick else 32)
    capacity = 4
    max_len = 48
    k_sweep = (K_SWEEP[:2] if quick else K_SWEEP) if primary else (8,)
    # arrival rate far above the service rate, so the engine — not the
    # trace — is the bottleneck and tok/s measures serving speed, not load
    reqs = poisson_trace(cfg, n, rate_hz=2000.0,
                         max_gen=16 if quick else 24)

    # warm every engine's compile cache outside the timed runs
    if primary:
        warm_naive(cfg, params, reqs, capacity)
    for k in (1,) + tuple(k_sweep):
        warm_engine(cfg, params, reqs, capacity=capacity, max_len=max_len,
                    k=k)

    def fresh():
        return [Request(uid=r.uid, prompt=r.prompt,
                        max_new_tokens=r.max_new_tokens, arrival=r.arrival)
                for r in reqs]

    prefix = "" if primary else f"{family}_"
    results = {}
    if primary:
        results["naive"] = bench_naive(cfg, params, fresh(), batch=capacity)
    results[f"{prefix}pertoken"] = bench_engine(
        cfg, params, fresh(), capacity=capacity, max_len=max_len, k=1,
        pipeline=False)
    for k in k_sweep:
        results[f"{prefix}macro_k{k}"] = bench_engine(
            cfg, params, fresh(), capacity=capacity, max_len=max_len, k=k,
            pipeline=True)
    layout = slot_cache_layout(cfg)
    for m in results.values():
        m["family"] = family
        m["cache_layout"] = layout
    return results


def _spec_pair(quick: bool):
    """Build the paper's speculative pair: PRETRAIN the source on the
    synthetic LM task, then grow the target from it with a Mango operator
    trained on the task loss (Eq. 7).  The grown target approximates the
    source's function at init — exactly what makes the source a
    well-matched draft — so the measured acceptance rate reflects the
    paper's setting, not random-init luck."""
    from repro.core import grow as growlib
    from repro.data.synthetic import lm_data_iter
    from repro.optim import OptimizerConfig, make_optimizer
    from repro.train.steps import make_train_step

    cfg_d, cfg_t = get_config(SPEC_DRAFT), get_config(SPEC_TARGET)
    fam_d = get_family(cfg_d)
    params_d = fam_d.init(jax.random.PRNGKey(0), cfg_d)
    opt_cfg = OptimizerConfig(lr=3e-3, weight_decay=1e-2)
    opt = make_optimizer(opt_cfg)[0](params_d)
    step_fn = jax.jit(make_train_step(cfg_d, opt_cfg),
                      donate_argnums=(0, 1))
    data = lm_data_iter(cfg_d.vocab_size, 8, 64, seed=0)
    for step in range(60 if quick else 120):
        b = {kk: jnp.asarray(v) for kk, v in next(data).items()}
        params_d, opt, _ = step_fn(params_d, opt, b, jnp.int32(step + 1))
    params_t = growlib.grow_from_source(
        cfg_d, cfg_t, method="mango", rank=1, steps=10 if quick else 30,
        data_iter=lm_data_iter(cfg_t.vocab_size, 4, 32, seed=1),
        params_src=params_d, log_fn=lambda *a: None)
    return cfg_t, params_t, cfg_d, params_d


def _bench_speculative(quick: bool):
    """Speculative sweep: non-speculative macro baseline vs d-sweep on
    the grown target, acceptance rate recorded per entry."""
    cfg_t, params_t, cfg_d, params_d = _spec_pair(quick)
    n = 16 if quick else 48
    capacity, max_len = 4, 48
    # speculation pays off on the decode side (it double-pays prefill for
    # the second pool), so even the quick trace keeps full-length
    # generations — only the request count shrinks
    reqs = poisson_trace(cfg_t, n, rate_hz=2000.0, max_prompt=16,
                         max_gen=24)

    def fresh():
        return [Request(uid=r.uid, prompt=r.prompt,
                        max_new_tokens=r.max_new_tokens, arrival=r.arrival)
                for r in reqs]

    results = {}
    warm_engine(cfg_t, params_t, reqs, capacity=capacity, max_len=max_len,
                k=8)
    results["spec_baseline_k8"] = bench_engine(
        cfg_t, params_t, fresh(), capacity=capacity, max_len=max_len, k=8,
        pipeline=True)
    for d in SPEC_D_SWEEP:
        spec = SpeculativeConfig(cfg_d, params_d, d=d)
        warm_engine(cfg_t, params_t, reqs, capacity=capacity,
                    max_len=max_len, k=SPEC_K, speculative=spec)
        results[f"spec_d{d}"] = bench_engine(
            cfg_t, params_t, fresh(), capacity=capacity, max_len=max_len,
            k=SPEC_K, pipeline=True,
            speculative=SpeculativeConfig(cfg_d, params_d, d=d))
    layout = slot_cache_layout(cfg_t)
    for m in results.values():
        m["family"] = cfg_t.family
        m["cache_layout"] = layout
        m["target"] = cfg_t.name
    return results


def _bench_kernel_modes(quick: bool, kernel_mode: str):
    """Kernel-vs-jnp slot decode, side by side, full-KV and ring-window.

    Same trace, same K, only ``cfg.decode_kernel`` differs — the entry
    pair is the direct measure of the kernel-backed slot path.
    ``kernel_mode`` is ``"auto"`` (compiled Pallas, TPU only) or
    ``"interpret"`` (the Pallas INTERPRETER, orders of magnitude slower
    than compiled — such entries document correctness cost, not TPU
    speed).  The trace is kept small for the interpreter's sake.
    """
    cfg = get_config(FAMILY_ARCHS["transformer"])
    fam = get_family(cfg)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    n = 4 if quick else 8
    capacity, max_len, k = 2, 48, 8
    reqs = poisson_trace(cfg, n, rate_hz=2000.0, max_gen=8 if quick else 16)

    def fresh():
        return [Request(uid=r.uid, prompt=r.prompt,
                        max_new_tokens=r.max_new_tokens, arrival=r.arrival)
                for r in reqs]

    results = {}
    for tag, cfg_m in (("jnp", cfg),
                       (kernel_mode,
                        cfg.replace(decode_kernel=kernel_mode))):
        for wcfg in (cfg_m, cfg_m.replace(name=cfg.name + "-win", window=16)):
            layout = slot_cache_layout(wcfg)
            warm_engine(wcfg, params, reqs, capacity=capacity,
                        max_len=max_len, k=k)
            m = bench_engine(wcfg, params, fresh(), capacity=capacity,
                             max_len=max_len, k=k, pipeline=True)
            m["family"] = wcfg.family
            m["cache_layout"] = layout
            key = "kernel_" + ("ring_" if wcfg.window else "") + tag
            results[key + f"_k{k}"] = m
    return results


def _bench_pool_modes(quick: bool):
    """Dense vs paged slot pool, side by side:

      * mixed / prefix — the transformer trajectory pairs: a Poisson
        trace of unrelated prompts (paged indirection overhead,
        pages-per-request vs the dense full reservation) and a trace
        sharing one prompt prefix (copy-on-write hit rate, fewer
        prefill batches);
      * griffin / xlstm — per-family pairs on the mixed trace: these
        families no longer silently fall back to dense (griffin pages
        its attention rings, xlstm its conv tails), so the pairs price
        the indirection where only part of the pool pages;
      * ring_prefix — a window-9 transformer (its padded ring holds one
        page of slack over the window, the tail-restore gate) on the
        shared-prefix trace with explicit arena headroom — registration
        copies need free pages — so ``prefix_hit_rate`` measures ring
        tail-restore sharing;
      * sampled_prefix — seeded non-greedy sampling on the shared-prefix
        trace: a hit replays the request's per-uid PRNG chain on device,
        so sharing survives sampled serving (``prefix_hit_rate`` > 0
        without ``sampling is None``).

    Same trace, same K, only ``pool=`` differs per pair — the paged
    engine is token-exact vs dense (tested in test_paged_pool.py), so the
    pairs compare cost, not quality.
    """
    from repro.serve import SamplingParams

    cfg = get_config(FAMILY_ARCHS["transformer"])
    fam = get_family(cfg)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    n = 8 if quick else 24
    capacity, max_len, k = 4, 48, 8

    results = {}

    def _pair(tag, pcfg, pparams, reqs, *, pages=None, sampling=None):
        layout = slot_cache_layout(pcfg)

        def fresh():
            return [Request(uid=r.uid, prompt=r.prompt,
                            max_new_tokens=r.max_new_tokens,
                            arrival=r.arrival) for r in reqs]

        for pool in ("dense", "paged"):
            warm_engine(pcfg, pparams, reqs, capacity=capacity,
                        max_len=max_len, k=k, pool=pool, pages=pages,
                        sampling=sampling)
            # dry-run the exact trace untimed, in BOTH admission shapes
            # (batch and realtime trickle): hit-admission replay scans
            # compile per (group size, tail length), which the synthetic
            # warm prompts cannot cover
            for realtime in (False, True):
                ContinuousBatchingEngine(
                    pcfg, pparams, capacity=capacity, max_len=max_len,
                    k=k, pool=pool, pages=pages, sampling=sampling,
                ).run(fresh(), realtime=realtime, pipeline=realtime)
            m = bench_engine(pcfg, pparams, fresh(), capacity=capacity,
                             max_len=max_len, k=k, pipeline=True,
                             pool=pool, pages=pages, sampling=sampling)
            m["family"] = pcfg.family
            m["cache_layout"] = layout
            results[f"pool_{pool}_{tag}_k{k}"] = m

    _pair("mixed", cfg, params,
          poisson_trace(cfg, n, rate_hz=2000.0, max_gen=8 if quick else 16))
    _pair("prefix", cfg, params,
          prefix_trace(cfg, n, rate_hz=2000.0, max_gen=8 if quick else 12))

    # per-family pairs on a mixed trace (smaller: recurrent compiles are
    # the cost here, not tokens)
    nf = 6 if quick else 16
    for family in ("griffin", "xlstm"):
        fcfg = get_config(FAMILY_ARCHS[family])
        fparams = get_family(fcfg).init(jax.random.PRNGKey(0), fcfg)
        _pair(family, fcfg, fparams,
              poisson_trace(fcfg, nf, rate_hz=2000.0, max_gen=6))

    # ring tail-restore sharing: window 9 pads its ring to 16 (page 8,
    # nblk 2), satisfying the slack gate; --pages headroom lets the
    # best-effort registration copies actually land
    wcfg = cfg.replace(name=cfg.name + "-win9", window=9)
    _pair("ring_prefix", wcfg, params,
          prefix_trace(wcfg, n, rate_hz=2000.0, max_gen=8 if quick else 12),
          pages=16)

    # sampled replay sharing: hits must emit the same chain-sampled
    # tokens a miss admission would
    _pair("sampled_prefix", cfg, params,
          prefix_trace(cfg, n, rate_hz=2000.0, max_gen=8 if quick else 12),
          sampling=SamplingParams(temperature=0.9, top_k=12, seed=11))
    return results


def _bench_chaos(quick: bool):
    """Fault-tolerance cost and recovery, measured:

      * chaos_faultfree — the same trace on a journal-attached engine
        with an EMPTY fault plan: its ``host_syncs_per_token`` vs the
        plain ``macro_k8`` entry is the direct price of journaling
        (the acceptance bar is: none — flushes ride existing syncs);
      * chaos_injected  — a seeded plan (nan/oom/slow/malformed) against
        the same trace: ``survival_rate`` is the fraction of requests
        finishing normally, and every survivor is asserted token-equal
        to the fault-free run (a mismatch raises — the bench doubles as
        an integration check);
      * chaos_crash     — kill the engine mid-trace, rebuild from the
        journal, finish: ``recovery_latency_s`` is construction +
        journal replay + re-admission prefill of the resumed requests
        (first token of the first resumed request), and survivors are
        again token-checked.
    """
    from repro.serve import (EngineKilled, FaultPlan, RequestJournal,
                             read_journal, recovery_requests)
    import tempfile

    cfg = get_config(FAMILY_ARCHS["transformer"])
    fam = get_family(cfg)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    n = 8 if quick else 24
    capacity, max_len, k = 4, 48, 8
    reqs = poisson_trace(cfg, n, rate_hz=2000.0, max_gen=8 if quick else 16)

    def fresh():
        return [Request(uid=r.uid, prompt=r.prompt,
                        max_new_tokens=r.max_new_tokens, arrival=r.arrival)
                for r in reqs]

    warm_engine(cfg, params, reqs, capacity=capacity, max_len=max_len, k=k)
    results = {}
    tmp = tempfile.mkdtemp(prefix="chaos_journal_")
    layout = slot_cache_layout(cfg)

    # fault-free, journal attached: the journaling overhead entry
    j0 = RequestJournal(f"{tmp}/faultfree.jsonl")
    e0 = ContinuousBatchingEngine(cfg, params, capacity=capacity,
                                  max_len=max_len, k=k, journal=j0,
                                  faults=FaultPlan([]))
    t0 = time.monotonic()
    e0.run(fresh(), realtime=True, pipeline=True)
    dt = time.monotonic() - t0
    j0.close()
    want = dict(e0.finished)
    n_tok = sum(len(v) for v in want.values())
    results["chaos_faultfree_k8"] = {
        "tok_per_s": n_tok / dt, "p50_s": 0.0, "p99_s": 0.0,
        "host_syncs_per_token": e0.n_host_syncs / max(n_tok, 1),
        "survival_rate": 1.0, "journaled": True, "k": k,
    }

    # seeded non-crash plan: survival + blast radius
    plan = FaultPlan.seeded(3, 10, kinds=("nan", "oom", "slow",
                                          "malformed"), n_faults=3,
                            slow_s=0.01)
    e1 = ContinuousBatchingEngine(cfg, params, capacity=capacity,
                                  max_len=max_len, k=k, faults=plan)
    t0 = time.monotonic()
    e1.run(fresh(), realtime=True, pipeline=True)
    dt = time.monotonic() - t0
    survived = [u for u in want
                if e1.outcomes.get(u) == "finished"]
    mismatch = sum(
        not np.array_equal(e1.finished[u], want[u]) for u in survived)
    if mismatch:
        raise AssertionError(f"{mismatch} survivors token-mismatched "
                             "under injected faults")
    n_tok1 = sum(len(v) for v in e1.finished.values())
    results["chaos_injected_k8"] = {
        "tok_per_s": n_tok1 / dt, "p50_s": 0.0, "p99_s": 0.0,
        "survival_rate": len(survived) / len(reqs),
        "faults_injected": e1.n_faults_injected,
        "quarantined": e1.n_quarantined, "token_mismatches": 0, "k": k,
    }

    # crash + journal restart: recovery latency
    jpath = f"{tmp}/crash.jsonl"
    j2 = RequestJournal(jpath)
    e2 = ContinuousBatchingEngine(
        cfg, params, capacity=capacity, max_len=max_len, k=k, journal=j2,
        faults=FaultPlan.parse("crash@3"))
    try:
        e2.run(fresh(), realtime=True, pipeline=True)
        raise AssertionError("crash fault never fired")
    except EngineKilled:
        j2.close()
    t0 = time.monotonic()
    resumed, done = recovery_requests(read_journal(jpath))
    j3 = RequestJournal(jpath)
    e3 = ContinuousBatchingEngine(cfg, params, capacity=capacity,
                                  max_len=max_len, k=k, journal=j3)
    for r in resumed:
        e3.submit(r)
    while not e3.finished and (e3.waiting or e3.active or e3._inflight):
        e3.step()  # drive until the FIRST resumed request completes
    recovery_latency = time.monotonic() - t0
    e3.run([])  # drain the rest
    j3.close()
    out = {**done, **e3.finished}
    mismatch = sum(not np.array_equal(out[u], want[u]) for u in want
                   if u in out)
    if mismatch:
        raise AssertionError(f"{mismatch} resumed requests "
                             "token-mismatched vs uninterrupted run")
    results["chaos_crash_k8"] = {
        "tok_per_s": 0.0, "p50_s": 0.0, "p99_s": 0.0,
        "recovery_latency_s": recovery_latency,
        "resumed_requests": len(resumed),
        "recovered_done": len(done),
        "survival_rate": len(out) / len(reqs),
        "token_mismatches": 0, "k": k,
    }
    for m in results.values():
        m["family"] = cfg.family
        m["cache_layout"] = layout
    return results


def _bench_mesh(quick: bool):
    """Sharded-vs-single-device serving on one deterministic trace.

    Runs in this process over the visible devices, which must number 4:
    the same gpt-micro trace through the single-device engine, a 2x2
    (data x model) dense engine, and a 2x2 paged engine.  The sweep
    ASSERTS the acceptance criteria before reporting — sharded tokens
    must equal single-device tokens exactly, and sharded
    ``host_syncs_per_token`` must not exceed single-device on the same
    trace (the readback-locality contract: sharding adds collectives on
    device, never host syncs) — so a regression fails the bench rather
    than drifting into the trajectory.  Entries record
    ``mesh_shape``/``n_devices``.  On a CPU host, start the process with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``; forced host
    devices measure dispatch structure, not multi-chip speed.
    """
    if len(jax.devices()) != 4:
        raise RuntimeError(
            f"the mesh sweep needs 4 visible devices, found "
            f"{len(jax.devices())} (on a CPU host start the process with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    n = 8 if quick else 24
    gen = 8 if quick else 16
    cfg = get_config("gpt-micro")
    params = get_family(cfg).init(jax.random.PRNGKey(0), cfg)
    reqs = poisson_trace(cfg, n, rate_hz=2000.0, max_gen=gen)

    def fresh():
        return [Request(uid=r.uid, prompt=r.prompt,
                        max_new_tokens=r.max_new_tokens,
                        arrival=r.arrival) for r in reqs]

    def bench(mesh, pool):
        def build():
            return ContinuousBatchingEngine(
                cfg, params, capacity=4, max_len=48, k=8, pool=pool,
                mesh=mesh)
        build().run(fresh())          # warm the shared jit caches
        eng = build()
        t0 = time.monotonic()
        out = eng.run(fresh())        # realtime=False: deterministic
        dt = time.monotonic() - t0
        n_tok = sum(len(v) for v in out.values())
        m = {
            "tok_per_s": n_tok / dt, "p50_s": 0.0, "p99_s": 0.0,
            "host_syncs_per_token": eng.n_host_syncs / max(n_tok, 1),
            "decode_dispatches": eng.n_decode_dispatches,
            "prefill_batches": eng.n_prefills, "k": 8,
            "pool": eng.pool_kind, "mesh_shape": eng.mesh_shape,
            "n_devices": eng.n_devices, "family": cfg.family,
            "cache_layout": slot_cache_layout(cfg),
            "params_bytes_per_device": eng.params_bytes_per_device,
            "pool_bytes_per_device": eng.pool_bytes_per_device,
        }
        if eng.pool_kind == "paged":
            m["pages_highwater"] = eng.pages_highwater
            m["prefix_hit_rate"] = eng.prefix_hit_rate
            m["pages_per_request"] = (eng.n_pages_allocated
                                      / max(len(reqs), 1))
            m["dense_reservation_pages"] = eng._metas[0].nblk
            m["rejected"] = len(eng.rejected)
        return out, m

    results = {}
    want, results["mesh_1x1_dense_k8"] = bench(None, "dense")
    for tag, pool in (("mesh_2x2_dense_k8", "dense"),
                      ("mesh_2x2_paged_k8", "paged")):
        got, results[tag] = bench("2x2", pool)
        for u in want:
            assert np.array_equal(got[u], want[u]), \
                (tag, u, got[u], want[u])
        single = results["mesh_1x1_dense_k8"]["host_syncs_per_token"]
        shard = results[tag]["host_syncs_per_token"]
        assert shard <= single + 1e-9, (tag, shard, single)
    return results


def run(quick: bool = False, write_json: bool = True, families=None,
        speculate: bool = False, kernel=None, pool: bool = False,
        chaos: bool = False, mesh: bool = False):
    """``kernel``: ``None`` skips the kernel-vs-jnp section, else the
    Pallas mode it runs (``"auto"`` or ``"interpret"``)."""
    families = tuple(FAMILY_ARCHS) if families is None else tuple(families)
    results = {}
    partial = set(families) != set(FAMILY_ARCHS) or speculate or kernel \
        or pool or chaos or mesh
    if write_json and partial:
        # a partial run (--family subset, --speculate) must MERGE into
        # BENCH_serve_engine.json, never erase the other sections'
        # trajectory entries
        import json
        import pathlib
        path = pathlib.Path(__file__).resolve().parent.parent / \
            "BENCH_serve_engine.json"
        if path.exists():
            results.update(json.loads(path.read_text()).get("metrics", {}))
    for family in families:
        results.update(_bench_family(family, quick))
    if speculate:
        results.update(_bench_speculative(quick))
    if kernel:
        # the kernel section always reflects THIS sweep: purge merged-in
        # kernel_* keys first, or a CPU (interpret) and a TPU (auto) run
        # would accumulate stale side-by-side entries per layout
        for key in [k for k in results if k.startswith("kernel_")]:
            del results[key]
        results.update(_bench_kernel_modes(quick, kernel))
    if pool:
        # like the kernel section: the dense-vs-paged pairs always
        # reflect THIS sweep — purge merged-in pool_* keys first
        for key in [k for k in results if k.startswith("pool_")]:
            del results[key]
        results.update(_bench_pool_modes(quick))
    if chaos:
        for key in [k for k in results if k.startswith("chaos_")]:
            del results[key]
        results.update(_bench_chaos(quick))
    if mesh:
        for key in [k for k in results if k.startswith("mesh_")]:
            del results[key]
        results.update(_bench_mesh(quick))
    for m in results.values():
        # uniform schema across the whole trajectory: every entry says
        # what mesh it ran on (pre-sharding entries backfill as 1x1)
        m.setdefault("mesh_shape", "1x1")
        m.setdefault("n_devices", 1)

    for name, m in results.items():
        print(f"serve_{name},tok_per_s,{m['tok_per_s']:.1f}")
        print(f"serve_{name},p50_s,{m['p50_s']:.3f}")
        print(f"serve_{name},p99_s,{m['p99_s']:.3f}")
        if "host_syncs_per_token" in m:
            print(f"serve_{name},host_syncs_per_token,"
                  f"{m['host_syncs_per_token']:.3f}")
        if "acceptance_rate" in m:
            print(f"serve_{name},acceptance_rate,{m['acceptance_rate']:.3f}")
        if "survival_rate" in m:
            print(f"serve_{name},survival_rate,{m['survival_rate']:.3f}")
        if "recovery_latency_s" in m:
            print(f"serve_{name},recovery_latency_s,"
                  f"{m['recovery_latency_s']:.3f}")
        if m.get("pool") == "paged":
            print(f"serve_{name},pages_highwater,{m['pages_highwater']}")
            print(f"serve_{name},prefix_hit_rate,"
                  f"{m['prefix_hit_rate']:.3f}")
            print(f"serve_{name},pages_per_request,"
                  f"{m['pages_per_request']:.2f}")
    if write_json:
        path = write_bench_json("serve_engine", results)
        print(f"# wrote {path}")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--no-json", action="store_true")
    ap.add_argument("--family", default="all",
                    choices=["all", "none"] + sorted(FAMILY_ARCHS),
                    help="restrict the sweep to one model family "
                         "('none': only the --speculate section)")
    ap.add_argument("--speculate", action="store_true",
                    help="also bench speculative decode on the grown "
                         "gpt-micro pair (acceptance_rate recorded)")
    ap.add_argument("--kernel", default=None, choices=("auto", "interpret"),
                    help="also bench kernel-vs-jnp slot decode side by "
                         "side in this Pallas mode ('auto' compiles and "
                         "needs a TPU; 'interpret' — small trace)")
    ap.add_argument("--pool", action="store_true",
                    help="also bench dense-vs-paged slot pool pairs per "
                         "family (transformer/griffin/xlstm) plus ring "
                         "tail-restore and sampled-replay prefix traces "
                         "(pages high-water, prefix hit rate recorded)")
    ap.add_argument("--chaos", action="store_true",
                    help="also bench fault tolerance: journaling "
                         "overhead, survival under a seeded fault plan "
                         "(survivors token-checked), and crash+journal "
                         "recovery latency")
    ap.add_argument("--mesh", action="store_true",
                    help="also bench sharded serving on 4 visible devices "
                         "(2x2 dense + paged vs single-device; "
                         "token-exactness and host-sync parity asserted; "
                         "on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=4)")
    a = ap.parse_args()
    fams = {"all": tuple(FAMILY_ARCHS), "none": ()}.get(
        a.family, (a.family,))
    run(quick=a.quick, write_json=not a.no_json, families=fams,
        speculate=a.speculate, kernel=a.kernel, pool=a.pool,
        chaos=a.chaos, mesh=a.mesh)
