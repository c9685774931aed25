"""Bring-up check on a TPU: serving and live growth at published widths.

Drives the serve engine's main path once, through the entry points a user
calls, with random weights made from a fixed seed:

  1. serve  — qwen1.5-0.5b at its published widths (24 x 1024, 16 heads,
              vocab 151936, bf16) on the paged pool, once with the jnp slot
              attention and once with the compiled Pallas kernels
              (``decode_kernel="auto"``: slot decode plus flash prefill).
              Every request must finish with in-vocabulary tokens, and one
              slot-decode step's logits must agree between the two
              attention paths on the same cache.
  2. growth — gpt-small (12 x 512) serves and is grown live into gpt-base
              (12 x 768) with Mango, swapped in at a fixed dispatch while
              sequences are mid-flight; the source then drafts for the
              target.  The swap must land with no request dropped, the
              resumed sequences must finish their budget, the draft must
              propose, and one verify step's logits over the post-swap
              cache must agree between the kernel and the jnp path.

``--chips 4`` runs only sharded serving instead: qwen1.5-0.5b on a 2x2
(data x model) mesh with the dense and then the paged pool, each against
the 1x1 engine on device 0 with the same requests.  One decode step's
logits must agree with the 1x1 engine's, over the 1x1 cache and over
the sharded engine's own cache.

Each phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU it exits non-zero and prints no result.  Everything runs in
this one process.  Compiled programs are cached in
``$JAX_COMPILATION_CACHE_DIR``, else in ``.jax_cache/`` of this checkout.

Run from the checkout root:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.data.synthetic import lm_batch  # noqa: E402
from repro.models import get_family  # noqa: E402
from repro.serve import (  # noqa: E402
    ContinuousBatchingEngine,
    Request,
    UpgradeManager,
)
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

# max |logits_a - logits_b| / max |logits_b| of two attention paths (or
# sharded vs 1x1) on one cache: bf16 activations rounded in a different
# order at each of 24 layers differ by a few bf16 ulps (8 bits) each.
LOGIT_RTOL = 0.05

# The backend compile event encloses the persistent-cache lookup, and a
# jit traced inside another jit's trace logs its own nested trace event.
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_PROGRAM_EVENT = _COMPILE_EVENTS[2]  # one per XLA program produced


class CompileClock:
    """Seconds JAX spends compiling inside a ``with`` block — tracing,
    lowering, and the backend compile or its persistent-cache retrieval —
    and the number of XLA programs produced, from JAX's monitoring
    events.  Events nest, so the seconds are the length of the union of
    their intervals, never more than the block's wall time."""

    def __enter__(self):
        self._spans = []
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._event)

    def _event(self, event, duration, **_):
        # the listener runs as the event ends
        if event in _COMPILE_EVENTS:
            end = time.perf_counter()
            self._spans.append((end - duration, end))
            self.programs += event == _PROGRAM_EVENT

    @property
    def seconds(self):
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self._spans):
            start = max(start, reach)
            if end > start:
                total += end - start
                reach = end
        return total


class SmokeFailure(AssertionError):
    """A check of the chip smoke test failed."""


def _check(ok, *what):
    if not ok:
        raise SmokeFailure(*what)


def _widths(cfg):
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "vocab_size": cfg.vocab_size,
            "dtype": cfg.param_dtype}


def _peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _requests(cfg, n, prompt_lens, gen, seed):
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    return [Request(uid=u, max_new_tokens=gen,
                    prompt=lm_batch(cfg.vocab_size, 1,
                                    int(rng.integers(lo, hi + 1)),
                                    seed=seed * 1000 + u)[0])
            for u in range(n)]


def _check_served(eng, out, reqs, vocab):
    """Every request finished, none rejected, every token in the vocab."""
    _check(not eng.rejected, eng.rejected)
    bad = {u: o for u, o in eng.outcomes.items() if o != "finished"}
    _check(not bad, bad)
    _check(sorted(out) == sorted(r.uid for r in reqs), sorted(out))
    for r in reqs:
        toks = out[r.uid]
        _check(len(toks) == r.max_new_tokens, r.uid, len(toks))
        _check(((toks >= 0) & (toks < vocab)).all(), r.uid, toks)


def _outcomes(eng):
    counts = {}
    for o in eng.outcomes.values():
        counts[o] = counts.get(o, 0) + 1
    return counts


def _serve_twice(cfg, params, reqs, eng_kw):
    """Serve ``reqs`` on a cold engine (compiles every shape it meets),
    then on a fresh engine over the same shapes: the second run is the
    timed one and should compile nothing.  Returns (tokens by uid,
    record, the timed engine)."""
    with CompileClock() as cold:
        t0 = time.perf_counter()
        eng = ContinuousBatchingEngine(cfg, params, **eng_kw)
        eng.run(reqs)
        cold_s = time.perf_counter() - t0
    with CompileClock() as warm:
        eng = ContinuousBatchingEngine(cfg, params, **eng_kw)
        t0 = time.perf_counter()
        out = eng.run(reqs)
        run_s = time.perf_counter() - t0
    _check_served(eng, out, reqs, cfg.vocab_size)
    n_tok = sum(len(v) for v in out.values())
    return out, {
        "decode_kernel": eng.decode_kernel, "pool": eng.pool_kind,
        "compile_s": cold.seconds, "cold_run_s": cold_s, "run_s": run_s,
        "programs_compiled": cold.programs,
        "programs_compiled_in_timed_run": warm.programs,
        "tokens": n_tok, "tok_per_s": n_tok / run_s,
        "outcomes": _outcomes(eng)}, eng


def _probe_inputs(eng):
    """(tokens, positions, done) of the engine's slots after a block:
    each active slot's last token at its next write position."""
    cap = eng.capacity
    tokens = np.zeros((cap,), np.int32)
    positions = np.zeros((cap,), np.int32)
    done = np.ones((cap,), bool)
    for slot, seq in eng.active.items():
        tokens[slot], positions[slot], done[slot] = \
            seq.tokens[-1], seq.pos, False
    return tokens, positions, done


def _step_logits(cfg, params, pool, probe, plan=None, chunk=None):
    """Logits (float32, host) of one slot-decode step over ``pool`` or,
    given a (B, d + 1) token ``chunk``, of one speculative verify of it
    (the pool is read-only in both)."""
    fam = get_family(cfg)

    def step(p, tokens, positions, cache, done):
        if chunk is None:
            return fam.decode_step_slots(p, tokens, positions, cache, cfg,
                                         done=done)[0]
        return fam.verify_step_slots(p, tokens, positions, cache, cfg,
                                     done=done)[0]

    fn = jax.jit(step)
    if plan is not None:
        fn = plan.wrap(fn)
    tokens = probe[0] if chunk is None else chunk
    return np.asarray(fn(params, tokens, probe[1], pool, probe[2]),
                      np.float32)


def _logit_gap(a, b, done):
    """max |a - b| over live rows, relative to max |b|."""
    live = ~done
    return float(np.abs(a[live] - b[live]).max()
                 / max(np.abs(b[live]).max(), 1e-30))


def _matched_gap(eng, logits, ref_eng, ref_logits):
    """``_logit_gap`` over the sequences two engines hold with the same
    committed tokens, matched by uid (the engines may place a request in
    different slots).  Returns (gap, rows compared)."""
    ref_slot = {seq.req.uid: (slot, seq) for slot, seq in
                ref_eng.active.items()}
    pairs = [(slot, ref_slot[seq.req.uid][0])
             for slot, seq in eng.active.items()
             if seq.req.uid in ref_slot
             and ref_slot[seq.req.uid][1].tokens == seq.tokens]
    if not pairs:
        return None, 0
    rows, ref_rows = map(list, zip(*pairs))
    a, b = logits[rows], ref_logits[ref_rows]
    return (float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)),
            len(pairs))


def _token_agreement(a, b):
    same = total = 0
    for u in a:
        n = min(len(a[u]), len(b[u]))
        same += int((a[u][:n] == b[u][:n]).sum())
        total += n
    return same / max(total, 1)


def serve_phase(arch, kernel, *, n_requests=16, prompt_lens=(64, 512),
                gen=32, capacity=8, max_len=1024, k=8, prefill_bucket=128,
                pool="paged", seed=0):
    """Serve one trace with the jnp slot attention and with ``kernel``,
    then compare one decode step's logits of the two paths on one
    cache.  Returns the phase record (also printed as a JSON line)."""
    cfg = get_config(arch)
    params = get_family(cfg).init(jax.random.PRNGKey(seed), cfg)
    reqs = _requests(cfg, n_requests, prompt_lens, gen, seed)
    eng_kw = dict(capacity=capacity, max_len=max_len, k=k, pool=pool,
                  prefill_bucket=prefill_bucket)
    outs, runs = {}, {}
    for mode in ("jnp", kernel):
        outs[mode], runs[mode], _ = _serve_twice(
            cfg.replace(decode_kernel=mode), params, reqs, eng_kw)

    # one block on the kernel engine, then one more decode step over its
    # cache through each attention path
    eng = ContinuousBatchingEngine(cfg.replace(decode_kernel=kernel),
                                   params, **eng_kw)
    for r in reqs:
        eng.submit(r)
    eng.step()
    probe = _probe_inputs(eng)
    ref = _step_logits(cfg.replace(decode_kernel="jnp"), params, eng.pool,
                       probe)
    got = _step_logits(cfg.replace(decode_kernel=kernel), params, eng.pool,
                       probe)
    gap = _logit_gap(got, ref, probe[2])
    rec = {"phase": "serve", "config": arch, "widths": _widths(cfg),
           "pool": pool, "capacity": capacity, "max_len": max_len, "k": k,
           "requests": n_requests, "prompt_lens": list(prompt_lens),
           "gen": gen, "runs": runs, "logit_gap": gap,
           "logit_rtol": LOGIT_RTOL,
           "token_agreement": _token_agreement(outs[kernel], outs["jnp"]),
           "peak_bytes_in_use": _peak_bytes()}
    print(json.dumps(rec), flush=True)
    _check(gap <= LOGIT_RTOL, gap, LOGIT_RTOL)
    return rec


def growth_phase(src, tgt, kernel, *, n_requests=16, prompt_lens=(16, 128),
                 gen=32, capacity=8, max_len=256, k=8, prefill_bucket=64,
                 grow_steps=3, upgrade_at=2, spec_d=4, seed=0):
    """Serve ``src`` and grow it live into ``tgt`` with Mango: growth runs
    before serving (synchronous) and the swap lands at dispatch
    ``upgrade_at``, mid-way through the first cohort, after which the
    source drafts for the target.  Right after the swap, one speculative
    verify step of the target over its post-swap cache is compared
    between ``kernel`` and the jnp path."""
    cfg = get_config(src).replace(decode_kernel=kernel)
    params = get_family(cfg).init(jax.random.PRNGKey(seed), cfg)
    reqs = _requests(cfg, n_requests, prompt_lens, gen, seed)
    with CompileClock() as to_swap:
        t0 = time.perf_counter()
        eng = ContinuousBatchingEngine(
            cfg, params, capacity=capacity, max_len=max_len, k=k,
            pool="paged", prefill_bucket=prefill_bucket)
        mgr = UpgradeManager(eng, get_config(tgt), method="mango",
                             grow_steps=grow_steps, spec_d=spec_d,
                             upgrade_at=upgrade_at, probe_fp=True,
                             seed=seed)
        mgr.start(background=False)
        for r in reqs:
            eng.submit(r)
        # synchronous steps until the swap has landed and the step that
        # made it has run one speculative block with live sequences left
        while (eng.waiting or eng.active) and not (
                mgr.state == "swapped" and eng.active):
            eng.step()
        wall = time.perf_counter() - t0
    _check(mgr.state == "swapped", mgr.state, mgr.error)
    _check(eng.speculative is not None, mgr.spec_reason)
    _check(mgr.resumed > 0, "no sequence was mid-flight at the swap")

    probe = _probe_inputs(eng)
    _check(not probe[2].all(), "no live slot after the swap")
    drafts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (eng.capacity, spec_d), dtype=np.int32)
    chunk = np.concatenate([probe[0][:, None], drafts], 1)
    ref = _step_logits(eng.cfg.replace(decode_kernel="jnp"), eng.params,
                       eng.pool, probe, chunk=chunk)
    got = _step_logits(eng.cfg, eng.params, eng.pool, probe, chunk=chunk)
    gap = _logit_gap(got, ref, probe[2])

    with CompileClock() as after_swap:
        t0 = time.perf_counter()
        eng.run()
        wall += time.perf_counter() - t0
    out = dict(eng.finished)
    dropped = len(eng.rejected)
    _check(dropped == 0, eng.rejected)
    _check_served(eng, out, reqs, cfg.vocab_size)
    resumed = [r.uid for r in mgr.resumed_requests]
    _check(all(len(out[u]) == gen for u in resumed), resumed)
    totals = eng.lifetime_totals()
    compile_s = to_swap.seconds + after_swap.seconds
    rec = {"phase": "growth", "source": src, "target": tgt,
           "widths": {"source": _widths(cfg),
                      "target": _widths(mgr.cfg_tgt)},
           "method": "mango", "grow_steps": grow_steps,
           "decode_kernel": eng.decode_kernel, "pool": eng.pool_kind,
           "capacity": capacity, "max_len": max_len, "k": k,
           "requests": n_requests, "upgrade_at": upgrade_at,
           "state": mgr.state, "dropped": dropped,
           "resumed": mgr.resumed, "grow_seconds": mgr.grow_seconds,
           "pause_ms": mgr.pause_ms,
           "fp_token_agreement": mgr.fp_token_agreement,
           "draft": eng.speculative.cfg.name,
           "spec_proposed": totals["n_spec_proposed"],
           "spec_accepted": totals["n_spec_accepted"],
           "acceptance_rate": eng.acceptance_rate,
           "verify_logit_gap": gap, "logit_rtol": LOGIT_RTOL,
           "compile_s": compile_s,
           "programs_compiled": to_swap.programs + after_swap.programs,
           "wall_s": wall, "run_s": wall - compile_s,
           "tokens": sum(len(v) for v in out.values()),
           "outcomes": _outcomes(eng), "peak_bytes_in_use": _peak_bytes()}
    print(json.dumps(rec), flush=True)
    _check(totals["n_spec_proposed"] > 0, "no draft token was proposed")
    _check(gap <= LOGIT_RTOL, gap, LOGIT_RTOL)
    return rec


def mesh_phase(arch, kernel, *, mesh="2x2", n_requests=16,
               prompt_lens=(64, 512), gen=32, capacity=8, max_len=1024,
               k=8, prefill_bucket=128, seed=0):
    """Sharded serving on ``mesh``, dense and paged pools, each against
    the 1x1 engine on device 0 with the same requests."""
    cfg = get_config(arch).replace(decode_kernel=kernel)
    params = get_family(cfg).init(jax.random.PRNGKey(seed), cfg)
    reqs = _requests(cfg, n_requests, prompt_lens, gen, seed)
    recs = []
    for pool in ("dense", "paged"):
        eng_kw = dict(capacity=capacity, max_len=max_len, k=k, pool=pool,
                      prefill_bucket=prefill_bucket)
        out_s, run_s, sharded = _serve_twice(cfg, params, reqs,
                                             dict(eng_kw, mesh=mesh))
        # the 1x1 reference runs the attention path the sharded engine
        # actually ran (slot kernels fall back to jnp under TP)
        ref_cfg = sharded.cfg
        out_1, run_1, _ = _serve_twice(ref_cfg, params, reqs, eng_kw)
        # one block on a fresh 1x1 and a fresh sharded engine, then one
        # decode step over the 1x1 cache through both, and over each
        # engine's own cache (the sharded prefill, admission and pool
        # layout), rows matched by uid
        single = ContinuousBatchingEngine(ref_cfg, params, **eng_kw)
        own = ContinuousBatchingEngine(cfg, params, mesh=mesh, **eng_kw)
        for e in (single, own):
            for r in reqs:
                e.submit(r)
            e.step()
        probe = _probe_inputs(single)
        ref = _step_logits(ref_cfg, single.params, single.pool, probe)
        got = _step_logits(own.cfg, own.params,
                           jax.device_get(single.pool), probe,
                           plan=own.mesh_plan)
        gap = _logit_gap(got, ref, probe[2])
        got_own = _step_logits(own.cfg, own.params, own.pool,
                               _probe_inputs(own), plan=own.mesh_plan)
        gap_own, rows = _matched_gap(own, got_own, single, ref)
        rec = {"phase": "mesh", "config": arch, "widths": _widths(cfg),
               "pool": pool, "mesh": sharded.mesh_shape,
               "n_devices": sharded.n_devices,
               "reference": f"1x1 on {jax.devices()[0]}",
               "decode_kernel_requested": kernel,
               "decode_kernel": sharded.decode_kernel,
               "kernel_tp_fallback": sharded.kernel_tp_fallback,
               "capacity": capacity, "max_len": max_len, "k": k,
               "requests": n_requests, "runs": {"sharded": run_s,
                                                "single": run_1},
               "logit_gap": gap, "logit_gap_own_cache": gap_own,
               "own_cache_rows": rows, "logit_rtol": LOGIT_RTOL,
               "token_agreement": _token_agreement(out_s, out_1),
               "peak_bytes_in_use": _peak_bytes()}
        print(json.dumps(rec), flush=True)
        _check(gap <= LOGIT_RTOL, pool, gap, LOGIT_RTOL)
        _check(rows > 0, pool, "no sequence agreed after one block")
        _check(gap_own <= LOGIT_RTOL, pool, gap_own, LOGIT_RTOL)
        recs.append(rec)
    return recs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only sharded serving (2x2 mesh vs 1x1)")
    args = ap.parse_args(argv)
    dev = jax.devices()
    if dev[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's devices are "
                 f"{dev[0].platform!r}")
    if len(dev) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX sees {len(dev)}")
    enable_compile_cache()  # before the first compile
    if args.chips == 4:
        mesh_phase("qwen1.5-0.5b", "auto")
    else:
        serve_phase("qwen1.5-0.5b", "auto")
        growth_phase("gpt-small", "gpt-base", "auto")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))


if __name__ == "__main__":
    main()
