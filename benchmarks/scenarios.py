"""Scenario sweep harness: families x pool x kernel x trace-shape matrix,
merged into ``BENCH_serve_engine.json``.

Every cell runs in the calling process: a process that has touched JAX
holds the accelerator, so a child started from here could not reach it.
Each cell builds a cold engine of its exact shape; compiled functions are
shared between cells of the same configuration (``_jitted_engine_fns`` is
keyed on frozen configs), so only a cell's first run pays its compiles.
The sweep purges stale ``scenario_*`` / ``upgrade_*`` keys and MERGES
into the serve JSON so the other sections' trajectory entries survive.

Trace shapes:
  * ``bursty``      — short mixed requests arriving in two dense waves
                      (queueing + slot churn);
  * ``long_prompt`` — few requests whose prompts nearly fill ``max_len``
                      (prefill-bound, page-hungry);
  * ``eos_heavy``   — every request carries an eos it WILL emit mid-
                      budget (derived from a greedy dry run), so slots
                      retire early and admission backfills constantly.

``upgrade_*`` cells additionally arm a live :class:`UpgradeManager`
(growth pre-done so the swap lands deterministically at ``upgrade_at``
dispatches) and record the swap telemetry: ``upgrade_pause_ms``,
``dropped`` (ASSERTED zero — a swap that sheds load fails the bench),
resumed count, pre/post-swap tok/s, and the post-swap speculative
acceptance rate.

Run:  PYTHONPATH=src:. python benchmarks/scenarios.py [--quick]
          [--kernel auto|interpret]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import numpy as np

from benchmarks.common import write_bench_json
from repro.configs.base import get_config
from repro.data.synthetic import lm_batch
from repro.launch.serve import generate
from repro.models import get_family, slot_cache_layout
from repro.serve import ContinuousBatchingEngine, Request, UpgradeManager

# quick=True cells form the CI smoke subset; the rest only run in the
# full sweep.  The kernel cell runs the Pallas mode the sweep is given;
# under ``"interpret"`` (CPU hosts: correctness cost, not TPU speed) its
# trace is kept tiny.
SCENARIOS = (
    {"key": "scenario_gpt_dense_bursty", "arch": "gpt-micro",
     "pool": "dense", "trace": "bursty", "quick": True},
    {"key": "scenario_gpt_paged_long_prompt", "arch": "gpt-micro",
     "pool": "paged", "trace": "long_prompt", "quick": True},
    {"key": "scenario_gpt_dense_eos_heavy", "arch": "gpt-micro",
     "pool": "dense", "trace": "eos_heavy", "quick": True},
    {"key": "scenario_gpt_paged_bursty", "arch": "gpt-micro",
     "pool": "paged", "trace": "bursty", "quick": False},
    {"key": "scenario_griffin_dense_bursty", "arch": "griffin-micro",
     "pool": "dense", "trace": "bursty", "quick": True},
    {"key": "scenario_griffin_dense_eos_heavy", "arch": "griffin-micro",
     "pool": "dense", "trace": "eos_heavy", "quick": False},
    {"key": "scenario_gpt_kernel_bursty", "arch": "gpt-micro",
     "pool": "dense", "trace": "bursty", "kernel": "kernel",
     "quick": False},
    {"key": "upgrade_gpt_dense_midtrace", "arch": "gpt-micro",
     "grow": "gpt-micro-big", "pool": "dense", "trace": "bursty",
     "upgrade": True, "quick": True},
    {"key": "upgrade_gpt_paged_midtrace", "arch": "gpt-micro",
     "grow": "gpt-micro-big", "pool": "paged", "trace": "bursty",
     "upgrade": True, "quick": True},
    {"key": "upgrade_griffin_dense_midtrace", "arch": "griffin-micro",
     "grow": "griffin-micro-big", "pool": "dense", "trace": "bursty",
     "upgrade": True, "quick": False},
)

MAX_LEN = 40


def run_cell(spec, quick, kernel):
    """Serve one cell's trace on a fresh engine; returns {key: metrics}.
    ``kernel`` is the Pallas mode of the kernel cell: ``"auto"`` (compiled,
    TPU only) or ``"interpret"``."""
    cfg = get_config(spec["arch"])
    if spec.get("kernel") == "kernel":
        cfg = cfg.replace(decode_kernel=kernel)
    params = get_family(cfg).init(jax.random.PRNGKey(0), cfg)
    capacity, k = 3, 2
    interp = cfg.decode_kernel == "interpret"

    def _req(uid, plen, gen, arrival=0.0, eos=None):
        prompt = lm_batch(cfg.vocab_size, 1, plen, seed=400 + uid)[0]
        return Request(uid=uid, prompt=prompt, max_new_tokens=gen,
                       arrival=arrival, eos_id=eos)

    def make_trace(kind):
        rng = np.random.default_rng(7)
        if kind == "bursty":
            n = 4 if interp else (8 if quick else 12)
            g = 4 if interp else 10
            return [_req(u, int(rng.integers(4, 11)), g,
                         arrival=0.0 if u < n // 2 else 0.05)
                    for u in range(n)]
        if kind == "long_prompt":
            n = 3 if quick else 5
            gen = 6
            return [_req(u, MAX_LEN - gen - int(rng.integers(0, 4)), gen)
                    for u in range(n)]
        if kind == "eos_heavy":
            n = 6 if quick else 10
            reqs = [_req(u, int(rng.integers(4, 11)), 12) for u in range(n)]
            out = []
            for r in reqs:
                toks = np.asarray(generate(
                    cfg, params, np.asarray(r.prompt)[None],
                    max_new_tokens=r.max_new_tokens, max_len=MAX_LEN))[0]
                # the token it WILL greedily emit mid-budget becomes its eos
                out.append(Request(uid=r.uid, prompt=r.prompt,
                                   max_new_tokens=r.max_new_tokens,
                                   eos_id=int(toks[len(toks) // 2])))
            return out
        raise ValueError(kind)

    reqs = make_trace(spec["trace"])
    eng = ContinuousBatchingEngine(cfg, params, capacity=capacity,
                                   max_len=MAX_LEN, k=k, pool=spec["pool"],
                                   prefill_bucket=16)
    mgr = None
    if spec.get("upgrade"):
        mgr = UpgradeManager(eng, get_config(spec["grow"]), upgrade_at=4,
                             prewarm=not quick)
        mgr.start(background=False)  # growth pre-done: swap point is exact

    t0 = time.monotonic()
    out = eng.run(reqs)
    dt = time.monotonic() - t0
    n_tok = sum(len(v) for v in out.values())
    lat = sorted(s.t_done - t0 for s in eng.retired)
    p50 = lat[len(lat) // 2] if lat else 0.0
    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))] if lat else 0.0

    m = {
        "tok_per_s": n_tok / dt, "p50_s": p50, "p99_s": p99,
        "host_syncs_per_token": eng.n_host_syncs / max(n_tok, 1),
        "k": k, "trace": spec["trace"], "n_requests": len(reqs),
        "pool": eng.pool_kind, "decode_kernel": eng.decode_kernel,
        "family": cfg.family, "cache_layout": slot_cache_layout(eng.cfg),
        "mesh_shape": eng.mesh_shape, "n_devices": eng.n_devices,
    }
    if eng.pool_kind == "paged":
        m["pages_highwater"] = eng.pages_highwater
        m["prefix_hit_rate"] = eng.prefix_hit_rate
    if mgr is not None:
        assert mgr.state == "swapped", mgr.state
        dropped = len(eng.rejected)
        assert dropped == 0, eng.rejected  # zero-drop is the contract
        assert all(v == "finished" for v in eng.outcomes.values()), \
            eng.outcomes
        totals = eng.lifetime_totals()
        pre_tok = mgr.tokens_at_swap
        m.update({
            "upgrade_pause_ms": mgr.pause_ms,
            "grow_s": mgr.grow_seconds,
            "dropped": dropped,
            "resumed_requests": mgr.resumed,
            "held_submits": totals["n_held_for_upgrade"],
            "pre_swap_tok_per_s": pre_tok / max(mgr.t_swap - t0, 1e-9),
            "post_swap_tok_per_s": (totals["n_tokens"] - pre_tok)
                                   / max(t0 + dt - mgr.t_swap, 1e-9),
            "source": spec["arch"], "target": spec["grow"],
            # page-residency delta: pages live at quiesce (all invalidated
            # by the grown params), pages carried (structurally 0), and the
            # re-prefill page bill the resume wave pays for zero drops
            "pages_resident_at_swap": mgr.pages_resident_at_swap,
            "pages_carried": mgr.pages_carried,
            "pages_reprefilled": mgr.pages_reprefilled,
        })
        if eng.speculative is not None:
            m["acceptance_rate"] = eng.acceptance_rate
            m["draft"] = eng.speculative.cfg.name
        elif mgr.spec_reason:
            m["spec_disabled"] = mgr.spec_reason
    return {spec["key"]: m}


def run(quick: bool = False, write_json: bool = True,
        kernel: str = "auto"):
    cells = [s for s in SCENARIOS if s["quick"] or not quick]
    results = {}
    if write_json:
        # merge, never clobber: the scenario sweep owns only its own keys
        path = pathlib.Path(__file__).resolve().parent.parent / \
            "BENCH_serve_engine.json"
        if path.exists():
            results.update(json.loads(path.read_text()).get("metrics", {}))
        for key in [k for k in results
                    if k.startswith(("scenario_", "upgrade_"))]:
            del results[key]
    for spec in cells:
        results.update(run_cell(spec, quick, kernel))
    for name in (s["key"] for s in cells):
        m = results[name]
        print(f"serve_{name},tok_per_s,{m['tok_per_s']:.1f}")
        print(f"serve_{name},p50_s,{m['p50_s']:.3f}")
        print(f"serve_{name},p99_s,{m['p99_s']:.3f}")
        if "upgrade_pause_ms" in m:
            print(f"serve_{name},upgrade_pause_ms,"
                  f"{m['upgrade_pause_ms']:.1f}")
            print(f"serve_{name},dropped,{m['dropped']}")
            if m.get("pages_resident_at_swap"):
                print(f"serve_{name},pages_carried,{m['pages_carried']}")
                print(f"serve_{name},pages_reprefilled,"
                      f"{m['pages_reprefilled']}")
            print(f"serve_{name},pre_swap_tok_per_s,"
                  f"{m['pre_swap_tok_per_s']:.1f}")
            print(f"serve_{name},post_swap_tok_per_s,"
                  f"{m['post_swap_tok_per_s']:.1f}")
        if "acceptance_rate" in m:
            print(f"serve_{name},acceptance_rate,"
                  f"{m['acceptance_rate']:.3f}")
    if write_json:
        path = write_bench_json("serve_engine", results)
        print(f"# wrote {path}")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--no-json", action="store_true")
    ap.add_argument("--kernel", default="auto", choices=("auto", "interpret"),
                    help="Pallas mode of the kernel cell ('auto' compiles "
                         "and needs a TPU)")
    a = ap.parse_args()
    run(quick=a.quick, write_json=not a.no_json, kernel=a.kernel)
