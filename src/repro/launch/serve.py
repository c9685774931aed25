"""Serving launcher: naive lock-step batch or continuous batching.

Drives the same ``prefill``/``decode_step`` functions the dry-run lowers at
production scale.  Usable as a library (examples) or CLI:

  # naive fixed-batch loop
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b-smoke \
      --batch 4 --prompt-len 32 --gen 16

  # continuous batching over a slot pool (any family implementing the
  # slot-decode protocol: transformer, griffin, xlstm)
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b-smoke \
      --engine continuous --batch 8 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b-smoke \
      --engine continuous --batch 4 --gen 8

  # serve a model grown from a pretrained source (the paper's operator,
  # end-to-end at serve time)
  PYTHONPATH=src python -m repro.launch.serve --arch gpt-micro-big \
      --engine continuous --grow gpt-micro --grow-method mango

  # speculative serving: the pretrained SOURCE drafts for its grown
  # target (with --grow the source checkpoint is reused as the draft;
  # --draft picks any other servable config with the same vocab)
  PYTHONPATH=src python -m repro.launch.serve --arch gpt-micro-big \
      --engine continuous --grow gpt-micro --speculate --spec-d 4

  # non-greedy decode in the macro loop (also valid with --speculate:
  # draft proposals then go through rejection sampling)
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b-smoke \
      --engine continuous --temperature 0.8 --top-k 40 --top-p 0.95

  # profile the serve run: the engine's serve.* host spans and its
  # programs' device ops, by scope (docs/serving.md, "Tracing")
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b-smoke \
      --engine continuous --profile /tmp/serve-profile
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, list_configs
from repro.data.synthetic import lm_batch
from repro.models import get_family, serve_supported
from repro.serve import (
    ContinuousBatchingEngine,
    Request,
    SamplingParams,
    SpeculativeConfig,
    spec_pair_supported,
)
from repro.serve.engine import POLICIES
from repro.train.steps import make_decode_step, make_prefill_step
from repro.utils.compile_cache import enable_compile_cache


@functools.lru_cache(maxsize=None)
def _jitted_steps(cfg):
    """One jitted prefill/decode pair per config — ``cfg`` is a frozen
    dataclass, so repeated ``generate`` calls (and the test suite's many
    per-request baselines) reuse the compile cache instead of re-tracing
    fresh closures every call."""
    return (jax.jit(make_prefill_step(cfg)), jax.jit(make_decode_step(cfg)))


def generate(cfg, params, prompt_tokens, *, max_new_tokens=16,
             max_len=None, eos_id=None):
    """prompt_tokens: (B, P) int32 -> (B, <=max_new_tokens) greedy tokens.

    ``eos_id`` enables per-row early stopping: a row that emits eos is
    frozen (later entries clamp to eos) and the loop exits as soon as
    EVERY row has fired — the returned array is then shorter than
    ``max_new_tokens``.  Without eos the loop always decodes the full
    budget and stays fully lazy (no per-step host sync).
    """
    fam = get_family(cfg)
    B, P = prompt_tokens.shape
    max_len = max_len or (P + max_new_tokens)
    cache = fam.init_cache(cfg, B, max_len)
    prefill, decode = _jitted_steps(cfg)

    logits, cache = prefill(params, {"tokens": prompt_tokens}, cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    done = None if eos_id is None else (tok == eos_id)
    for t in range(max_new_tokens - 1):
        if done is not None and bool(done.all()):
            break
        tok, cache = decode(params, tok, jnp.int32(P + t), cache)
        if done is not None:
            tok = jnp.where(done, eos_id, tok)  # freeze finished rows
            done = done | (tok == eos_id)
        out.append(tok)
    return jnp.stack(out, axis=1)


def build_params(cfg, *, grow_from=None, grow_method="mango", grow_rank=1,
                 grow_steps=0, seed=0, log_fn=print, return_source=False):
    """Init params — directly, or grown from a source architecture via the
    paper's multi-linear operator (``core/grow.py``).

    ``return_source=True`` returns ``(params, cfg_src, params_src)`` —
    the pretrained source checkpoint the target was grown from, which is
    exactly the draft model speculative serving wants (``cfg_src`` /
    ``params_src`` are ``None`` without ``grow_from``).
    """
    fam = get_family(cfg)
    rng = jax.random.PRNGKey(seed)
    if not grow_from:
        params = fam.init(rng, cfg)
        return (params, None, None) if return_source else params

    from repro.core import grow as growlib
    from repro.data.synthetic import lm_data_iter

    cfg_src = get_config(grow_from)
    params_src = get_family(cfg_src).init(rng, cfg_src)
    params = growlib.grow_from_source(
        cfg_src, cfg, method=grow_method, rank=grow_rank,
        steps=grow_steps, params_src=params_src,
        data_iter=lm_data_iter(cfg.vocab_size, 4, 32, seed=seed + 1),
        rng=rng, log_fn=log_fn)
    return (params, cfg_src, params_src) if return_source else params


def require_servable(cfg):
    """Gate ``--engine continuous`` behind the slot-decode capability probe
    with an actionable message: WHY this config is out, and WHAT is in."""
    ok, why = serve_supported(cfg)
    if ok:
        return
    def probe(name):
        try:
            return serve_supported(get_config(name))[0]
        except Exception:
            return False

    servable = [n for n in list_configs() if probe(n)]
    raise SystemExit(
        f"error: --engine continuous cannot serve {cfg.name!r}: {why}\n"
        "The slot-decode protocol serves causal decoder configs of every "
        "family in the zoo:\n"
        "  transformer — full KV, MLA latent, and ring-buffer window "
        "caches;\n"
        "  griffin     — rglru/conv recurrent state + local-attention "
        "rings;\n"
        "  xlstm       — mLSTM/sLSTM recurrent state.\n"
        f"Servable registered configs: {', '.join(servable)}\n"
        "(--engine naive runs any decoder config lock-step.)")


def require_spec_servable(cfg_tgt, cfg_draft, d, max_len):
    """Gate ``--speculate`` behind the PAIR probe.

    Speculative serving needs BOTH models servable through the
    chunk-verify slot protocol (plus a shared vocabulary and a verify
    chunk that fits every ring) — probing only the target would accept
    pairs that fail at the first draft step.  The probe detail reports
    per-mode servability for each model, so the error names the failing
    side."""
    ok, why = spec_pair_supported(cfg_tgt, cfg_draft, d, max_len)
    if ok:
        print(f"[serve] speculative pair: {why}")
        return
    raise SystemExit(
        f"error: --speculate cannot serve this draft/target pair: {why}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--engine", default="naive",
                    choices=["naive", "continuous"])
    ap.add_argument("--batch", type=int, default=4,
                    help="naive: batch size; continuous: request count")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=4,
                    help="continuous: decode slot-pool size")
    ap.add_argument("--max-len", type=int, default=0,
                    help="continuous: per-slot cache length (0 = auto)")
    ap.add_argument("--k", type=int, default=8,
                    help="continuous: macro-step length (decode tokens — or "
                         "speculative blocks — per on-device dispatch; host "
                         "syncs once per dispatch)")
    ap.add_argument("--policy", default="fifo", choices=list(POLICIES),
                    help="admission policy: fifo, or spf (length-bucketed "
                         "shortest-prefill-first — less pad waste)")
    ap.add_argument("--grow", default=None, metavar="SRC_ARCH",
                    help="grow params from this source arch before serving")
    ap.add_argument("--grow-method", default="mango",
                    choices=["mango", "ligo", "bert2bert", "stackbert",
                             "net2net"])
    ap.add_argument("--grow-rank", type=int, default=1)
    ap.add_argument("--grow-steps", type=int, default=0)
    ap.add_argument("--grow-cfg", default=None, metavar="TGT_ARCH",
                    help="continuous: LIVE upgrade — Mango-grow --arch "
                         "into this target while serving, then hot-swap "
                         "the grown weights into the running engine with "
                         "zero dropped requests (mid-flight sequences "
                         "continue token-exactly; the old source becomes "
                         "the speculative draft when the pair probe "
                         "passes).  Growth method/rank/steps follow "
                         "--grow-method/--grow-rank/--grow-steps")
    ap.add_argument("--upgrade-at", type=int, default=0,
                    help="with --grow-cfg: minimum decode dispatches "
                         "before the hot-swap may land (0 = first block "
                         "boundary after growth is ready)")
    ap.add_argument("--upgrade-sync", action="store_true",
                    help="with --grow-cfg: grow BEFORE serving starts "
                         "instead of on a background thread — the swap "
                         "then lands deterministically at --upgrade-at "
                         "(CI smoke / reproducible traces)")
    ap.add_argument("--speculate", action="store_true",
                    help="speculative decode: a draft model proposes, the "
                         "target verifies (needs --draft, or --grow whose "
                         "source checkpoint then drafts)")
    ap.add_argument("--draft", default=None, metavar="DRAFT_ARCH",
                    help="draft config for --speculate (default: the --grow "
                         "source)")
    ap.add_argument("--spec-d", type=int, default=4,
                    help="speculation depth: draft proposals per block")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy; implied 1.0 "
                         "when only --top-k/--top-p are set)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--sample-seed", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a sequence early when it emits this token")
    ap.add_argument("--kernel", default="jnp",
                    choices=["jnp", "auto", "interpret", "reference"],
                    help="slot-decode attention backend: jnp (pure-jnp "
                         "model path), auto (compiled Pallas kernels; "
                         "needs a TPU and fails elsewhere), interpret "
                         "(Pallas interpreter, any backend), reference "
                         "(kernels/ref.py oracles)")
    ap.add_argument("--pool", default="dense", choices=["dense", "paged"],
                    help="continuous: slot-pool layout — dense (one full "
                         "max_len row per slot) or paged (block tables "
                         "over a shared page arena + copy-on-write prefix "
                         "cache; families without a pageable KV group "
                         "fall back to dense)")
    ap.add_argument("--pages", type=int, default=0,
                    help="paged: page-arena depth (0 = capacity * blocks "
                         "per slot, i.e. the dense pool's footprint)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="continuous: serve over a (data=replica, "
                         "model=TP) device mesh — weights and slot pools "
                         "shard, the engine protocol is unchanged.  "
                         "Default: auto-chosen from the visible device "
                         "count (1 device serves unsharded).  Validate "
                         "on CPU with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="continuous: per-request TTL in seconds — the "
                         "watchdog evicts a request this long after its "
                         "arrival with outcome 'expired' (0 = off)")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="continuous: append-only crash-safe request "
                         "journal (JSONL); committed tokens flush at "
                         "block-readback granularity")
    ap.add_argument("--resume", action="store_true",
                    help="replay --journal before serving: mid-flight "
                         "requests re-admit token-exactly (prompt ‖ "
                         "committed), finished ones are not re-run")
    ap.add_argument("--snapshot", default=None, metavar="DIR",
                    help="continuous: write an engine snapshot (weights + "
                         "geometry, checkpoint format) before serving — "
                         "restore_engine() rebuilds the engine from it")
    ap.add_argument("--faults", default=None, metavar="PLAN",
                    help="continuous: deterministic fault injection — "
                         "'kind@step[:arg],...' with kinds "
                         "nan/oom/slow/hang/malformed/crash, or "
                         "'seed:S[:N]' for a seeded random plan "
                         "(chaos testing; see serve/faults.py)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the serve run "
                         "(and of a live upgrade) into DIR")
    args = ap.parse_args()
    enable_compile_cache()
    profile = (jax.profiler.trace(args.profile) if args.profile
               else contextlib.nullcontext())

    cfg = get_config(args.arch).replace(decode_kernel=args.kernel)
    if args.engine == "continuous":
        # probe BEFORE param init/growth — rejection must not cost a grow
        require_servable(cfg)
    sampling = None
    if args.temperature > 0 or args.top_k > 0 or args.top_p < 1.0:
        # honor ANY non-default sampling flag: --top-k/--top-p alone used
        # to be silently greedy (SamplingParams was only built for
        # --temperature > 0, and temperature 0 means greedy)
        temperature = args.temperature if args.temperature > 0 else 1.0
        if args.temperature <= 0:
            print("[serve] --top-k/--top-p without --temperature: "
                  "sampling at temperature 1.0")
        sampling = SamplingParams(temperature=temperature,
                                  top_k=args.top_k, top_p=args.top_p,
                                  seed=args.sample_seed)
    if args.engine == "naive" and (sampling is not None
                                   or args.policy != "fifo"):
        # silently greedy-decoding while the user asked for sampling
        # would misrepresent the output
        raise SystemExit("error: --temperature/--top-k/--top-p/--policy "
                         "require --engine continuous (the naive loop is "
                         "greedy lock-step)")
    if args.engine == "naive" and args.kernel != "jnp":
        # same silently-ignored-flag class: the naive loop never touches
        # the slot-decode protocol, so a kernel mode would not run
        raise SystemExit("error: --kernel requires --engine continuous "
                         "(the Pallas kernels back the slot-decode path)")
    if args.engine == "naive" and (args.pool != "dense" or args.pages):
        raise SystemExit("error: --pool/--pages require --engine "
                         "continuous (the naive loop has no slot pool)")
    if args.engine == "naive" and args.mesh:
        raise SystemExit("error: --mesh requires --engine continuous "
                         "(only the slot-pool engine shards across "
                         "devices)")
    if args.engine == "naive" and (args.deadline or args.journal
                                   or args.resume or args.faults
                                   or args.snapshot):
        raise SystemExit("error: --deadline/--journal/--resume/--snapshot/"
                         "--faults require --engine continuous (the fault "
                         "tolerance layer lives in the slot-pool engine)")
    if args.resume and not args.journal:
        raise SystemExit("error: --resume needs --journal PATH (the "
                         "journal IS the recovery record)")
    if args.grow_cfg and args.engine != "continuous":
        raise SystemExit("error: --grow-cfg requires --engine continuous "
                         "(a live upgrade hot-swaps the slot-pool "
                         "engine)")
    if (args.upgrade_at or args.upgrade_sync) and not args.grow_cfg:
        raise SystemExit("error: --upgrade-at/--upgrade-sync need "
                         "--grow-cfg TGT_ARCH (they schedule the live "
                         "upgrade)")
    speculative = None
    max_len = args.max_len or (args.prompt_len + args.gen)
    if args.speculate:
        if args.engine != "continuous":
            raise SystemExit("error: --speculate requires --engine "
                             "continuous")
        draft_name = args.draft or args.grow
        if draft_name is None:
            raise SystemExit("error: --speculate needs a draft model — "
                             "pass --draft ARCH, or --grow SRC (the "
                             "pretrained source then drafts for its grown "
                             "target)")
        # probe the PAIR before any param init/growth
        require_spec_servable(cfg, get_config(draft_name), args.spec_d,
                              max_len)
    params, cfg_src, params_src = build_params(
        cfg, grow_from=args.grow, grow_method=args.grow_method,
        grow_rank=args.grow_rank, grow_steps=args.grow_steps,
        return_source=True)
    if args.speculate:
        if args.draft and (cfg_src is None or args.draft != cfg_src.name):
            cfg_d = get_config(args.draft)
            params_d = get_family(cfg_d).init(jax.random.PRNGKey(0), cfg_d)
        else:
            # the paper's pair: the pretrained source checkpoint the
            # target was grown from doubles as the draft
            cfg_d, params_d = cfg_src, params_src
        speculative = SpeculativeConfig(cfg_d, params_d, d=args.spec_d)

    if args.engine == "naive":
        prompts = jnp.asarray(lm_batch(cfg.vocab_size, args.batch,
                                       args.prompt_len))
        t0 = time.time()
        with profile:
            toks = generate(cfg, params, prompts, max_new_tokens=args.gen,
                            eos_id=args.eos_id)
            toks.block_until_ready()
        dt = time.time() - t0
        toks_np = np.asarray(toks)
        if args.eos_id is None:
            n_tok = toks_np.size
        else:
            # count up to each row's first eos — the frozen filler past
            # it was never really decoded
            fired = toks_np == args.eos_id
            n_tok = sum(int(np.argmax(r)) + 1 if r.any() else len(r)
                        for r in fired)
        print(f"[naive] generated {n_tok} tokens "
              f"({args.batch}x<={toks_np.shape[1]}) in "
              f"{dt:.2f}s ({n_tok / dt:.1f} tok/s)")
        print(toks_np[:2])
        return

    from repro.serve import (
        EngineKilled,
        FaultPlan,
        RequestJournal,
        read_journal,
        recovery_requests,
        snapshot_engine,
    )

    recovered = {}
    resumed = []
    if args.resume:
        st = read_journal(args.journal)
        resumed, recovered = recovery_requests(st)
        print(f"[serve] --resume: journal replays {len(st.order)} "
              f"request(s) — {len(recovered)} already complete, "
              f"{len(resumed)} re-admitting mid-flight")
    journal = RequestJournal(args.journal) if args.journal else None
    faults = FaultPlan.parse(args.faults) if args.faults else None
    from repro.distributed import serve_sharding
    mesh_arg = None
    if args.mesh:
        try:
            mesh_arg = serve_sharding.validate_serve_mesh(
                args.mesh, cfg, args.capacity,
                n_devices=len(jax.devices()))
        except ValueError as e:
            # the clear-error contract: a layout that cannot shard this
            # engine dies HERE, naming the geometry, not as an XLA shape
            # crash three layers down
            raise SystemExit(f"error: {e}")
    elif len(jax.devices()) > 1:
        try:
            mesh_arg = serve_sharding.choose_serve_mesh_shape(
                len(jax.devices()), cfg, args.capacity)
        except ValueError as e:
            # serving on one of several visible devices would leave the
            # rest idle without saying so: name the layout problem instead
            raise SystemExit(f"error: {e}")
    engine = ContinuousBatchingEngine(cfg, params, capacity=args.capacity,
                                      max_len=max_len, k=args.k,
                                      policy=args.policy, pool=args.pool,
                                      pages=args.pages or None,
                                      sampling=sampling,
                                      speculative=speculative,
                                      deadline=args.deadline or None,
                                      journal=journal, faults=faults,
                                      mesh=mesh_arg)
    mb = 1024 * 1024
    print(f"[serve] mesh {engine.mesh_shape} "
          f"({engine.n_devices} device(s)) — per-device reservation: "
          f"params {engine.params_bytes_per_device / mb:.2f} MiB, "
          f"slot pools {engine.pool_bytes_per_device / mb:.2f} MiB")
    if engine.kernel_tp_fallback:
        print(f"[serve] --kernel {args.kernel}: the Pallas slot kernels "
              "read whole pool rows, so tensor-parallel serving falls "
              "back to the jnp path (token-exact either way)")
    if engine.pages_budget is not None:
        arena = ("ONE physical arena shared by target and draft "
                 "(per-engine refcount namespaces; pages trade freely)"
                 if engine.speculative is not None else "target arena")
        note = (f"--pages {args.pages}" if args.pages
                else "default: dense pool footprint")
        print(f"[serve] page budget: {engine.pages_budget} pages — "
              f"{arena} ({note})")
    if args.pool == "paged" and engine.pool_fallback_reason is not None:
        print(f"[serve] --pool paged fallback: "
              f"{engine.pool_fallback_reason} — affected pool(s) serve "
              "dense")
    if args.snapshot:
        path = snapshot_engine(engine, args.snapshot)
        print(f"[serve] engine snapshot -> {path}")
    with profile:
        upgrade_mgr = None
        if args.grow_cfg:
            from repro.serve.upgrade import UpgradeError, UpgradeManager
            try:
                upgrade_mgr = UpgradeManager(
                    engine, get_config(args.grow_cfg),
                    method=args.grow_method, rank=args.grow_rank,
                    grow_steps=args.grow_steps, spec_d=args.spec_d,
                    upgrade_at=args.upgrade_at, probe_fp=True)
            except UpgradeError as e:
                raise SystemExit(f"error: --grow-cfg: {e}")
            upgrade_mgr.start(background=not args.upgrade_sync)
            mode = ("pre-grown" if args.upgrade_sync
                    else "growing in background")
            print(f"[serve] live upgrade armed: {cfg.name} -> "
                  f"{upgrade_mgr.cfg_tgt.name} ({mode}, swap at dispatch "
                  f">= {args.upgrade_at})")
        rng = np.random.default_rng(0)
        reqs = list(resumed)
        known = {r.uid for r in resumed} | set(recovered)
        for uid in range(args.batch):
            if uid in known:
                continue  # --resume already owns this uid
            plen = int(rng.integers(max(1, args.prompt_len // 2),
                                    args.prompt_len + 1))
            prompt = lm_batch(cfg.vocab_size, 1, plen, seed=uid)[0]
            reqs.append(Request(uid=uid, prompt=prompt,
                                max_new_tokens=args.gen, eos_id=args.eos_id))
        t0 = time.time()
        try:
            out = engine.run(reqs)
        except EngineKilled as e:
            # the injected crash: the journal survived, the process "died" —
            # exit cleanly so the kill/restart smoke can resume us
            if journal is not None:
                journal.close()
            print(f"[serve] ENGINE KILLED ({e}) — journal at {args.journal} "
                  "holds the committed state; rerun with --resume")
            return
        dt = time.time() - t0
        if upgrade_mgr is not None:
            if upgrade_mgr.state in ("growing", "ready"):
                # the trace finished before the background growth was ready:
                # land the swap now so the NEXT trace serves the target
                upgrade_mgr.wait()
                upgrade_mgr.poll(engine)
                print("[serve] upgrade: growth outlived the trace — swap "
                      "landed at trace end")
            if upgrade_mgr.state == "swapped":
                spec_note = (f"draft={upgrade_mgr.cfg_src.name} "
                             f"d={upgrade_mgr.spec_d}"
                             if engine.speculative is not None else
                             f"off ({upgrade_mgr.spec_reason})")
                fp = upgrade_mgr.fp_token_agreement
                page_note = ""
                if upgrade_mgr.pages_resident_at_swap:
                    page_note = (
                        f", pages {upgrade_mgr.pages_carried} carried / "
                        f"{upgrade_mgr.pages_reprefilled} re-prefilled "
                        f"({upgrade_mgr.pages_resident_at_swap} resident at "
                        "swap)")
                print(f"[serve] upgrade SWAPPED: {upgrade_mgr.cfg_src.name} "
                      f"-> {upgrade_mgr.cfg_tgt.name} in "
                      f"{upgrade_mgr.grow_seconds:.1f}s growth, pause "
                      f"{upgrade_mgr.pause_ms:.0f} ms, "
                      f"{upgrade_mgr.resumed} mid-flight resumed, "
                      f"{engine.n_held_for_upgrade} held submits, "
                      f"{len(engine.rejected)} dropped{page_note}; greedy "
                      f"agreement {'n/a' if fp is None else f'{fp:.3f}'}; "
                      f"post-swap speculation {spec_note}")
            elif upgrade_mgr.state == "failed":
                print(f"[serve] upgrade FAILED (engine kept serving "
                      f"{cfg.name}): {upgrade_mgr.error}")
    out = {**recovered, **out}
    n_tok = sum(len(v) for v in out.values())
    mode = "speculative" if speculative is not None else "continuous"
    spec_note = "" if speculative is None else (
        f", draft={speculative.cfg.name} d={speculative.d} "
        f"acceptance={engine.acceptance_rate:.2f}")
    paged_note = "" if engine.pool_kind != "paged" else (
        f", {engine.pages_highwater} pages peak"
        f" ({next(m for m in engine._metas if m is not None).page}"
        " tok/page)"
        f", prefix hit rate {engine.prefix_hit_rate:.2f}")
    print(f"[{mode}] {cfg.family}/{engine.cache_layout} "
          f"({engine.pool_kind} pool) served "
          f"{len(reqs)} requests / {n_tok} tokens in "
          f"{dt:.2f}s ({n_tok / dt:.1f} tok/s, "
          f"{engine.n_decode_dispatches} macro-steps of K={args.k}, "
          f"{engine.n_prefills} prefill batches, "
          f"{engine.n_host_syncs / max(n_tok, 1):.2f} host syncs/token"
          f"{spec_note}{paged_note})")
    if engine.rejected:
        # rejections are recorded, not raised — surface them in the report
        print(f"[{mode}] rejected {len(engine.rejected)} request(s):")
        for uid, why in sorted(engine.rejected.items()):
            print(f"  uid {uid}: {why}")
    bad = {u: o for u, o in engine.outcomes.items() if o != "finished"}
    if bad or engine.n_faults_injected:
        print(f"[{mode}] fault report: {engine.n_faults_injected} "
              f"fault(s) injected, {engine.n_expired} expired, "
              f"{engine.n_quarantined} quarantined, {engine.n_shed} shed, "
              f"{engine.n_spec_fallbacks} spec fallback(s), "
              f"{engine.n_degraded_admissions} degraded admission(s)")
        for uid, o in sorted(bad.items()):
            print(f"  uid {uid}: {o}")
    if journal is not None:
        journal.close()
    for uid in sorted(out)[:2]:
        print(uid, out[uid])


if __name__ == "__main__":
    main()
