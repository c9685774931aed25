"""Continuous-batching serve engine with on-device macro-step decode.

The naive loop in ``launch/serve.py`` runs one fixed batch lock-step:
every sequence prefills together, decodes together, and the batch ends
when the *longest* request finishes.  Under real traffic (mixed prompt
lengths, mixed generation lengths, asynchronous arrivals) that wastes
most decode FLOPs on finished or not-yet-admitted rows.

This engine serves a *stream* of requests through a fixed-capacity slot
pool instead:

  * ``Request``       — prompt + max_new_tokens (+ optional eos, arrival
                        time for trace replay);
  * slot cache pool   — one ``fam.init_cache(cfg, capacity, max_len)``
                        allocation; row ``i`` is an independent sequence
                        slot, initialized at admission, advanced per-step
                        at its own length, and zero-evicted at retirement;
  * batched admission — all newly-arrived requests sharing a prefill
                        bucket prefill in ONE multi-row call (group size
                        padded to a power of two to bound recompiles;
                        padding rows scatter to an out-of-range slot index
                        and are dropped) and scatter into their slots in
                        one donated update; the admission *policy* decides
                        who goes first when slots are scarce (FIFO, or
                        length-bucketed shortest-prefill-first);
  * macro-step loop   — ``make_slot_decode_loop(cfg, k)`` runs K decode
                        steps per dispatch entirely on device under a
                        ``lax.scan``: per-slot eos / max-new-token
                        stopping is applied INSIDE the scan (finished rows
                        freeze and become bit-exact no-ops with
                        ``kv_len == 0``), and the host reads back a
                        ``(K, capacity)`` token block — one host↔device
                        sync per K tokens instead of one per token;
  * speculative mode  — a ``SpeculativeConfig`` swaps the macro loop for
                        ``make_speculative_loop``: a small DRAFT model
                        (the paper's pretrained source / growth seed)
                        proposes ``d`` tokens per slot, the target
                        verifies them in one batched chunk forward, and
                        each block commits 1..d+1 tokens per slot — the
                        engine then runs TWO slot pools (target + draft)
                        through the same admission/eviction scatters, and
                        acceptance telemetry rides the block readback;
  * sampling          — a non-greedy ``SamplingParams`` threads per-slot
                        PRNG chains through admission and the decode
                        loops (temperature / top-k / top-p; speculative
                        mode uses draft-rejection sampling);
  * kernel backend    — ``cfg.decode_kernel`` swaps the slot attention
                        inside ``decode_step_slots``/``verify_step_slots``
                        between the jnp path and the Pallas kernel family
                        (token-exact either way; the draft cfg is aligned
                        to the target's switch automatically);
  * double buffering  — ``run()`` dispatches macro-block N+1 (pure
                        device-side dataflow, no sync) before blocking on
                        block N's tokens, so readback overlaps compute.

All decode state (tokens, positions, remaining budget, eos ids, sampling
chains, done mask) is persistent and device-resident; the host touches it
only through incremental scatters at admission/eviction — there is no
per-step O(capacity) host rebuild and no per-token ``np.asarray``.

Invariant (tested in ``tests/test_serve_engine.py``,
``tests/test_serve_families.py`` and ``tests/test_speculative.py``):
greedy tokens are *exactly* the sequential ``generate()`` tokens for
every request, for any interleaving, any K — and any speculation depth:
a speculative block only ever emits the target's own argmax tokens, so
acceptance changes speed, never output.

Fault tolerance (PR 7): per-request deadlines and queue-age load
shedding fold into the same done-mask/eviction machinery; per-slot
NaN/Inf sentinels computed INSIDE the decode scans ride the existing
block readback (zero extra host syncs) and quarantine-evict poisoned
slots; a speculative engine whose draft misbehaves drops to the plain
macro loop, and a faulted paged arena drops prefix sharing for
dense-style full reservation.  A :class:`repro.serve.recovery
.RequestJournal` (``journal=``) makes every committed token crash-safe,
and a :class:`repro.serve.faults.FaultPlan` (``faults=``) injects
deterministic failures for the chaos harness.  All of it defaults off:
the fault-free hot path dispatches exactly as before.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import get_family, serve_supported, slot_cache_layout
from repro.serve import faults as faults_lib
from repro.serve import paged as paged_lib
from repro.serve import sampling as sampling_lib
from repro.serve import tracing
from repro.serve.speculative import (
    SpeculativeConfig,
    make_draft_prefill,
    make_speculative_loop,
    spec_pair_supported,
)
from repro.train.steps import make_prefill_admit_step, make_slot_decode_loop

POLICIES = ("fifo", "spf")

# Telemetry that accumulates per drain window.  ``drain()`` folds these
# into ``engine.lifetime`` and zeroes them, so a long-lived server's
# windowed rates (acceptance, tok/s, hit rate) reflect the CURRENT window
# instead of everything since boot.
_WINDOW_COUNTERS = (
    "n_decode_dispatches", "n_prefills", "n_host_syncs", "n_tokens",
    "n_spec_proposed", "n_spec_accepted", "n_admitted",
    "n_prefix_hits", "n_prefix_misses", "n_prefix_stalls",
    "n_pages_allocated", "n_expired", "n_quarantined", "n_shed",
    "n_spec_fallbacks", "n_faults_injected", "n_degraded_admissions",
    "n_held_for_upgrade", "n_blocks_read", "n_moe_pairs",
    "n_moe_experts_hit",
)


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.lru_cache(maxsize=None)
def _jitted_engine_fns(cfg, k, sampling, spec_key, paged_key, mesh_plan):
    """Shared jitted (loop, prefill, draft_prefill, admit, evict,
    hit_admit) per (config, K, sampling, speculative pair, paging
    geometry, mesh plan): every engine instance over the same frozen
    configs reuses one compile cache.  Pool and state buffers are donated
    throughout — the engine always rebinds the returned handles, so every
    update is in place instead of a pool copy.

    ``mesh_plan`` (a :class:`repro.distributed.serve_sharding
    .ServeMeshPlan`, or None for the single-device engine) wraps every
    returned function so it traces under the plan's mesh + logical rules:
    the model-internal ``annotate`` calls then pin activations to the
    (data=slots, model=heads) layout, and the committed shardings of the
    params/pool/state arguments do the rest through GSPMD.

    ``pools`` is a TUPLE of slot pools — ``(target,)`` normally,
    ``(target, draft)`` in speculative mode — so admission and eviction
    scatter every model's pool in the same donated update.  ``paged_key``
    carries one :class:`repro.serve.paged.PoolMeta` (or None for a dense
    pool) per pool; the decode/prefill jits are pool-structure-opaque
    (``decode_step_slots`` dispatches on ``"bt" in cache`` internally),
    only admission and eviction scatter differently.

    ``admit`` and ``evict`` take slot-index vectors that may contain the
    out-of-range index ``capacity`` (padding rows); jnp scatters drop
    out-of-bounds updates, so padded rows are no-ops by construction.
    The same convention covers paged pools: unallocated / padding block
    table entries carry the out-of-range page id ``n_pages``.
    """
    sampled = not sampling_lib.is_greedy(sampling)
    fb_loop = None
    if spec_key is None:
        loop = jax.jit(make_slot_decode_loop(cfg, k, sampling),
                       donate_argnums=(1, 2, 3, 5, 6)
                       + ((7,) if sampled else ()))
        draft_prefill = None
    else:
        cfg_d, d = spec_key
        loop = jax.jit(make_speculative_loop(cfg, cfg_d, d, k, sampling),
                       donate_argnums=(2, 3, 4, 6, 7, 8, 9))
        draft_prefill = jax.jit(make_draft_prefill(cfg_d),
                                donate_argnums=(3,))
        # the degradation ladder's target: a plain (non-speculative)
        # macro loop over the TARGET pool alone, compiled lazily on
        # first use when the draft misbehaves mid-serve
        fb_loop = jax.jit(make_slot_decode_loop(cfg, k, sampling),
                          donate_argnums=(1, 2, 3, 5, 6)
                          + ((7,) if sampled else ()))
    prefill = jax.jit(make_prefill_admit_step(cfg, sampling),
                      donate_argnums=(3,))

    def _scatter_state(state, slots, first, plens, rem0, eos_new, keys_new):
        tokens, positions, remaining, eos, done, keys = state
        tokens = tokens.at[slots].set(first)
        positions = positions.at[slots].set(plens)
        remaining = remaining.at[slots].set(rem0)
        eos = eos.at[slots].set(eos_new)
        keys = keys.at[slots].set(keys_new)
        # a request can finish at its very first (prefill) token
        done = done.at[slots].set((first == eos_new) | (rem0 <= 0))
        return tokens, positions, remaining, eos, done, keys

    def admit_fn(pools, rows, state, slots, bt_rows, first, plens, rem0,
                 eos_new, keys_new):
        new_pools = []
        for pool, row, btr, m in zip(pools, rows, bt_rows, paged_key):
            if btr is None:
                new_pools.append(jax.tree.map(
                    lambda p, r: p.at[:, slots].set(r), pool, row))
            else:
                new_pools.append(paged_lib.admit_scatter(pool, row, slots,
                                                         btr, m))
        state = _scatter_state(state, slots, first, plens, rem0, eos_new,
                               keys_new)
        return tuple(new_pools), state

    def evict_fn(pools, state, slots, zero_pids):
        new_pools = []
        for pool, zp, m in zip(pools, zero_pids, paged_key):
            if zp is None:
                new_pools.append(jax.tree.map(
                    lambda p: p.at[:, slots].set(0), pool))
            else:
                new_pools.append(paged_lib.evict_clear(pool, slots, zp, m))
        tokens, positions, remaining, eos, done, keys = state
        tokens = tokens.at[slots].set(0)
        positions = positions.at[slots].set(0)
        remaining = remaining.at[slots].set(0)
        eos = eos.at[slots].set(-1)
        keys = keys.at[slots].set(0)
        done = done.at[slots].set(True)
        return tuple(new_pools), (tokens, positions, remaining, eos, done,
                                  keys)

    # rows (arg 1) is NOT donated: an (n, ...)-shaped buffer can never alias
    # the (capacity, ...) pool, so donating it only produces warnings
    admit = jax.jit(admit_fn, donate_argnums=(0, 2))
    evict = jax.jit(evict_fn, donate_argnums=(0, 1))

    # prefix-hit admission: the shared prompt pages are already resident,
    # so the new slot only runs its private TAIL tokens (at most one
    # page) through decode steps — no bucket prefill dispatch at all.
    # Built for every non-speculative paged-target engine: full-KV and
    # MLA hits alias resident pages directly; windowed (ring) hits first
    # RECONSTRUCT the ring by copying resident absolute-position pages
    # into the slot's private ring pages; sampled engines derive the
    # row's chain on device — (seed, uid) advanced by ``skips`` splits,
    # exactly mirroring ``prefill_sampled`` — and draw the first token
    # from the chain instead of argmax, so a hit-admitted request emits
    # the same tokens as its bucket-prefilled twin.
    hit_admit = None
    reg_copy = None
    if paged_key and paged_key[0] is not None and spec_key is None:
        meta0 = paged_key[0]
        fam = get_family(cfg)
        windowed = bool(getattr(cfg, "window", None))

        def hit_fn(params, pools, state, slots, bt_rows0, src_pids,
                   dst_pids, tail_tokens, tail_len, pos0, plens, rem0,
                   eos_new, uids, skips):
            pool = paged_lib.set_block_tables(pools[0], slots, bt_rows0,
                                              meta0)
            if windowed:
                pool = paged_lib.ring_restore_copy(pool, src_pids,
                                                   dst_pids, meta0)
            cap = state[0].shape[0]

            def scat(vals, fill, dtype):
                return jnp.full((cap,), fill, dtype).at[slots].set(
                    vals, mode="drop")

            wave = jnp.zeros((cap,), bool).at[slots].set(
                jnp.ones(slots.shape, bool), mode="drop")
            tl = scat(tail_len, 0, jnp.int32)
            p0 = scat(pos0, 0, jnp.int32)
            toks = jnp.zeros((cap, meta0.page), jnp.int32).at[slots].set(
                tail_tokens, mode="drop")
            if sampled:
                uc = scat(uids, 0, jnp.int32)
                sk = scat(skips, 0, jnp.int32)
                roots = jax.vmap(lambda u: sampling_lib.request_key(
                    sampling.seed, u))(uc)
                # a resume's committed run consumed one split per token
                roots = jax.lax.fori_loop(
                    0, jnp.max(sk),
                    lambda i, ks: jnp.where(
                        (i < sk)[:, None],
                        sampling_lib.next_keys(ks)[0], ks),
                    roots)
            else:
                roots = jnp.zeros((cap, 2), jnp.uint32)

            def body(carry, j):
                cache, first, chain = carry
                live = wave & (j < tl)
                last = live & (j == tl - 1)
                logits, cache = fam.decode_step_slots(
                    params, toks[:, j], p0 + j, cache, cfg, done=~live)
                if sampled:
                    chain_new, subs = sampling_lib.next_keys(chain)
                    nxt = sampling_lib.sample_logits(logits, subs,
                                                     sampling)
                    # the chain advances exactly once: on the first
                    # really-sampled token (the j == tl - 1 draw)
                    chain = jnp.where(last[:, None], chain_new, chain)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                first = jnp.where(last, nxt, first)
                return (cache, first, chain), None

            (pool, first, chain), _ = jax.lax.scan(
                body, (pool, jnp.zeros((cap,), jnp.int32), roots),
                jnp.arange(meta0.page, dtype=jnp.int32))
            tokens, positions, remaining, eos, done, keys = state
            plc = scat(plens, 0, jnp.int32)
            rmc = scat(rem0, 0, jnp.int32)
            eoc = scat(eos_new, -1, jnp.int32)
            tokens = jnp.where(wave, first, tokens)
            positions = jnp.where(wave, plc, positions)
            remaining = jnp.where(wave, rmc, remaining)
            eos = jnp.where(wave, eoc, eos)
            keys = jnp.where(wave[:, None], chain, keys)
            done = jnp.where(wave, (first == eoc) | (rmc <= 0), done)
            return ((pool,) + pools[1:],
                    (tokens, positions, remaining, eos, done, keys), first)

        hit_admit = jax.jit(hit_fn, donate_argnums=(1, 2))
        if windowed:
            # miss-admission companion: copy the prompt's last intact
            # full pages out of the (ring-layout) prefill scratch into
            # registry-only pages, so later admissions can reconstruct
            def reg_fn(pool0, rows0, reg_pids, reg_blk):
                return paged_lib.register_copy(pool0, reg_pids, reg_blk,
                                               rows0, meta0)

            reg_copy = jax.jit(reg_fn, donate_argnums=(0,))
    fns = (loop, prefill, draft_prefill, admit, evict, hit_admit, fb_loop,
           reg_copy)
    if mesh_plan is not None:
        fns = tuple(mesh_plan.wrap(f) for f in fns)
    return fns


@dataclasses.dataclass
class Request:
    """One generation request.

    ``deadline`` (seconds from arrival/submission) overrides the
    engine-wide TTL; ``n_committed`` marks the last N prompt tokens as
    previously-COMMITTED generated tokens — the journal-resume contract:
    the "prompt" is the original prompt ‖ the committed run, prefill
    re-derives the exact next token, and the budget counts the committed
    run against ``max_new_tokens``.
    """
    uid: int
    prompt: np.ndarray  # (P,) int32 prompt tokens
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    arrival: float = 0.0  # seconds since trace start (trace replay only)
    deadline: Optional[float] = None  # per-request TTL override
    n_committed: int = 0  # journal resume: committed suffix of ``prompt``


@dataclasses.dataclass
class _Sequence:
    """In-flight state of an admitted request."""
    req: Request
    slot: int
    pos: int  # current length == write position of the next decode step
    tokens: List[int]
    t_first: float = 0.0  # wall time of first token (admission prefill)
    t_done: float = 0.0


class ContinuousBatchingEngine:
    """Slot-pool continuous batching over a family's slot-state protocol.

    The engine is family-agnostic: it only talks to ``init_cache`` /
    ``prefill_last`` / ``decode_step_slots`` (plus ``verify_step_slots``
    / ``commit_slots`` in speculative mode) and treats the slot pool as
    an opaque pytree whose leaves lead with (layers, capacity, ...).  That
    covers the transformer family's full KV and MLA latent caches,
    ring-buffer window KV caches (sliding-window configs — O(window)
    per-slot memory), and the O(1) recurrent states of griffin (rglru h +
    conv tails + local-attention rings) and xlstm (mLSTM C/n/m, sLSTM
    carries, conv tails).  ``repro.models.serve_supported(cfg)`` is the
    capability probe gating admission to this engine;
    ``serve.speculative.spec_pair_supported`` gates a draft/target pair.

    ``k`` is the macro-step length: decode tokens per on-device dispatch
    (speculative blocks per dispatch in speculative mode, each emitting
    up to ``d + 1`` tokens).  Larger K amortizes host work and syncs over
    more tokens; admission (and therefore TTFT for queued requests)
    happens only at block boundaries, so K trades admission latency
    against decode throughput.  ``k=1`` recovers per-token behaviour
    through the same code path.

    ``policy`` picks who wins scarce slots at admission: ``"fifo"``
    (arrival order) or ``"spf"`` — length-bucketed shortest-prefill-first,
    which groups short prompts into shared prefill buckets ahead of long
    ones, cutting pad waste in the batched admission forward (ties break
    by arrival, so spf cannot starve a long prompt behind an endless
    stream of short ones forever — it only reorders the currently-arrived
    set).
    """

    def __init__(self, cfg, params, *, capacity: int = 8,
                 max_len: int = 256, prefill_bucket: int = 16, k: int = 8,
                 policy: str = "fifo", pool: str = "dense",
                 pages: Optional[int] = None,
                 sampling: Optional[sampling_lib.SamplingParams] = None,
                 speculative: Optional[SpeculativeConfig] = None,
                 deadline: Optional[float] = None,
                 shed_age: Optional[float] = None,
                 journal=None, faults=None, mesh=None):
        if pool not in ("dense", "paged"):
            raise ValueError(f"unknown pool kind {pool!r} "
                             "(choose 'dense' or 'paged')")
        if k < 1:
            raise ValueError(f"macro-step length k must be >= 1 (got {k})")
        if policy not in POLICIES:
            raise ValueError(f"unknown admission policy {policy!r} "
                             f"(choose from {POLICIES})")
        if deadline is not None and deadline <= 0:
            raise ValueError(f"deadline must be > 0 (got {deadline})")
        if shed_age is not None and shed_age <= 0:
            raise ValueError(f"shed_age must be > 0 (got {shed_age})")
        self.capacity = capacity
        self.max_len = max_len
        self.prefill_bucket = prefill_bucket
        self.k = k
        self.policy = policy
        self.sampling = None if sampling_lib.is_greedy(sampling) \
            else sampling
        self.deadline = deadline  # engine-wide TTL (seconds); None = off
        self.shed_age = shed_age  # queue-age load-shed threshold
        self.journal = journal  # RequestJournal or None
        self.faults = faults  # FaultPlan or None (chaos harness only)
        self._pool_arg = pool  # requested pool kind (re-applied on swap)
        self.pages_arg = pages  # requested --pages budget (snapshot field)
        self._mesh_arg = mesh  # requested mesh (re-validated on swap)
        # host-side request bookkeeping.  Owned by __init__ and NEVER
        # rebuilt by _configure: a live upgrade replaces the model under
        # the traffic, not the traffic under the model.
        self.waiting: collections.deque[Request] = collections.deque()
        self.active: Dict[int, _Sequence] = {}
        self.finished: Dict[int, np.ndarray] = {}
        self.retired: List[_Sequence] = []  # kept for latency accounting
        self.rejected: Dict[int, str] = {}  # uid -> why submit refused it
        # uid -> terminal outcome: finished / expired / quarantined /
        # shed / rejected (only "finished" rows are complete outputs)
        self.outcomes: Dict[int, str] = {}
        self._seen_uids: set = set()
        self._t_submit: Dict[int, float] = {}  # uid -> wall submit time
        self._any_deadline = deadline is not None  # fast path when off
        self._fault_step = 0  # dispatches seen (FaultPlan clock)
        self._oom_waves = 0  # admission waves stalled by an oom fault
        self._poison_jit = None  # lazy donated jit of faults.poison_pool
        self._evict_pending: List[int] = []
        # (block, valid, poison, draft fault, [(slot, uid)], spec stats,
        # moe stats) of dispatched-but-unread macro steps
        self._inflight: collections.deque = collections.deque()
        # live-upgrade machinery: serve/upgrade.py attaches an
        # UpgradeManager here and drives upgrade_state through
        # serving -> relayout -> swapped at a block-readback boundary
        self.upgrade = None
        self.upgrade_state = "serving"
        self._held_for_upgrade: List[Request] = []
        self.n_upgrades = 0  # completed hot-swaps since boot
        self.n_decode_dispatches = 0
        self.n_prefills = 0  # admission-batch prefill dispatches
        self.n_host_syncs = 0  # blocking device->host reads
        self.n_tokens = 0  # generated tokens (incl. prefill first tokens)
        self.n_spec_proposed = 0  # draft tokens offered to the target
        self.n_spec_accepted = 0  # draft tokens the target kept
        self.n_admitted = 0  # requests that got a slot (+pages if paged)
        self.n_prefix_hits = 0  # admissions served from resident pages
        self.n_prefix_misses = 0  # prefix probes that found no full chain
        self.n_prefix_stalls = 0  # hits deferred on tail-page backpressure
        self.n_pages_allocated = 0  # fresh target-pool pages handed out
        self.n_expired = 0  # deadline-evicted requests (active or queued)
        self.n_quarantined = 0  # NaN/Inf-poisoned slots evicted
        self.n_shed = 0  # queued requests dropped by queue-age shedding
        self.n_spec_fallbacks = 0  # draft faults that tripped plain decode
        self.n_faults_injected = 0  # FaultPlan records actually fired
        self.n_degraded_admissions = 0  # full-reservation paged admissions
        self.n_held_for_upgrade = 0  # submits held across a swap window
        # plain macro blocks read back, and their expert-layer work summed
        # over steps and layers: token-expert pairs computed here, held
        # experts that got a pair (zero for a model without experts)
        self.n_blocks_read = 0
        self.n_moe_pairs = 0
        self.n_moe_experts_hit = 0
        # drained-window history (satellite: drain() snapshots + resets
        # the window counters; lifetime totals live here)
        self.lifetime: Dict[str, int] = {c: 0 for c in _WINDOW_COUNTERS}
        self._configure(cfg, params, speculative)

    def _configure(self, cfg, params, speculative):
        """Build — or, on a live upgrade, REBUILD — everything derived
        from the model configuration: mesh plan, slot pools + paging
        metadata, decode state, committed shardings, and the jitted fn
        set.  ``_apply_upgrade`` calls this again with the grown config
        after quiescing, which is exactly the "pool re-layout" step of
        the swap; every host-side queue/telemetry structure lives in
        ``__init__`` and survives."""
        capacity, max_len = self.capacity, self.max_len
        pool, pages = self._pool_arg, self.pages_arg
        ok, why = serve_supported(cfg)
        if not ok:
            raise NotImplementedError(
                f"continuous batching cannot serve {cfg.name!r}: {why}")
        # ``mesh``: None (single-device), "DxM", or a (data, model) tuple.
        # A 1x1 mesh is inert — the same engine serves 1..N devices.
        self.mesh_plan = None
        self.kernel_tp_fallback = False
        if self._mesh_arg is not None:
            from repro.distributed import serve_sharding
            shape = serve_sharding.validate_serve_mesh(
                self._mesh_arg, cfg, capacity, n_devices=None)
            if shape[0] * shape[1] > 1:
                if shape[0] * shape[1] != len(jax.devices()):
                    raise ValueError(
                        f"mesh {shape[0]}x{shape[1]} needs "
                        f"{shape[0] * shape[1]} devices but "
                        f"{len(jax.devices())} are visible")
                self.mesh_plan = serve_sharding.get_serve_plan(shape)
                if cfg.decode_kernel != "jnp":
                    # the Pallas slot kernels read whole pool rows per
                    # block — under TP each device only holds its head
                    # shard, so sharded engines fall back to the jnp
                    # path (token-exact either way)
                    cfg = cfg.replace(decode_kernel="jnp")
                    self.kernel_tp_fallback = True
        self.mesh_shape = (self.mesh_plan.describe()
                           if self.mesh_plan is not None else "1x1")
        self.n_devices = (self.mesh_plan.n_devices
                          if self.mesh_plan is not None else 1)
        limit = cfg.max_seq_len
        if cfg.learned_pos:
            limit = min(limit, cfg.learned_pos)
        if max_len > limit:
            # beyond this, position lookups clamp silently instead of erroring
            raise ValueError(
                f"max_len {max_len} exceeds the model's position range "
                f"{limit}")
        if speculative is not None:
            ok, why = spec_pair_supported(cfg, speculative.cfg,
                                          speculative.d, max_len)
            if not ok:
                raise NotImplementedError(
                    f"speculative serving cannot run this pair: {why}")
            if speculative.cfg.decode_kernel != cfg.decode_kernel:
                # one attention backend per engine: the draft pool's slot
                # decode and catch-up verify follow the target's switch
                speculative = SpeculativeConfig(
                    speculative.cfg.replace(decode_kernel=cfg.decode_kernel),
                    speculative.params, speculative.d)
        self.cfg = cfg
        self.params = params
        self.fam = get_family(cfg)
        self.cache_layout = slot_cache_layout(cfg)
        self.decode_kernel = cfg.decode_kernel  # telemetry / bench tag
        self.speculative = speculative

        fams = [self.fam]
        cfgs = [cfg]
        if speculative is not None:
            fams.append(get_family(speculative.cfg))
            cfgs.append(speculative.cfg)
        # Probe every pool's natural paging geometry first: families
        # DECLARE their pageable cache groups through the slot-state
        # protocol (``models.paged_groups``), so paging is no longer a
        # transformer-shaped structural guess — xlstm pages its conv
        # tails (mLSTM carries stay dense-per-slot), MLA pages its
        # latent caches, griffin pages its local-attention rings (and
        # keeps them paged under speculation via the paged ring-restore
        # commit).  A family that declares nothing stays dense WITH a
        # named reason instead of a silent ``pool_kind`` flip.
        probe = [None] * len(fams)
        reasons = []
        if pool == "paged":
            for i, (f, c) in enumerate(zip(fams, cfgs)):
                probe[i] = paged_lib.pool_meta(
                    c, jax.eval_shape(lambda f=f, c=c: f.init_cache(
                        c, capacity, max_len)))
                if probe[i] is None:
                    role = "target" if i == 0 else "draft"
                    reasons.append(
                        f"{role}: "
                        f"{paged_lib.pool_fallback_reason(c) or 'unpageable cache layout'}")
        self.pool_fallback_reason = "; ".join(reasons) or None
        paged_idx = [i for i, m in enumerate(probe) if m is not None]
        # ONE page-id space across every paged pool of the engine: page
        # ``p`` is row ``p`` of each pool's arenas, a request allocates
        # its worst-case page count once and every pool consumes the
        # leading slice — so an explicit --pages budget is real shared
        # memory (draft and target trade pages freely) instead of the
        # old static per-pool split.
        self.pages_budget = None
        n_pages = None
        if paged_idx:
            n_pages = int(pages) if pages else max(
                probe[i].n_pages for i in paged_idx)
            self.pages_budget = n_pages
        pools, metas = [], []
        for i, (f, c) in enumerate(zip(fams, cfgs)):
            if probe[i] is not None:
                p, m = paged_lib.build_paged_pool(f, c, capacity, max_len,
                                                  n_pages=n_pages)
            else:
                p, m = f.init_cache(c, capacity, max_len), None
            pools.append(p)
            metas.append(m)
        self._pools = tuple(pools)
        self._metas = tuple(metas)
        self._paged = bool(paged_idx)
        self.pool_kind = "paged" if self._paged else "dense"
        # pool index -> refcount namespace in the shared allocator
        self._ns_of = {pi: j for j, pi in enumerate(paged_idx)}
        self._alloc = paged_lib.PageAllocator(
            metas[paged_idx[0]], namespaces=len(paged_idx)) \
            if paged_idx else None
        # slot -> page-id list owned by the admitted request (one list:
        # every paged pool consumes its leading slice of the same ids)
        self._slot_pages: Dict[int, list] = {}
        # release()d pages awaiting their zeroing scatter (rollbacks);
        # a page that hits global zero is zeroed in EVERY paged pool
        self._zero_pending: List[int] = []
        # shared-prefix admission: meaningful where the target's seq
        # pages are absolute-position-addressed (full KV, MLA latents)
        # or reconstructible (rings with at least one page of slack
        # over the window — the admission prefill's partial tail page
        # always clobbers the oldest ring page, so a slack-less ring
        # has no intact shareable tail).  Sampled engines take the path
        # too: the hit replays the request's (seed, uid) chain on
        # device, so hit and miss admissions emit identical tokens.
        window = getattr(cfg, "window", None)
        self._windowed = bool(window)
        ring_ok = True
        if window and metas[0] is not None:
            ring_ok = (metas[0].nblk - 1) * metas[0].page + 1 >= window \
                and metas[0].nblk > 1
        self._prefix_ok = (metas[0] is not None and speculative is None
                           and cfg.family == "transformer"
                           and metas[0].page > 0 and ring_ok)
        self._spec_fallback = False  # draft faulted: plain macro decode
        self._arena_degraded = False  # paged arena faulted: no sharing
        # persistent device-resident decode state: (tokens, positions,
        # remaining, eos_ids, done, sampling keys) — idle slots are done
        self._state = (jnp.zeros((capacity,), jnp.int32),
                       jnp.zeros((capacity,), jnp.int32),
                       jnp.zeros((capacity,), jnp.int32),
                       jnp.full((capacity,), -1, jnp.int32),
                       jnp.ones((capacity,), bool),
                       jnp.zeros((capacity, 2), jnp.uint32))
        if self.mesh_plan is not None:
            # Commit every long-lived buffer to the mesh ONCE, here.
            # After this, each macro step's cross-device traffic is only
            # the per-layer TP collectives GSPMD inserts in the forward
            # pass — the host never moves pool bytes again (readback is
            # the per-slot token/done scalars only).
            from repro.distributed import serve_sharding
            plan = self.mesh_plan
            self.params = jax.device_put(
                self.params, plan.params_shardings_for(self.fam, cfg,
                                                       self.params))
            if self.speculative is not None:
                self.speculative = SpeculativeConfig(
                    self.speculative.cfg,
                    jax.device_put(
                        self.speculative.params,
                        plan.params_shardings_for(
                            get_family(self.speculative.cfg),
                            self.speculative.cfg,
                            self.speculative.params)),
                    self.speculative.d)
            self._pools = tuple(
                jax.device_put(p, plan.pool_shardings(f, c, p, m))
                for f, c, p, m in zip(fams, cfgs, self._pools,
                                      self._metas))
            self._state = jax.device_put(self._state,
                                         plan.state_shardings())
            self.params_bytes_per_device = serve_sharding.per_device_bytes(
                self.params)
            self.pool_bytes_per_device = serve_sharding.per_device_bytes(
                self._pools)
        else:
            from repro.distributed.serve_sharding import per_device_bytes
            self.params_bytes_per_device = per_device_bytes(self.params)
            self.pool_bytes_per_device = per_device_bytes(self._pools)
        if self.mesh_plan is not None and self.mesh_plan.data > 1:
            # admission round-robins consecutive requests across the data
            # replicas' slot bands (pop from the end)
            self.free = self.mesh_plan.free_slot_order(capacity)[::-1]
        else:
            self.free = list(range(capacity))[::-1]  # pop -> slot 0..

        spec_key = None if speculative is None \
            else (speculative.cfg, speculative.d)
        (self._loop, self._prefill, self._draft_prefill, self._admit,
         self._evict, self._hit_admit, self._fb_loop,
         self._reg_copy) = _jitted_engine_fns(
            cfg, self.k, self.sampling, spec_key, self._metas,
            self.mesh_plan)

    @property
    def pool(self):
        """The target model's slot pool (kept for telemetry/tests)."""
        return self._pools[0]

    @property
    def acceptance_rate(self) -> float:
        """Fraction of draft proposals the target accepted (speculative
        mode; 0.0 before any speculative block was read back)."""
        return self.n_spec_accepted / max(self.n_spec_proposed, 1)

    @property
    def pages_in_use(self) -> int:
        """Live (refcounted) pages in the shared arena right now (0 when
        dense)."""
        return self._alloc.pages_in_use() if self._alloc is not None else 0

    @property
    def pages_highwater(self) -> int:
        """Peak live shared-arena pages since construction (0 when
        dense)."""
        return self._alloc.highwater if self._alloc is not None else 0

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prefix probes served from resident pages (current
        drain window)."""
        probes = self.n_prefix_hits + self.n_prefix_misses
        return self.n_prefix_hits / max(probes, 1)

    def lifetime_totals(self) -> Dict[str, int]:
        """Window counters summed across every drained window PLUS the
        live one — the "since boot" view ``drain()`` no longer clobbers."""
        return {c: self.lifetime[c] + getattr(self, c)
                for c in _WINDOW_COUNTERS}

    # ------------------------------------------------------------- admission
    def _reject(self, uid: int, why: str):
        """Graceful rejection: record, journal, keep serving.  The uid is
        NOT marked seen — a corrected resubmission is fine."""
        self.rejected[uid] = why
        self.outcomes[uid] = "rejected"
        if self.journal is not None:
            self.journal.record_reject(uid, why)

    def _invalid_reason(self, req: Request) -> Optional[str]:
        """Every malformed-request class, in one place.  A mid-trace bad
        request must never raise out of ``submit`` — a replayed trace (or
        a hostile client) would otherwise kill every in-flight sequence
        over one request that was never servable anyway."""
        P, nc = len(req.prompt), req.n_committed
        if req.max_new_tokens < 1:
            return ("max_new_tokens must be >= 1 "
                    "(prefill always emits the first token)")
        if P < 1:
            return "empty prompt"
        if not (0 <= nc < req.max_new_tokens and nc < P):
            return (f"n_committed {nc} must lie in [0, max_new_tokens) "
                    "and leave at least one real prompt token")
        if req.eos_id is not None and not (
                0 <= req.eos_id < self.cfg.vocab_size):
            return (f"eos_id {req.eos_id} outside the vocabulary "
                    f"[0, {self.cfg.vocab_size})")
        if req.deadline is not None and req.deadline <= 0:
            return f"deadline must be > 0 (got {req.deadline})"
        toks = np.asarray(req.prompt)
        if toks.size and (int(toks.min()) < 0
                          or int(toks.max()) >= self.cfg.vocab_size):
            return (f"prompt tokens outside the vocabulary "
                    f"[0, {self.cfg.vocab_size})")
        # a resumed request's committed run sits in its prompt, so the
        # cache needs P - nc original + max_new positions, not P + max_new
        if P - nc + req.max_new_tokens > self.max_len:
            return (f"prompt {P - nc} + {req.max_new_tokens} new tokens "
                    f"exceeds max_len {self.max_len}")
        if self._alloc is not None:
            need = max(paged_lib.pages_needed(
                P, req.max_new_tokens - nc, m)
                for m in self._metas if m is not None)
            if need > self._alloc.meta.n_pages:
                # a request no eviction wave can ever make room for must
                # not enter the queue: _admit_batch would push it back to
                # the front forever and livelock the whole server
                return (f"needs {need} pages but the arena holds only "
                        f"{self._alloc.meta.n_pages} (raise --pages or "
                        f"shrink the request)")
        return None

    def submit(self, req: Request):
        if req.uid in self._seen_uids or any(
                r.uid == req.uid for r in self._held_for_upgrade):
            # a DUPLICATE uid is a caller bug, not a malformed request:
            # silently rejecting it would orphan the caller's wait on
            # the first submission's output
            raise ValueError(f"request uid {req.uid} already submitted")
        if self.upgrade_state == "relayout":
            # mid-swap the geometry (and therefore validity — max_len,
            # vocab, page need) is changing underneath us: hold the
            # request and run it through the ordinary submit path once
            # the flip lands, instead of racing the pool re-layout
            self._held_for_upgrade.append(req)
            self.n_held_for_upgrade += 1
            self._t_submit.setdefault(req.uid, time.monotonic())
            return
        self._submit_checked(req)

    def _submit_checked(self, req: Request):
        """Validate + enqueue (the body of ``submit`` past the dup-uid
        and upgrade gates; also the release path for held submissions)."""
        why = self._invalid_reason(req)
        if why is not None:
            self._t_submit.pop(req.uid, None)
            self._reject(req.uid, f"request {req.uid}: {why}")
            return
        self._seen_uids.add(req.uid)
        # setdefault: a request held across a swap keeps its original
        # submit time, so deadlines/shedding count the held window too
        self._t_submit.setdefault(req.uid, time.monotonic())
        if req.deadline is not None:
            self._any_deadline = True
        if self.journal is not None:
            self.journal.record_submit(req)
        self.waiting.append(req)

    def _bucketed(self, n: int) -> int:
        b = self.prefill_bucket
        return min(-(-n // b) * b, self.max_len)

    def _select_admissions(self, now: Optional[float]) -> List[Request]:
        """Pick the arrived requests to admit into the free slots.

        FIFO takes them in submission order (the original behaviour);
        ``spf`` sorts the currently-arrived set by bucketed prefill
        length first (ties by submission order), so short prompts share
        admission buckets instead of padding up to a long straggler's
        bucket — less pad waste per batched prefill and faster TTFT for
        cheap requests.  Selection never skips an arrived request when a
        slot is free for it.

        Cost note: this used to ``del self.waiting[i]`` once per taken
        request — each delete is O(queue) on a deque, so a deep backlog
        paid O(queue * capacity) per admission wave on top of the scan.
        Selection is now one linear pass and ONE queue rebuild per wave
        (and the common fifo/no-clock case is a plain popleft run).
        """
        nfree = len(self.free)
        if nfree == 0 or not self.waiting:
            return []
        if now is None and self.policy == "fifo":
            # everything has "arrived": take straight off the head
            return [self.waiting.popleft()
                    for _ in range(min(nfree, len(self.waiting)))]
        items = list(self.waiting)
        arrived = [i for i, r in enumerate(items)
                   if now is None or r.arrival <= now]
        if self.policy == "spf":
            arrived.sort(key=lambda i: (
                self._bucketed(len(items[i].prompt)), i))
        take = arrived[:nfree]
        if not take:
            return []
        taken = set(take)
        self.waiting = collections.deque(
            r for i, r in enumerate(items) if i not in taken)
        return [items[i] for i in take]

    def _alloc_request(self, req: Request):
        """Reserve shared-arena pages for one request.

        Returns an admission record, or None on backpressure (nothing is
        held — the alloc is all-or-nothing).  A request allocates its
        WORST-CASE page count across the engine's paged pools once, with
        a reference in every paged pool's namespace; each pool's block
        table consumes the leading slice of the same ids (page ``p`` is
        a row in every pool's arenas), so draft and target trade freely
        inside one budget.

        The target pool is probed for a shared-prefix hit first.  Full /
        MLA layouts: every full page strictly before the prompt's last
        token must resolve through the registry (full chain or nothing);
        the request increfs the resident pages, allocates only its
        private tail, and rides the no-prefill admission path.  Ring
        layouts cannot alias resident pages (the slot's ring keeps
        wrapping over them), so a ring hit pins the registered tail
        copies only long enough for ``_admit_hits`` to COPY them into
        the slot's freshly-allocated private ring pages — the chained
        digest of the last looked-up page commits to the entire prefix,
        so matching just the reconstructible tail still proves identity.
        """
        P = len(req.prompt)
        n_new = req.max_new_tokens - req.n_committed
        alloc = self._alloc
        ns_all = tuple(self._ns_of.values())
        info = {"hit": False, "share": 0, "nreg": 0, "digests": None,
                "pids": None, "resident": None}
        if self._prefix_ok and not self._arena_degraded:
            meta = self._metas[0]
            digests = paged_lib.prefix_digests(req.prompt, meta.page)
            info["digests"] = digests
            share = (P - 1) // meta.page  # >= 1 private tail token stays
            # rings can only reconstruct the last nblk - 1 full pages
            # (the prefill tail always clobbered the oldest ring page)
            nreg = min(share, meta.nblk - 1) if self._windowed else share
            resident = alloc.lookup(digests[share - nreg:share]) \
                if nreg > 0 else None
            if resident is not None:
                # Pin the resident pages BEFORE the tail alloc: under
                # memory pressure alloc() reclaims zero-ref LRU-retained
                # pages, which can include the very pages lookup() just
                # returned — the same physical page would then serve as
                # both a shared prefix page and a private tail page of
                # this slot, and tail writes would corrupt the prefix KV.
                alloc.incref(resident)
                total = paged_lib.pages_needed(P, n_new, meta)
                # a ring hit's pages are ALL private (resident copies
                # are sources for the reconstruction, not aliased)
                tail = alloc.alloc(total if self._windowed
                                   else total - share, ns=ns_all)
                if tail is None:
                    # Tail backpressure, NOT a registry miss: unpin and
                    # wait for the next eviction wave.  (A fresh full
                    # alloc of ``total > tail`` pages cannot succeed
                    # either, so don't fall through to the miss path.)
                    self._zero_pending.extend(alloc.release(resident))
                    self.n_prefix_stalls += 1
                    return None
                info.update(hit=True, share=share, nreg=nreg)
                if self._windowed:
                    info["pids"] = tail
                    info["resident"] = list(resident)
                else:
                    info["pids"] = list(resident) + tail
                self.n_prefix_hits += 1
                self.n_pages_allocated += len(tail)
                return info
            if nreg > 0:
                self.n_prefix_misses += 1
        paged_metas = [m for m in self._metas if m is not None]
        # degradation ladder: once the arena has seen a poisoned slot,
        # sharing is off and every admission reserves its FULL block
        # table (dense-pool semantics on paged storage) — worst-case
        # isolation in exchange for capacity
        need = max(m.nblk for m in paged_metas) if self._arena_degraded \
            else max(paged_lib.pages_needed(P, n_new, m)
                     for m in paged_metas)
        pids = alloc.alloc(need, ns=ns_all)
        if pids is None:
            return None
        info["pids"] = pids
        self.n_pages_allocated += len(pids)
        if self._arena_degraded:
            self.n_degraded_admissions += 1
        return info

    def _admit_batch(self, now: Optional[float]):
        """Admit every arrived request a free slot can take, ONE prefill
        dispatch per model + ONE pool/state scatter + ONE host sync per
        prefill-bucket group — instead of three host syncs per request.

        Paged pools add two stages in front: a host-side page-allocation
        pass (all-or-nothing per request; the first request that cannot
        get its pages returns itself and everything grabbed after it to
        the FRONT of the queue, preserving order), and the prefix probe
        that diverts full-chain hits to the no-prefill admission path.
        """
        if self._oom_waves > 0:
            # injected allocator exhaustion: this wave admits nothing
            # (requests stay queued — exactly the page-backpressure path)
            if self.waiting:
                self._oom_waves -= 1
            return
        grabbed = self._select_admissions(now)
        if not grabbed:
            return
        with tracing.span("admit") as sp:
            if self._paged:
                pairs = []
                with tracing.span("admit.alloc"):
                    for i, r in enumerate(grabbed):
                        info = self._alloc_request(r)
                        if info is None:
                            # page backpressure: wait for the next
                            # eviction wave
                            self.waiting.extendleft(reversed(grabbed[i:]))
                            break
                        pairs.append((r, info))
            else:
                pairs = [(r, None) for r in grabbed]
            sp.set_metadata(n=len(pairs), uids=[r.uid for r, _ in pairs])
            misses = [(r, a) for r, a in pairs
                      if a is None or not a["hit"]]
            hits = [(r, a) for r, a in pairs
                    if a is not None and a["hit"]]
            if misses:
                self._admit_miss_groups(misses)
            if hits:
                with tracing.span("admit.hits", n=len(hits)):
                    self._admit_hits(hits)

    def _admit_miss_groups(self, pairs):
        """The batched-prefill admission path (dense pools, and paged
        requests whose prefix missed)."""
        groups: Dict[int, list] = {}
        for r, a in pairs:
            groups.setdefault(self._bucketed(len(r.prompt)),
                              []).append((r, a))
        for bucket, group in sorted(groups.items()):
            n = len(group)
            npad = _pow2(n)  # bound (group size, bucket) compile count
            with tracing.span("admit.prefill", bucket=bucket, n=n,
                              npad=npad):
                slots, plens, first = self._prefill_group(bucket, group,
                                                          npad)
            self.n_prefills += 1
            with tracing.span("admit.sync"):
                first_host = np.asarray(first)
            self.n_host_syncs += 1
            t = time.monotonic()
            for j, (r, a) in enumerate(group):
                # a resume re-enters holding its committed run: output
                # continuity without replaying already-delivered tokens
                prior = [int(x) for x in
                         r.prompt[len(r.prompt) - r.n_committed:]] \
                    if r.n_committed else []
                seq = _Sequence(r, int(slots[j]), pos=int(plens[j]),
                                tokens=prior + [int(first_host[j])],
                                t_first=t)
                self.active[seq.slot] = seq
                self.n_tokens += 1
                self.n_admitted += 1
                if self.journal is not None:
                    self.journal.record_tokens(r.uid, [int(first_host[j])])
                if (a is not None and self._prefix_ok and a["digests"]
                        and not self._windowed):
                    # pages fully covered by the prompt now hold its
                    # canonical prefill-built KV — make them shareable.
                    # (Tail pages decode-built by the HIT path are never
                    # registered: only prefill bytes enter the registry.
                    # Windowed rings registered via the copy pass above.)
                    reg = len(r.prompt) // self._metas[0].page
                    if reg:
                        self._alloc.register(a["digests"][:reg],
                                             a["pids"][:reg])
                self._finish_if_done(seq, seq.tokens[-1])
            if self.journal is not None:
                # ride the admission host sync that just happened
                self._flush_journal()

    def _prefill_group(self, bucket, group, npad):
        """Enqueue one bucket group's prefill and its pool/state
        scatter (no host sync).  Returns (slots, plens, first tokens)."""
        n = len(group)
        padded = np.zeros((npad, bucket), np.int32)
        plens = np.ones((npad,), np.int32)
        rem0 = np.zeros((npad,), np.int32)
        eos_new = np.full((npad,), -1, np.int32)
        # padding rows target the out-of-range slot ``capacity``:
        # their scatters are dropped entirely (paged pools likewise
        # pad block-table rows with the out-of-range page sentinel)
        slots = np.full((npad,), self.capacity, np.int32)
        bt_rows = [None if m is None else
                   np.full((npad, m.nblk), m.sentinel, np.int32)
                   for m in self._metas]
        for j, (r, a) in enumerate(group):
            plens[j] = len(r.prompt)
            padded[j, :plens[j]] = r.prompt
            # a resume's committed run is part of its prompt and
            # already spent that much budget
            rem0[j] = r.max_new_tokens - r.n_committed - 1
            eos_new[j] = -1 if r.eos_id is None else r.eos_id
            slots[j] = self.free.pop()
            if a is not None:
                pids = a["pids"]
                self._slot_pages[int(slots[j])] = pids
                for pi, m in enumerate(self._metas):
                    if m is not None:
                        cnt = min(len(pids), m.nblk)
                        bt_rows[pi][j, :cnt] = pids[:cnt]
        rows = [self.fam.init_cache(self.cfg, npad, self.max_len)]
        # pad-tail cache entries are garbage but never visible: each
        # decode step overwrites its own position before the per-row
        # length mask reaches it
        if self.sampling is None:
            first, rows[0] = self._prefill(
                self.params, jnp.asarray(padded), jnp.asarray(plens),
                rows[0])
            keys_dev = jnp.zeros((npad, 2), jnp.uint32)
        else:
            # chain roots are derived from (seed, uid) ON DEVICE in
            # the same prefill dispatch — no key round-trip/sync;
            # ``skips`` replays a resume's committed-run chain splits
            # so its first fresh sample draws from the same chain
            # position as the uninterrupted run
            uids = np.zeros((npad,), np.int32)
            uids[:n] = [r.uid for r, _ in group]
            skips = np.zeros((npad,), np.int32)
            skips[:n] = [r.n_committed for r, _ in group]
            first, rows[0], keys_dev = self._prefill(
                self.params, jnp.asarray(padded), jnp.asarray(plens),
                rows[0], jnp.asarray(uids), jnp.asarray(skips))
        if self.speculative is not None:
            # the draft pool admits the SAME prompt rows: its per-row
            # state after the real prompt, first token comes from the
            # target
            draft_rows = get_family(self.speculative.cfg).init_cache(
                self.speculative.cfg, npad, self.max_len)
            rows.append(self._draft_prefill(
                self.speculative.params, jnp.asarray(padded),
                jnp.asarray(plens), draft_rows))
            self.n_prefills += 1
        if (self._windowed and self._prefix_ok
                and not self._arena_degraded
                and self._reg_copy is not None):
            # ring prefix cache: the admit scatter is about to write
            # RING-wrapped pages, which the donor will keep
            # overwriting — so copy the prompt's last intact full
            # pages out of the prefill scratch into registry-only
            # pages first (best-effort: an admission proceeds fine
            # without registering, it just can't donate hits)
            meta = self._metas[0]
            reg_pids = np.full((npad, meta.nblk), meta.sentinel,
                               np.int32)
            reg_blk = np.zeros((npad, meta.nblk), np.int32)
            reg_records = []
            for j, (r, a) in enumerate(group):
                if a is None or not a["digests"]:
                    continue
                share = (len(r.prompt) - 1) // meta.page
                nreg = min(share, meta.nblk - 1)
                if nreg <= 0:
                    continue
                got = self._alloc.alloc(nreg)
                if got is None:
                    continue
                for t, ab in enumerate(range(share - nreg, share)):
                    reg_pids[j, t] = got[t]
                    reg_blk[j, t] = ab % meta.nblk
                reg_records.append(
                    (a["digests"][share - nreg:share], got))
            if reg_records:
                pool0 = self._reg_copy(
                    self._pools[0], rows[0], jnp.asarray(reg_pids),
                    jnp.asarray(reg_blk))
                self._pools = (pool0,) + self._pools[1:]
                for dg, got in reg_records:
                    self._alloc.register(dg, got)
                    # registered pages retire to the LRU with their
                    # bytes intact; a first-writer-wins loser comes
                    # back on the zero list and is freed
                    self._zero_pending.extend(self._alloc.release(got))
        self._pools, self._state = self._admit(
            self._pools, tuple(rows), self._state, jnp.asarray(slots),
            tuple(None if b is None else jnp.asarray(b)
                  for b in bt_rows),
            first, jnp.asarray(plens), jnp.asarray(rem0),
            jnp.asarray(eos_new), keys_dev)
        return slots, plens, first

    def _admit_hits(self, pairs):
        """No-prefill admission: point the slots' leading block-table
        entries at the resident shared pages (full / MLA layouts) or
        reconstruct the slot's private ring from the registered
        absolute-position copies (windowed layouts), then run ONLY the
        private tail tokens (at most one page of them) through masked
        decode steps inside one jit — no bucket prefill dispatch at
        all.  Sampled engines derive the first token from the request's
        (seed, uid) chain inside the same jit."""
        meta = self._metas[0]
        n = len(pairs)
        npad = _pow2(n)
        slots = np.full((npad,), self.capacity, np.int32)
        bt_rows = np.full((npad, meta.nblk), meta.sentinel, np.int32)
        src_pids = np.full((npad, meta.nblk), meta.sentinel, np.int32)
        dst_pids = np.full((npad, meta.nblk), meta.sentinel, np.int32)
        tail_tokens = np.zeros((npad, meta.page), np.int32)
        tail_len = np.zeros((npad,), np.int32)
        pos0 = np.zeros((npad,), np.int32)
        plens = np.ones((npad,), np.int32)
        rem0 = np.zeros((npad,), np.int32)
        eos_new = np.full((npad,), -1, np.int32)
        uids = np.zeros((npad,), np.int32)
        skips = np.zeros((npad,), np.int32)
        for j, (r, a) in enumerate(pairs):
            pids = a["pids"]
            slots[j] = self.free.pop()
            self._slot_pages[int(slots[j])] = pids
            bt_rows[j, :len(pids)] = pids
            pos0[j] = a["share"] * meta.page
            if self._windowed:
                # absolute page ``ab`` was registered at copy ``t`` and
                # lands in the slot's private ring page for block
                # ``ab % nblk`` — the exact rotation a sequential fill
                # of the ring would have left it at
                for t, ab in enumerate(range(a["share"] - a["nreg"],
                                             a["share"])):
                    src_pids[j, t] = a["resident"][t]
                    dst_pids[j, t] = pids[ab % meta.nblk]
            tail = np.asarray(r.prompt[pos0[j]:], np.int32)
            tail_len[j] = len(tail)
            tail_tokens[j, :len(tail)] = tail
            plens[j] = len(r.prompt)
            rem0[j] = r.max_new_tokens - r.n_committed - 1
            eos_new[j] = -1 if r.eos_id is None else r.eos_id
            uids[j] = r.uid
            skips[j] = r.n_committed
        self._pools, self._state, first = self._hit_admit(
            self.params, self._pools, self._state, jnp.asarray(slots),
            jnp.asarray(bt_rows), jnp.asarray(src_pids),
            jnp.asarray(dst_pids), jnp.asarray(tail_tokens),
            jnp.asarray(tail_len), jnp.asarray(pos0), jnp.asarray(plens),
            jnp.asarray(rem0), jnp.asarray(eos_new), jnp.asarray(uids),
            jnp.asarray(skips))
        if self._windowed:
            # the reconstruction copy has consumed the resident pages
            # (ordering via the donated pool buffer chain); unpin them —
            # still-registered pages retire back to the LRU intact
            for _, a in pairs:
                if a["resident"]:
                    self._zero_pending.extend(
                        self._alloc.release(a["resident"]))
        with tracing.span("admit.sync"):
            first_host = np.asarray(first)  # capacity-wide: index by slot
        self.n_host_syncs += 1
        t = time.monotonic()
        for j, (r, a) in enumerate(pairs):
            slot = int(slots[j])
            prior = [int(x) for x in
                     r.prompt[len(r.prompt) - r.n_committed:]] \
                if r.n_committed else []
            seq = _Sequence(r, slot, pos=int(plens[j]),
                            tokens=prior + [int(first_host[slot])],
                            t_first=t)
            self.active[slot] = seq
            self.n_tokens += 1
            self.n_admitted += 1
            if self.journal is not None:
                self.journal.record_tokens(r.uid, [int(first_host[slot])])
            self._finish_if_done(seq, seq.tokens[-1])
        if self.journal is not None:
            self._flush_journal()

    def _flush_journal(self):
        with tracing.span("journal.flush"):
            self.journal.flush()

    # ------------------------------------------------------------- lifecycle
    def _finish_if_done(self, seq: _Sequence, last_token: int):
        """Host-side stopping rule — the exact mirror of the in-scan rule
        (the device marks the row done at the same token)."""
        done = (len(seq.tokens) >= seq.req.max_new_tokens
                or (seq.req.eos_id is not None
                    and last_token == seq.req.eos_id))
        if not done:
            return
        self._retire(seq, "finished")

    def _retire(self, seq: _Sequence, outcome: str):
        """Retire a sequence with a terminal ``outcome`` — the shared
        tail of normal completion AND forced eviction (expiry,
        quarantine).  Partial tokens are still delivered: a request the
        watchdog killed keeps everything it committed."""
        seq.t_done = time.monotonic()
        self.finished[seq.req.uid] = np.asarray(seq.tokens, np.int32)
        self.outcomes[seq.req.uid] = outcome
        if self.journal is not None:
            self.journal.record_finish(seq.req.uid, outcome)
        self.retired.append(seq)
        del self.active[seq.slot]
        # the slot re-enters ``free`` only once its eviction has been
        # APPLIED (_flush_evictions) — handing it out earlier would let a
        # same-wave admission claim it and then be wiped by the pending
        # zero-evict
        self._evict_pending.append(seq.slot)

    def _quarantine(self, seq: _Sequence):
        """Evict a slot whose logits went non-finite.  The device row
        already froze itself (the in-scan sentinel folds into the done
        mask at the bad step, committing nothing from it), so quarantine
        is an ordinary forced retirement — plus arena degradation: a
        paged pool can no longer trust resident prefix pages, so the
        registry is flushed and admissions fall back to full
        reservation."""
        self.n_quarantined += 1
        self._retire(seq, "quarantined")
        if self._alloc is not None and not self._arena_degraded:
            self._arena_degraded = True
            self._zero_pending.extend(self._alloc.flush_registry())
            self._prefix_ok = False

    def _deadline_of(self, req: Request) -> Optional[float]:
        return req.deadline if req.deadline is not None else self.deadline

    def _age(self, req: Request, now: Optional[float]) -> float:
        """Seconds since the request entered the system: trace-clock when
        replaying arrivals, wall-clock since ``submit`` otherwise."""
        if now is not None:
            return now - req.arrival
        return time.monotonic() - self._t_submit.get(req.uid,
                                                     time.monotonic())

    def _expire(self, now: Optional[float]):
        """Deadline watchdog + queue-age load shedding.  No-op (single
        dict check) unless a TTL or shed threshold is configured, so the
        fault-free path pays nothing."""
        if not self._any_deadline and self.shed_age is None:
            return
        for seq in list(self.active.values()):
            ddl = self._deadline_of(seq.req)
            if ddl is not None and self._age(seq.req, now) > ddl:
                self.n_expired += 1
                self._retire(seq, "expired")
        if not self.waiting:
            return
        keep = collections.deque()
        for r in self.waiting:
            age = self._age(r, now)
            ddl = self._deadline_of(r)
            if ddl is not None and age > ddl:
                # expired before ever getting a slot: empty output, same
                # terminal telemetry as an active expiry
                self.n_expired += 1
                self.finished[r.uid] = np.zeros((0,), np.int32)
                self.outcomes[r.uid] = "expired"
                if self.journal is not None:
                    self.journal.record_finish(r.uid, "expired")
            elif self.shed_age is not None and age > self.shed_age:
                # sustained backpressure: drop the oldest queued work
                # with an explicit outcome instead of serving everyone
                # late; the uid may be resubmitted after the storm
                self.n_shed += 1
                self.outcomes[r.uid] = "shed"
                self.rejected[r.uid] = (
                    f"shed after {age:.3f}s queued (> {self.shed_age})")
                self._seen_uids.discard(r.uid)
                if self.journal is not None:
                    self.journal.record_finish(r.uid, "shed")
            else:
                keep.append(r)
        self.waiting = keep

    def _flush_evictions(self):
        """Zero-evict retired slots and reset their decode state, batched
        into one fixed-shape donated scatter (slot list padded to capacity
        with the dropped out-of-range index — a single compile).

        Even though admission's full-row overwrite already guarantees
        correctness, in multi-tenant serving a retired request's KV must
        not outlive the request in device memory; resetting the frozen
        token also means idle-slot no-op steps derive from token 0, never
        from a previous tenant's text.

        Paged pools release the retired slots' pages here too (symmetric
        with slot reuse — a page re-enters circulation only once its
        zeroing is applied).  Pages whose refcount drops to zero while
        PREFIX-REGISTERED are retained with their bytes intact (they ARE
        the cached value) and are absent from the zero list.
        """
        if not self._evict_pending and not (self._paged
                                            and self._zero_pending):
            return
        with tracing.span("evict", n=len(self._evict_pending)):
            zero = list(self._zero_pending)
            self._zero_pending.clear()
            for slot in self._evict_pending:
                pids = self._slot_pages.pop(slot, None)
                if pids:
                    # one reference per namespace was taken at admission; a
                    # page crosses GLOBAL zero during exactly one of these
                    # releases and must then be zeroed in EVERY paged pool
                    # (it is a row in each pool's arenas)
                    for ns in self._ns_of.values():
                        zero.extend(self._alloc.release(pids, ns=ns))
            slots = np.full((self.capacity,), self.capacity, np.int32)
            slots[:len(self._evict_pending)] = self._evict_pending
            if not self._paged:
                self._pools, self._state = self._evict(
                    self._pools, self._state, jnp.asarray(slots),
                    (None,) * len(self._pools))
            else:
                # fixed zero-list shape (capacity * max nblk, shared by all
                # paged pools) bounds the compile count; overflow (possible
                # after alloc rollbacks) loops — the slot scatter is
                # idempotent
                lim = self.capacity * max(m.nblk for m in self._metas
                                          if m is not None)
                while True:
                    take = zero[:lim]
                    del zero[:lim]
                    chunk = []
                    for m in self._metas:
                        if m is None:
                            chunk.append(None)
                            continue
                        zp = np.full((lim,), m.sentinel, np.int32)
                        zp[:len(take)] = take
                        chunk.append(jnp.asarray(zp))
                    self._pools, self._state = self._evict(
                        self._pools, self._state, jnp.asarray(slots),
                        tuple(chunk))
                    if not zero:
                        break
            self.free.extend(self._evict_pending)
            self._evict_pending.clear()

    # ----------------------------------------------------------- live upgrade
    def _apply_upgrade(self, mgr) -> None:
        """Hot-swap the grown model under live traffic.  Driven by an
        attached :class:`repro.serve.upgrade.UpgradeManager` at a
        block-readback boundary (``poll`` from :meth:`step`/:meth:`run`).

        The pause is ONE quiesce, not a compile (the manager pre-warmed
        the grown fn set): every in-flight macro block is read back and
        its tokens committed, each mid-flight sequence becomes a
        journal-style resume request (original prompt ‖ committed run,
        ``n_committed`` marking the suffix), the pools / decode state /
        shardings / jitted fns are rebuilt for the grown geometry, and
        the resumes re-enter through the ordinary admission path at the
        FRONT of the queue — ahead of everything that was still waiting.
        Zero requests are dropped: a resume's position and page need
        equal its original request's, so it is admissible by
        construction."""
        with tracing.span("upgrade.swap"):
            t0 = time.perf_counter()
            self.upgrade_state = "relayout"
            while self._inflight:
                self._process(self._inflight.popleft())
            self._flush_evictions()
            # page-residency delta: pages live at quiesce are all LOST by the
            # swap — cache rows are internal activations of the OLD function
            # (grown params + re-laid geometry invalidate every byte), so
            # "carried" is structurally zero and the visible cost of a live
            # upgrade is the re-prefill page bill of the resume wave.
            pages_at_swap = self.pages_in_use
            resumes: List[Request] = []
            for seq in sorted(self.active.values(),
                              key=lambda s: (s.t_first, s.req.uid)):
                r = seq.req
                orig = (r.prompt[:len(r.prompt) - r.n_committed]
                        if r.n_committed else r.prompt)
                resumes.append(Request(
                    uid=r.uid,
                    prompt=np.asarray(list(orig) + seq.tokens, np.int32),
                    max_new_tokens=r.max_new_tokens, eos_id=r.eos_id,
                    arrival=r.arrival, deadline=r.deadline,
                    n_committed=len(seq.tokens)))
            self.active.clear()
            self._evict_pending.clear()
            spec = mgr.spec_config()
            self._configure(mgr.cfg_tgt, mgr.grown_params, spec)
            if spec is not None and any(self._invalid_reason(r) is not None
                                        for r in resumes):
                # enabling the post-swap draft raised the shared-arena page
                # need (a request reserves max(need) across pools) above an
                # explicit --pages budget for an in-flight resume; zero-drop
                # beats free speculation, so swap without the draft
                mgr.disable_spec("draft page need exceeds the shared arena "
                                 "for an in-flight request")
                self._configure(mgr.cfg_tgt, mgr.grown_params, None)
            # queued (never-admitted) requests were validated under the OLD
            # geometry; re-validate so one that became unservable cannot
            # livelock admission.  Mid-flight resumes skip this by design.
            keep: collections.deque = collections.deque()
            for r in self.waiting:
                why = self._invalid_reason(r)
                if why is None:
                    keep.append(r)
                else:
                    self._seen_uids.discard(r.uid)
                    self._reject(r.uid,
                                 f"request {r.uid}: {why} "
                                 "(post-upgrade geometry)")
            keep.extendleft(reversed(resumes))
            self.waiting = keep
            self.n_upgrades += 1
            self.upgrade_state = "swapped"
            held, self._held_for_upgrade = self._held_for_upgrade, []
            for r in held:
                self._submit_checked(r)
            if self.journal is not None:
                # last-submit-wins resume records: a crash right after the
                # swap replays exactly these prompt‖committed requests
                for r in resumes:
                    self.journal.record_submit(r)
                self._flush_journal()
            pause_ms = (time.perf_counter() - t0) * 1e3
            pages_reprefill = 0
            if self._alloc is not None:
                pages_reprefill = sum(
                    max(paged_lib.pages_needed(len(r.prompt),
                                               r.max_new_tokens, m)
                        for m in self._metas if m is not None)
                    for r in resumes)
            mgr._swapped(self, pause_ms, resumes,
                         pages_resident=pages_at_swap,
                         pages_reprefilled=pages_reprefill)

    # ---------------------------------------------------------------- faults
    def _inject(self, f):
        """Fire one FaultPlan record.  Called from ``_dispatch`` only
        when a plan is attached — the default path never gets here."""
        self.n_faults_injected += 1
        if f.kind == "crash":
            # kill -9 at a step boundary: journaled state survives,
            # unread in-flight blocks do not
            if self.journal is not None:
                self._flush_journal()
            raise faults_lib.EngineKilled(
                f"injected crash at engine step {self._fault_step}")
        if f.kind in ("slow", "hang"):
            time.sleep(f.duration)
            return
        if f.kind == "oom":
            self._oom_waves += max(int(f.duration), 1)
            return
        if f.kind == "malformed":
            # a hostile request arriving mid-trace; the unified rejection
            # path must absorb it without disturbing in-flight work
            self.submit(Request(uid=-(1000 + self._fault_step),
                                prompt=np.zeros((0,), np.int32),
                                max_new_tokens=1))
            return
        # kind == "nan": corrupt a live slot's cache bytes on device —
        # the NaN flows through real attention into real logits, where
        # the in-scan sentinel must catch it
        slot = f.slot if f.slot in self.active else (
            min(self.active) if self.active else None)
        if slot is None or f.pool >= len(self._pools):
            return
        if self._poison_jit is None:
            self._poison_jit = jax.jit(faults_lib.poison_pool,
                                       donate_argnums=(0,))
        meta = self._metas[f.pool]
        # paged pools poison the slot's first page (attention reads it
        # every step); the page id also guards dense engines, where it
        # is simply unused.  The shared id space means the slot's first
        # page is a row of EVERY paged pool, so the same id is right for
        # whichever pool the fault targets.
        pid = self._slot_pages[slot][0] if meta is not None else 0
        pools = list(self._pools)
        pools[f.pool] = self._poison_jit(pools[f.pool], jnp.int32(slot),
                                         jnp.int32(pid))
        self._pools = tuple(pools)

    # ------------------------------------------------------------- step loop
    def _dispatch(self):
        """Launch one on-device macro step (K decode steps — or K whole
        speculative draft→verify→commit blocks — with no sync)."""
        with tracing.span("dispatch"):
            if self.faults is not None:
                self._fault_step += 1
                for f in self.faults.due(self._fault_step):
                    self._inject(f)
            tokens, positions, remaining, eos_ids, done, keys = self._state
            stats = None
            dbad = None
            moe = None
            if self.speculative is not None and not self._spec_fallback:
                (block, valid, poison, dbad, tokens, positions, remaining,
                 done, pool_t, pool_d, keys, n_prop, n_acc) = self._loop(
                    self.params, self.speculative.params, tokens, positions,
                    remaining, eos_ids, done, self._pools[0], self._pools[1],
                    keys)
                self._pools = (pool_t, pool_d)
                stats = (n_prop, n_acc)
            else:
                # _fb_loop: a speculative engine whose draft misbehaved keeps
                # serving through the plain macro loop on its TARGET pool
                loop = self._fb_loop if self._spec_fallback else self._loop
                if self.sampling is not None:
                    (block, valid, poison, moe, tokens, positions, remaining,
                     done, pool, keys) = loop(self.params, tokens, positions,
                                              remaining, eos_ids, done,
                                              self._pools[0], keys)
                else:
                    (block, valid, poison, moe, tokens, positions, remaining,
                     done, pool) = loop(self.params, tokens, positions,
                                        remaining, eos_ids, done,
                                        self._pools[0])
                self._pools = (pool,) + self._pools[1:]
            self._state = (tokens, positions, remaining, eos_ids, done, keys)
            self.n_decode_dispatches += 1
            live = [(slot, seq.req.uid) for slot, seq in self.active.items()]
            self._inflight.append((block, valid, poison, dbad, live, stats,
                                   moe))

    def _process(self, item):
        """Block on one macro step's token block (the single host sync per
        dispatch) and advance the host-side sequence records.  The
        NaN/Inf sentinels and the journal's committed-token deltas ride
        this same readback — fault tolerance adds no host sync."""
        with tracing.span("readback"):
            block, valid, poison, dbad, live, stats, moe = item
            with tracing.span("readback.wait"):
                block, valid, poison, dbad, stats, moe = jax.device_get(
                    (block, valid, poison, dbad, stats, moe))
            self.n_host_syncs += 1
            if moe is not None:
                # expert-layer counts ride the same readback
                self.n_blocks_read += 1
                self.n_moe_pairs += int(moe[0])
                self.n_moe_experts_hit += int(moe[1])
            if stats is not None:
                # acceptance telemetry rides the same readback — no extra
                # sync
                self.n_spec_proposed += int(stats[0])
                self.n_spec_accepted += int(stats[1])
            for slot, uid in live:
                seq = self.active.get(slot)
                if seq is None or seq.req.uid != uid:
                    # the slot was retired (and possibly re-admitted) while
                    # this block was in flight; its rows were device-done,
                    # so the valid mask is all False for it anyway
                    continue
                vm = valid[:, slot]
                nv = int(vm.sum())
                if nv:
                    new = [int(t) for t in block[:, slot][vm]]
                    seq.pos += nv
                    seq.tokens.extend(new)
                    self.n_tokens += nv
                    if self.journal is not None:
                        self.journal.record_tokens(uid, new)
                    self._finish_if_done(seq, seq.tokens[-1])
                if bool(poison[slot]) and self.active.get(slot) is seq:
                    # the row froze itself at the bad step (nothing from it
                    # was committed); evict it with an explicit outcome
                    self._quarantine(seq)
            if dbad is not None and bool(dbad) and not self._spec_fallback:
                # degradation ladder: draft logits went non-finite — keep
                # serving every request through the plain target-only loop
                self._spec_fallback = True
                self.n_spec_fallbacks += 1
            if self.journal is not None:
                self._flush_journal()

    def step(self, now: Optional[float] = None):
        """One synchronous engine iteration: expire, evict, admit arrived
        requests into free slots, run one macro step, and read it back."""
        with tracing.span("step", active=len(self.active),
                          waiting=len(self.waiting)):
            if self.upgrade is not None:
                self.upgrade.poll(self)
            self._expire(now)
            self._flush_evictions()
            self._admit_batch(now)
            if not self.active and not self._inflight:
                return
            if self.active:
                self._dispatch()
            while self._inflight:
                self._process(self._inflight.popleft())

    def run(self, requests=None, *, realtime: bool = False,
            pipeline: bool = True):
        """Serve until every submitted request finishes.

        ``realtime=True`` replays ``Request.arrival`` offsets against the
        wall clock (benchmark traces); otherwise arrivals are ignored and
        admission is purely slot-limited (FIFO or spf by ``policy``).

        ``pipeline=True`` double-buffers readback: macro-block N+1 is
        dispatched (device-side dataflow only) before the host blocks on
        block N's tokens, so the device never idles on readback.
        Admissions chain onto the latest dispatched state, which defers a
        queued request by at most one extra block.  ``pipeline=False``
        syncs after every block (the per-token engine of PR 1 when k=1).

        Returns {uid: np.ndarray of generated tokens} for the requests that
        finished during THIS call (``self.finished`` keeps the full
        history across calls).
        """
        already = set(self.finished)
        for r in requests or ():
            self.submit(r)
        t0 = time.monotonic()

        def wall_now():
            return time.monotonic() - t0 if realtime else None

        if not pipeline:
            while self.waiting or self.active or self._inflight:
                now = wall_now()
                if realtime and not self.active and self.waiting:
                    nxt = min(r.arrival for r in self.waiting)
                    if nxt > now:
                        with tracing.span("sleep"):
                            time.sleep(nxt - now)
                        now = wall_now()
                self.step(now=now)
        else:
            while self.waiting or self.active or self._inflight:
                with tracing.span("step", active=len(self.active),
                                  waiting=len(self.waiting)):
                    now = wall_now()
                    if (realtime and not self.active
                            and not self._inflight and self.waiting):
                        nxt = min(r.arrival for r in self.waiting)
                        if nxt > now:
                            with tracing.span("sleep"):
                                time.sleep(nxt - now)
                            now = wall_now()
                    if self.upgrade is not None:
                        self.upgrade.poll(self)
                    self._expire(now)
                    self._flush_evictions()
                    self._admit_batch(now)
                    if self.active:
                        self._dispatch()
                    # block on the OLDEST in-flight block only once a
                    # newer one is already dispatched (or nothing is left
                    # to dispatch)
                    if len(self._inflight) >= (2 if self.active else 1):
                        self._process(self._inflight.popleft())
        self._flush_evictions()
        return {uid: toks for uid, toks in self.finished.items()
                if uid not in already}

    def drain(self):
        """Return and clear all accumulated results and latency history,
        and roll the telemetry window.

        A long-lived server must call this periodically — ``finished``,
        ``retired``, ``rejected``, and the uid-dedup set otherwise grow
        with every request ever served, and the window counters (token /
        sync / acceptance / prefix tallies) otherwise accumulate forever,
        silently turning every derived rate into a since-boot average.
        The counters snapshot into ``self.lifetime`` and reset to zero;
        ``lifetime_totals()`` keeps the since-boot view.  Drained uids
        become submittable again.
        """
        out = self.finished
        self.finished = {}
        self.retired = []
        self.rejected = {}
        self.outcomes = {}
        self._seen_uids.difference_update(out)
        for uid in out:
            self._t_submit.pop(uid, None)
        for c in _WINDOW_COUNTERS:
            self.lifetime[c] += getattr(self, c)
            setattr(self, c, 0)
        return out
