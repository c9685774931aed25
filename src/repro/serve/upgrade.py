"""Live-growth serving: hot-swap Mango-grown weights into a running
engine with zero dropped requests.

The paper's core property — multi-linear growth is (approximately)
function-preserving: the grown target computes the source's function at
swap time — turns a model upgrade into a *serving event* instead of a
redeploy.  :class:`UpgradeManager` drives it end to end:

    serving ──start()──▶ growing ──▶ ready ──poll()──▶ relayout ──▶ swapped
                            │                                         │
                            └────────────▶ failed (engine keeps serving
                                                   the source model)

* **growing** — ``core/grow.py: grow_from_source`` runs Mango (or any
  registered growth method) on the engine's CURRENT weights, optionally
  on a background thread while the engine keeps serving the source.
* **ready** — the grown fn set is pre-warmed: a scratch engine with the
  target geometry compiles every jitted function the swap will flip to
  (``_jitted_engine_fns`` is process-wide and keyed on frozen configs,
  so the live engine hits the warm cache).  The swap pause is then one
  quiesce, not a compile.
* **relayout → swapped** — at the next block-readback boundary whose
  lifetime dispatch count has reached ``upgrade_at``, the engine
  quiesces, converts every mid-flight sequence into a journal-style
  resume request (original prompt ‖ committed run), rebuilds pools /
  shardings / fns for the grown geometry, and re-admits the resumes
  through the ordinary admission path — token-exact continuations, zero
  drops (``engine._apply_upgrade``).
* **draft-after-swap** — the old source is, by construction, a
  distribution-matched draft for its own grown target; if the
  ``spec_pair_supported`` probe passes, the swap flips the engine into
  speculative mode with the source as draft, so the upgrade ends with
  spec serving enabled for free.

Everything that can fail is validated eagerly in ``__init__`` with a
named :class:`UpgradeError` — family mismatch, unservable target,
position range, vocabulary change, mesh divisibility — so a doomed
upgrade dies before a single growth FLOP, and never mid-swap.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

import jax
import numpy as np

from repro.models import get_family, serve_supported
from repro.serve import tracing
from repro.serve.engine import ContinuousBatchingEngine, Request
from repro.serve.speculative import SpeculativeConfig, spec_pair_supported

UPGRADE_STATES = ("serving", "growing", "ready", "relayout", "swapped",
                  "failed")


class UpgradeError(RuntimeError):
    """A live upgrade that cannot work, detected before it starts."""


def probe_token_agreement(cfg_src, params_src, cfg_tgt, params_tgt,
                          prompts, *, gen: int = 8) -> float:
    """Fraction of greedy tokens on which source and target agree over a
    probe batch — the measurable form of the paper's function-preservation
    claim (1.0 ⇔ the grown target continues every greedy sequence
    exactly where the source would)."""
    from repro.launch.serve import generate
    prompts = np.asarray(prompts, np.int32)
    a = np.asarray(generate(cfg_src, params_src, prompts,
                            max_new_tokens=gen))
    b = np.asarray(generate(cfg_tgt, params_tgt, prompts,
                            max_new_tokens=gen))
    return float((a == b).mean())


class UpgradeManager:
    """Grow ``engine.cfg`` into ``cfg_tgt`` and hot-swap it in.

    Parameters
    ----------
    engine : the live :class:`ContinuousBatchingEngine` (attaches as
        ``engine.upgrade``; the engine polls at block boundaries).
    cfg_tgt : target model config (same family; Mango maps within one).
    method / rank / grow_steps / data_iter : forwarded to
        ``core/grow.py: grow_from_source`` (``grow_steps > 0`` trains the
        operator on ``data_iter`` first — Eq. 7).
    grow_noise : operator-init noise scale.  Defaults to ``0.0`` — the
        untrained structured init then coincides with the Net2Net
        expansion, the most function-preserving init available (depth
        growth keeps it approximate; measure with
        :func:`probe_token_agreement`).  Pass ``None`` for the trainer's
        default (0.01) when growth is followed by operator training.
    grown_params : skip growth entirely and swap these in (precomputed
        growth, or a checkpoint-restored target).
    speculate_after : ``"auto"`` (default) enables draft-after-swap when
        the pair probe passes and records the reason when it does not;
        ``True`` makes a failed probe an :class:`UpgradeError`;
        ``False`` disables it.
    spec_d : speculation depth for the post-swap pair.
    upgrade_at : minimum LIFETIME decode dispatches before the swap may
        land — "mid-trace upgrade" in the scenario harness.
    prewarm : compile the grown fn set before the flip (recommended; off
        only for tests that want the cold-swap path).
    probe_fp : measure :func:`probe_token_agreement` on synthetic prompts
        after growth (recorded as ``fp_token_agreement``).
    """

    def __init__(self, engine: ContinuousBatchingEngine, cfg_tgt, *,
                 method: str = "mango", rank: int = 1,
                 grow_steps: int = 0, data_iter=None, grow_noise=0.0,
                 grown_params=None, speculate_after="auto",
                 spec_d: int = 4, upgrade_at: int = 0,
                 prewarm: bool = True, probe_fp: bool = False,
                 seed: int = 0):
        if engine.upgrade is not None and engine.upgrade.state not in (
                "swapped", "failed"):
            raise UpgradeError(
                "engine already has an upgrade in flight "
                f"(state {engine.upgrade.state!r})")
        cfg_src = engine.cfg
        # the target inherits the engine's decode-kernel switch so the
        # pre-warmed fn-set key matches what _configure will build
        cfg_tgt = cfg_tgt.replace(decode_kernel=engine.decode_kernel)
        if cfg_src.family != cfg_tgt.family:
            raise UpgradeError(
                f"growth operators map within one family: engine serves "
                f"{cfg_src.name!r} ({cfg_src.family}) but the target is "
                f"{cfg_tgt.name!r} ({cfg_tgt.family})")
        if cfg_src.vocab_size != cfg_tgt.vocab_size:
            raise UpgradeError(
                f"live upgrade needs an unchanged vocabulary (committed "
                f"tokens must stay valid): {cfg_src.vocab_size} -> "
                f"{cfg_tgt.vocab_size}")
        ok, why = serve_supported(cfg_tgt)
        if not ok:
            raise UpgradeError(
                f"target {cfg_tgt.name!r} is not servable: {why}")
        limit = cfg_tgt.max_seq_len
        if cfg_tgt.learned_pos:
            limit = min(limit, cfg_tgt.learned_pos)
        if engine.max_len > limit:
            raise UpgradeError(
                f"engine max_len {engine.max_len} exceeds target "
                f"{cfg_tgt.name!r} position range {limit}")
        if engine.mesh_plan is not None:
            from repro.distributed import serve_sharding
            try:
                serve_sharding.validate_serve_mesh(
                    engine.mesh_plan.shape, cfg_tgt, engine.capacity)
            except ValueError as e:
                raise UpgradeError(
                    f"target {cfg_tgt.name!r} does not fit the engine's "
                    f"{engine.mesh_shape} mesh: {e}") from e
        self._spec_enabled = False
        self.spec_reason: Optional[str] = None
        if speculate_after not in ("auto", True, False):
            raise UpgradeError(
                f"speculate_after must be 'auto', True or False "
                f"(got {speculate_after!r})")
        if speculate_after in ("auto", True):
            ok, why = spec_pair_supported(cfg_tgt, cfg_src, spec_d,
                                          engine.max_len)
            if ok:
                self._spec_enabled = True
            elif speculate_after is True:
                raise UpgradeError(
                    f"draft-after-swap pair {cfg_src.name!r} -> "
                    f"{cfg_tgt.name!r} is unsupported: {why}")
            else:
                self.spec_reason = why

        self.engine = engine
        self.cfg_src = cfg_src
        self.cfg_tgt = cfg_tgt
        # the draft is the source AS SERVED NOW: weights captured before
        # growth, so the post-swap draft is bit-identical to what every
        # mid-flight sequence was decoded with
        self.params_src = engine.params
        self.method = method
        self.rank = rank
        self.grow_steps = grow_steps
        self.data_iter = data_iter
        self.grow_noise = grow_noise
        self.grown_params = grown_params
        self.spec_d = spec_d
        self.upgrade_at = upgrade_at
        self.prewarm = prewarm
        self.probe_fp = probe_fp
        self.seed = seed

        self.state = "serving"
        self.history: List[tuple] = [("serving", time.monotonic())]
        self.error: Optional[BaseException] = None
        self.fp_token_agreement: Optional[float] = None
        self.grow_seconds: Optional[float] = None
        self.pause_ms: Optional[float] = None
        self.resumed: Optional[int] = None
        # page-residency delta of the swap (paged engines; zeros when
        # dense): pages_resident_at_swap were live at quiesce and are all
        # invalidated (cache bytes are activations of the pre-growth
        # function), pages_carried is therefore structurally 0, and
        # pages_reprefilled is the page bill the resume wave pays to
        # rebuild state under the grown model — the measurable cost of
        # the zero-drop guarantee.
        self.pages_resident_at_swap: Optional[int] = None
        self.pages_carried: Optional[int] = None
        self.pages_reprefilled: Optional[int] = None
        self.tokens_at_swap: Optional[int] = None
        self.t_swap: Optional[float] = None
        self._ready = threading.Event()
        self._thread: Optional[threading.Thread] = None
        engine.upgrade = self

    # ---------------------------------------------------------------- states
    def _set_state(self, state: str) -> None:
        assert state in UPGRADE_STATES, state
        self.state = state
        self.history.append((state, time.monotonic()))

    def spec_config(self) -> Optional[SpeculativeConfig]:
        """The post-swap draft pair (None when draft-after-swap is off)."""
        if not self._spec_enabled:
            return None
        return SpeculativeConfig(self.cfg_src, self.params_src,
                                 d=self.spec_d)

    def disable_spec(self, why: str) -> None:
        """Called by the swap when enabling the draft would violate the
        zero-drop guarantee (e.g. the draft's page need pushing a
        resume's shared-arena reservation past an explicit --pages)."""
        self._spec_enabled = False
        self.spec_reason = why

    # ----------------------------------------------------------------- growth
    def start(self, background: bool = True) -> "UpgradeManager":
        """Kick off growth.  ``background=True`` grows on a thread while
        the engine keeps serving the source (the production path);
        ``background=False`` blocks until ready (deterministic tests and
        pre-grown swaps).  A growth failure moves to ``failed`` and the
        engine simply keeps serving — a bad upgrade must never take down
        live traffic."""
        if self.state != "serving":
            raise UpgradeError(f"start() in state {self.state!r}")
        self._set_state("growing")
        if background:
            self._thread = threading.Thread(target=self._grow, daemon=True)
            self._thread.start()
        else:
            self._grow()
            if self.error is not None:
                raise self.error
        return self

    def _grow(self) -> None:
        t0 = time.monotonic()
        try:
            if self.grown_params is None:
                from repro.core.grow import grow_from_source
                data_iter = self.data_iter
                if self.grow_steps and data_iter is None:
                    from repro.data.synthetic import lm_data_iter
                    data_iter = lm_data_iter(self.cfg_tgt.vocab_size, 4, 32,
                                             seed=self.seed + 1)
                with tracing.span("upgrade.grow", method=self.method,
                                  steps=self.grow_steps):
                    self.grown_params = grow_from_source(
                        self.cfg_src, self.cfg_tgt, method=self.method,
                        rank=self.rank, steps=self.grow_steps,
                        data_iter=data_iter, params_src=self.params_src,
                        rng=jax.random.PRNGKey(self.seed),
                        noise=self.grow_noise, log_fn=lambda *a, **k: None)
            self.grow_seconds = time.monotonic() - t0
            if self.probe_fp:
                rng = np.random.default_rng(self.seed)
                prompts = rng.integers(
                    0, self.cfg_tgt.vocab_size, size=(4, 8), dtype=np.int32)
                self.fp_token_agreement = probe_token_agreement(
                    self.cfg_src, self.params_src, self.cfg_tgt,
                    self.grown_params, prompts)
            if self.prewarm:
                with tracing.span("upgrade.prewarm"):
                    self._prewarm()
            self._set_state("ready")
            self._ready.set()
        except BaseException as e:  # noqa: BLE001 — surfaced via .error
            self.error = e
            self._set_state("failed")
            self._ready.set()

    def _prewarm(self) -> None:
        """Compile the grown fn set BEFORE the flip.  A scratch engine
        with the exact post-swap geometry drives every jitted function
        through every (bucket × pow2-group) admission shape and the
        macro loop; ``_jitted_engine_fns`` is lru-cached on frozen
        configs + pool metas + mesh plan, so the live engine's post-swap
        calls hit this warm cache and the swap pause contains no
        compile."""
        eng = self.engine
        scratch = ContinuousBatchingEngine(
            self.cfg_tgt, self.grown_params, capacity=eng.capacity,
            max_len=eng.max_len, prefill_bucket=eng.prefill_bucket,
            k=eng.k, policy=eng.policy, pool=eng._pool_arg,
            pages=eng.pages_arg, sampling=eng.sampling,
            speculative=self.spec_config(), mesh=eng._mesh_arg)
        buckets = sorted({scratch._bucketed(n)
                          for n in range(1, eng.max_len - 1)})
        # group counts whose pow2 padding covers every admission-wave
        # size the swap's resume wave can produce (a wave of `capacity`
        # resumes pads to _pow2(capacity))
        counts = sorted({min(1 << i, eng.capacity)
                         for i in range(eng.capacity.bit_length() + 1)})
        uid = -1_000_000  # scratch uids can never collide with traffic
        for n in counts:
            for b in buckets:
                plen = max(1, min(b, eng.max_len - 2))
                reqs = [Request(uid=uid - i,
                                prompt=np.zeros((plen,), np.int32),
                                max_new_tokens=2) for i in range(n)]
                uid -= n
                scratch.run(reqs)

    # ------------------------------------------------------------------ swap
    def poll(self, engine: Optional[ContinuousBatchingEngine] = None
             ) -> bool:
        """Called by the engine at every block boundary.  Returns True
        when it performed the swap."""
        engine = engine or self.engine
        if self.state != "ready":
            return False
        if engine.lifetime_totals()["n_decode_dispatches"] < self.upgrade_at:
            return False
        self._set_state("relayout")
        engine._apply_upgrade(self)
        return True

    def _swapped(self, engine: ContinuousBatchingEngine, pause_ms: float,
                 resumes, *, pages_resident: int = 0,
                 pages_reprefilled: int = 0) -> None:
        """Engine callback at the end of ``_apply_upgrade``."""
        self.pause_ms = pause_ms
        self.resumed = len(resumes)
        self.resumed_requests = list(resumes)
        self.pages_resident_at_swap = int(pages_resident)
        self.pages_carried = 0
        self.pages_reprefilled = int(pages_reprefilled)
        self.tokens_at_swap = engine.lifetime_totals()["n_tokens"]
        self.t_swap = time.monotonic()
        self._set_state("swapped")

    def wait(self) -> "UpgradeManager":
        """Join a background growth; re-raise its failure here."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self.error is not None:
            raise self.error
        return self
