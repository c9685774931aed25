"""Speculative decoding for the continuous-batching engine.

The paper grows every target weight as a (multi-)linear function of the
pretrained source weights — which makes the small source model an
unusually well-matched *draft* for speculative decoding of its grown
target.  This module exploits that pair at serve time:

  * the DRAFT (source config, or the target's seed checkpoint before
    growth) proposes ``d`` tokens per slot by running its own slot-decode
    recurrence on a scratch continuation of the draft pool;
  * the TARGET verifies all ``d`` (+ the carried token) in ONE batched
    chunk forward — the family's ``verify_step_slots`` hook — yielding
    its next-token choice after every chunk prefix;
  * the longest accepted prefix is committed per slot through
    ``commit_slots``: KV layouts scatter only the accepted positions
    (rollback = "never wrote it"), recurrent layouts gather the stacked
    per-step state at the accepted boundary (``freeze_rows``-style
    snapshot/restore).  Paged pools commit the same way through block
    tables — ring layouts via the paged ``spec_ring_restore`` twin
    (``models/common.py``), so griffin + speculative serves paged, and a
    draft/target pair draws from ONE shared page arena (per-engine
    refcount namespaces; see ``serve/paged.py``);
  * per-slot eos / budget stopping is folded into the acceptance mask, so
    a slot that finishes mid-chunk freezes exactly there — the same
    contract as the macro decode loop.

``make_speculative_loop(cfg_t, cfg_d, d, k)`` wraps ``k`` whole
draft→verify→commit blocks under one ``lax.scan``, so a dispatch emits up
to ``k * (d + 1)`` tokens per slot with a single host sync — PR 2's
macro-step structure, now emitting several tokens per target step.

Greedy speculative decode is token-exact versus non-speculative
``generate()``: every emitted token IS the target's argmax after its
committed prefix — acceptance only decides how many of them one block
emits.  With sampling, draft proposals go through classic rejection
sampling (accept ``x ~ q`` with prob ``min(1, p(x)/q(x))``, resample
rejections from ``normalize(max(p - q, 0))``), which preserves the
target's sampling distribution; with draft == target it accepts
everything.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import get_family, spec_decode_supported
from repro.serve import sampling as sampling_lib


@dataclasses.dataclass
class SpeculativeConfig:
    """Draft-side configuration for a speculative engine.

    ``cfg``/``params`` are the draft model (typically the pretrained
    source the target was grown from); ``d`` is the speculation depth:
    draft proposals per block, so a block commits between 1 and ``d + 1``
    tokens per live slot.
    """
    cfg: Any
    params: Any
    d: int = 4


def spec_pair_supported(cfg_target, cfg_draft, d: int = 4,
                        max_len: Optional[int] = None):
    """Capability probe for a speculative (target, draft) PAIR.

    Returns (ok, detail).  ``detail`` reports per-mode servability for
    BOTH models — a pair is speculatively servable only when each side
    passes its own slot-decode probe, implements the chunk-verify hooks,
    and the two share a vocabulary; ring-buffer layouts additionally need
    the ``d + 1``-token verify chunk to fit their ring.
    """
    if d < 1:
        return False, f"speculation depth d must be >= 1 (got {d})"
    ok_t, det_t = spec_decode_supported(cfg_target)
    ok_d, det_d = spec_decode_supported(cfg_draft)
    per_mode = (f"target {cfg_target.name!r}: "
                f"{'ok — ' if ok_t else 'NOT SERVABLE — '}{det_t}; "
                f"draft {cfg_draft.name!r}: "
                f"{'ok — ' if ok_d else 'NOT SERVABLE — '}{det_d}")
    if not (ok_t and ok_d):
        return False, per_mode
    if cfg_target.vocab_size != cfg_draft.vocab_size:
        return False, (f"draft/target vocabularies differ "
                       f"({cfg_draft.vocab_size} vs "
                       f"{cfg_target.vocab_size}) — draft proposals would "
                       "not index the target distribution")
    for role, cfg in (("target", cfg_target), ("draft", cfg_draft)):
        ring = min(cfg.window, max_len) if (cfg.window and max_len) \
            else cfg.window
        if ring and d + 1 > ring:
            return False, (f"{role} {cfg.name!r}: verify chunk d+1={d + 1} "
                           f"overruns its ring-buffer window ({ring}) — "
                           "a chunk position would wrap onto a committed "
                           "slot")
    return True, per_mode


def make_draft_prefill(cfg_d):
    """Admission prefill for the DRAFT pool: same bucket-padded prompt
    batch as the target's admission, logits discarded — only the per-row
    prompt state matters (the first generated token is the target's)."""
    fam = get_family(cfg_d)

    def prefill_fn(params_d, tokens, plens, cache):
        _, cache = fam.prefill_last(
            params_d, {"tokens": tokens, "plens": plens}, cfg_d, cache)
        return cache

    return prefill_fn


def make_speculative_loop(cfg_t, cfg_d, d: int, k: int, sampling=None):
    """K speculative blocks under one ``lax.scan`` — the engine's
    macro-step for speculative mode.

    fn(params_t, params_d, tokens (B,), positions (B,), remaining (B,),
       eos_ids (B,), done (B,), pool_t, pool_d, keys (B,2)) ->
        (block (K*(d+1), B) int32, valid (K*(d+1), B) bool,
         poison (B,) bool, draft_bad () bool,
         tokens, positions, remaining, done, pool_t, pool_d, keys,
         n_proposed (), n_accepted ())

    Block semantics mirror ``make_slot_decode_loop``: ``valid[i, b]``
    marks really-committed tokens, rows emit eos as valid then go quiet,
    finished rows are exact no-ops.  ``n_proposed`` / ``n_accepted``
    count draft tokens offered/accepted across the whole dispatch — the
    acceptance-rate telemetry rides the block readback, costing no extra
    host sync.

    NaN/Inf sentinels ride the same readback.  ``poison[b]`` flags a row
    whose TARGET verify logits came back non-finite (the block commits
    nothing for the row — ``n_feed`` forces 0, so both pools stay at the
    row's pre-block state — and the row freezes via the done-mask; the
    engine quarantine-evicts it).  With sampling, a row whose DRAFT
    logits were non-finite is poisoned too: its rejection-sampling draw
    would no longer follow the target distribution.  Under greedy decode
    a broken draft cannot corrupt output (every emitted token is the
    target's own argmax — bad proposals are merely rejected), so greedy
    rows survive a draft fault; either way the scalar ``draft_bad``
    reports any non-finite draft logits across the dispatch, and the
    engine uses it to drop to plain macro decode (degradation ladder).
    """
    fam_t, fam_d = get_family(cfg_t), get_family(cfg_d)
    greedy = sampling_lib.is_greedy(sampling)
    S = d + 1

    def one_block(tokens, positions, remaining, eos_ids, done, pool_t,
                  pool_d, keys, params_t, params_d):
        B = tokens.shape[0]
        live0 = ~done
        # effective proposals: drafts the budget could even use — a row
        # owing R more tokens can accept at most min(d, R - 1) drafts
        # (the block's last output is always the target's own token), so
        # budget clipping must not read as draft rejection in the
        # acceptance telemetry
        n_prop_rows = jnp.where(live0,
                                jnp.minimum(d, jnp.maximum(remaining - 1,
                                                           0)), 0)

        if greedy:
            def draft_body(carry, j):
                tok, cache, dbad = carry
                logits, cache = fam_d.decode_step_slots(
                    params_d, tok, positions + j, cache, cfg_d, done=done)
                dbad = dbad | (~done & ~jnp.all(
                    jnp.isfinite(logits.astype(jnp.float32)), -1))
                nxt = jnp.where(done, tok,
                                jnp.argmax(logits, -1).astype(jnp.int32))
                return (nxt, cache, dbad), nxt

            # the scratch draft continuation: proposals advance a copy of
            # the draft pool; the real pool only moves at commit time
            (_, _, dbad), drafts = jax.lax.scan(
                draft_body, (tokens, pool_d, jnp.zeros((B,), bool)),
                jnp.arange(d))
            chunk = jnp.concatenate([tokens[None], drafts], 0).T  # (B, S)
            logits_t, pend_t = fam_t.verify_step_slots(
                params_t, chunk, positions, pool_t, cfg_t, done=done)
            tbad = ~jnp.all(jnp.isfinite(logits_t.astype(jnp.float32)),
                            axis=(1, 2))
            # greedy: a broken draft only wastes proposals, it cannot
            # change the emitted tokens — poison on target faults alone
            bad = live0 & tbad
            out_tokens = jnp.argmax(logits_t, -1).astype(jnp.int32)
            # greedy acceptance: proposal j survives iff it IS the
            # target's argmax after the (already accepted) prefix — so
            # every emitted token is the target's own token and
            # acceptance only sets how many are emitted per block
            match = chunk[:, 1:] == out_tokens[:, :-1]
        else:
            keys_new, kblock = sampling_lib.next_keys(keys)
            keys = jnp.where(live0[:, None], keys_new, keys)

            def subkey(c):
                return jax.vmap(lambda kk: jax.random.fold_in(kk, c))(kblock)

            def draft_body(carry, j):
                tok, cache, dbad = carry
                logits, cache = fam_d.decode_step_slots(
                    params_d, tok, positions + j, cache, cfg_d, done=done)
                dbad = dbad | (~done & ~jnp.all(
                    jnp.isfinite(logits.astype(jnp.float32)), -1))
                qj = sampling_lib.filtered_probs(logits, sampling)
                kj = jax.vmap(jax.random.fold_in)(kblock, jnp.full((B,), j))
                nxt = jnp.where(done, tok,
                                sampling_lib.sample_probs(qj, kj))
                return (nxt, cache, dbad), (nxt, qj)

            (_, _, dbad), (drafts, qs) = jax.lax.scan(
                draft_body, (tokens, pool_d, jnp.zeros((B,), bool)),
                jnp.arange(d))
            chunk = jnp.concatenate([tokens[None], drafts], 0).T
            logits_t, pend_t = fam_t.verify_step_slots(
                params_t, chunk, positions, pool_t, cfg_t, done=done)
            tbad = ~jnp.all(jnp.isfinite(logits_t.astype(jnp.float32)),
                            axis=(1, 2))
            # sampled: a non-finite draft distribution breaks rejection
            # sampling's target-distribution guarantee — poison the row
            bad = live0 & (tbad | dbad)
            V = logits_t.shape[-1]
            p = sampling_lib.filtered_probs(
                logits_t.reshape(B * S, V), sampling).reshape(B, S, V)
            qs = jnp.swapaxes(qs, 0, 1)  # (B, d, V)
            x = chunk[:, 1:]  # (B, d) draft proposals
            p_x = jnp.take_along_axis(p[:, :-1], x[..., None], -1)[..., 0]
            q_x = jnp.take_along_axis(qs, x[..., None], -1)[..., 0]
            u = jax.vmap(lambda kk: jax.random.uniform(kk, (d,)))(subkey(d))
            match = u < jnp.minimum(1.0, p_x / jnp.maximum(q_x, 1e-38))
            # replacements: residual distribution at each rejection
            # point; the all-accepted bonus draws from the target's own
            # next distribution
            repl_dists = jnp.concatenate(
                [sampling_lib.residual_probs(p[:, :-1], qs), p[:, -1:]], 1)
            logp = jnp.where(repl_dists > 0,
                             jnp.log(jnp.maximum(repl_dists, 1e-38)),
                             sampling_lib.NEG_INF)
            repl = jax.vmap(
                lambda kk, lp: jax.random.categorical(kk, lp, axis=-1))(
                    subkey(d + 1), logp).astype(jnp.int32)  # (B, S)
            acc_tok = jnp.concatenate(
                [x, jnp.zeros((B, 1), jnp.int32)], 1)
            acc_mask = jnp.concatenate(
                [match, jnp.zeros((B, 1), bool)], 1)
            out_tokens = jnp.where(acc_mask, acc_tok, repl)

        # ---- acceptance chain + per-slot stopping (shared) -----------
        # output j (1-based) is committed iff the row is live, proposals
        # 1..j-1 were all accepted, the budget still owes >= j tokens,
        # and no earlier output in this block was the row's eos
        acc_ok = jnp.concatenate(
            [jnp.ones((B, 1), bool), jnp.cumsum(~match, 1) == 0], 1)
        steps = jnp.arange(1, S + 1, dtype=remaining.dtype)
        budget_ok = steps[None] <= remaining[:, None]
        is_eos = out_tokens == eos_ids[:, None]
        no_eos_before = (jnp.cumsum(is_eos, 1) - is_eos) == 0
        # a poisoned row commits NOTHING this block (n_out = 0, so its
        # state and both pools stay at the pre-block snapshot) and
        # freezes via the done-mask — the engine quarantine-evicts it
        alive = live0 & ~bad
        valid = alive[:, None] & acc_ok & budget_ok & no_eos_before
        n_out = valid.sum(1).astype(jnp.int32)
        last_idx = jnp.maximum(n_out - 1, 0)
        last_tok = jnp.take_along_axis(out_tokens, last_idx[:, None],
                                       1)[:, 0]
        tokens = jnp.where(alive, last_tok, tokens)
        remaining = jnp.where(alive, remaining - n_out, remaining)
        fired_eos = jnp.take_along_axis(is_eos, last_idx[:, None], 1)[:, 0]
        done_next = done | bad | (alive & (fired_eos | (remaining <= 0)))
        # ---- commit the accepted prefix into BOTH pools --------------
        # feeds are chunk indices < n_out: the carried token plus the
        # accepted proposals; the last output is never fed (it is the
        # next block's carried token, or the row just finished)
        n_feed = jnp.where(done | bad, 0, n_out)
        pool_t = fam_t.commit_slots(params_t, chunk, positions, n_feed,
                                    pool_t, pend_t, cfg_t, done=done)
        # draft catch-up: the draft consumes the same committed chunk
        # through its own verify/commit hooks (its scratch proposals were
        # discarded), so both pools agree on every committed position —
        # including the bonus-position feed the propose scan never ran
        _, pend_d = fam_d.verify_step_slots(params_d, chunk, positions,
                                            pool_d, cfg_d, done=done)
        pool_d = fam_d.commit_slots(params_d, chunk, positions, n_feed,
                                    pool_d, pend_d, cfg_d, done=done)
        positions = positions + n_out
        n_prop = jnp.sum(n_prop_rows)
        n_acc = jnp.sum(jnp.maximum(n_out - 1, 0))
        return (tokens, positions, remaining, done_next, pool_t, pool_d,
                keys), (out_tokens.T, valid.T, bad, dbad.any(), n_prop,
                        n_acc)

    def loop_fn(params_t, params_d, tokens, positions, remaining, eos_ids,
                done, pool_t, pool_d, keys):
        def body(carry, _):
            (tokens, positions, remaining, done, pool_t, pool_d,
             keys) = carry
            return one_block(tokens, positions, remaining, eos_ids, done,
                             pool_t, pool_d, keys, params_t, params_d)

        carry, (blocks, valids, bads, dbads, props, accs) = jax.lax.scan(
            body, (tokens, positions, remaining, done, pool_t, pool_d,
                   keys), None, length=k)
        tokens, positions, remaining, done, pool_t, pool_d, keys = carry
        B = tokens.shape[0]
        block = blocks.reshape(k * S, B)
        valid = valids.reshape(k * S, B)
        return (block, valid, bads.any(0), dbads.any(), tokens, positions,
                remaining, done, pool_t, pool_d, keys, props.sum(),
                accs.sum())

    return loop_fn
