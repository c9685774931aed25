"""Step builders: train / eval / prefill / decode / operator-grow.

These are the exact functions the launcher jits with mesh shardings and the
dry-run lowers at full scale, so everything here must be shape-polymorphic
over batch/seq and mesh-agnostic (sharding comes only from annotations +
in/out shardings).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import get_family
from repro.optim import OptimizerConfig, make_optimizer
from repro.train.loss import loss_for


def make_train_step(cfg, opt_cfg: OptimizerConfig, schedule=None,
                    n_microbatches: int = 1, grad_transform=None):
    """-> step_fn(params, opt_state, batch, step) -> (params, state, metrics).

    ``n_microbatches`` > 1 splits the global batch and accumulates grads
    under a scan (sequential accumulation — the standard memory/throughput
    trade at large global batch).
    ``grad_transform`` — optional hook applied to the averaged grads before
    the optimizer (gradient compression plugs in here).
    """
    fam = get_family(cfg)
    loss_fn = loss_for(cfg)
    _, update_fn = make_optimizer(opt_cfg, schedule)

    def fwd_loss(params, batch):
        logits, aux = fam.forward(params, batch, cfg)
        return loss_fn(logits, aux, batch, cfg)

    grad_fn = jax.value_and_grad(fwd_loss, has_aux=True)

    def step_fn(params, opt_state, batch, step):
        if n_microbatches == 1:
            (_, metrics), grads = grad_fn(params, batch)
        else:
            B_glob = batch["tokens"].shape[0]

            def split(x):
                ax = next(i for i, s in enumerate(x.shape) if s == B_glob)
                n = n_microbatches
                lead = x.shape[:ax]
                return jnp.moveaxis(
                    x.reshape(*lead, n, x.shape[ax] // n, *x.shape[ax + 1:]),
                    len(lead), 0)
            micro = jax.tree.map(split, batch)

            def acc_body(carry, mb):
                g_acc, m_acc = carry
                (_, metrics), grads = grad_fn(params, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, grads)
                m_acc = jax.tree.map(jnp.add, m_acc, metrics)
                return (g_acc, m_acc), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            m0 = jax.eval_shape(
                lambda: grad_fn(params, jax.tree.map(lambda x: x[0],
                                                     micro))[0][1])
            m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), m0)
            (grads, metrics), _ = jax.lax.scan(
                acc_body, (g0, m0), micro,
                unroll=getattr(cfg, "unroll_scans", False))
            grads = jax.tree.map(lambda g: g / n_microbatches, grads)
            metrics = jax.tree.map(lambda m: m / n_microbatches, metrics)
        if grad_transform is not None:
            grads = grad_transform(grads)
        params, opt_state, opt_metrics = update_fn(
            params, opt_state, grads, step)
        metrics.update(opt_metrics)
        return params, opt_state, metrics

    return step_fn


def make_eval_step(cfg):
    fam = get_family(cfg)
    loss_fn = loss_for(cfg)

    def eval_fn(params, batch):
        logits, aux = fam.forward(params, batch, cfg)
        _, metrics = loss_fn(logits, aux, batch, cfg)
        return metrics

    return eval_fn


def make_prefill_step(cfg):
    fam = get_family(cfg)

    def prefill_fn(params, batch, cache):
        return fam.prefill(params, batch, cfg, cache)

    return prefill_fn


def make_decode_step(cfg):
    """One greedy serving step: feed current tokens, emit next + cache.
    (Non-greedy decode lives in the engine's sampled loops —
    ``serve/sampling.py`` — not here.)"""
    fam = get_family(cfg)

    def decode_fn(params, tokens, pos, cache):
        logits, cache = fam.decode_step(params, tokens, pos, cache, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, cache

    return decode_fn


def make_prefill_full_step(cfg):
    """Prefill that returns logits at every position (continuous batching:
    prompts are padded to bucket lengths, the engine reads each request's
    true last-token logits)."""
    fam = get_family(cfg)
    if not hasattr(fam, "prefill_full"):
        raise NotImplementedError(
            f"family {cfg.family!r} has no full-logits prefill")

    def prefill_fn(params, batch, cache):
        return fam.prefill_full(params, batch, cfg, cache)

    return prefill_fn


def make_slot_decode_step(cfg):
    """Continuous-batching decode: every batch row is an independent cache
    slot at its own sequence length.

    fn(params, tokens (B,), positions (B,), cache) -> (next (B,), cache).
    """
    fam = get_family(cfg)
    if not hasattr(fam, "decode_step_slots"):
        raise NotImplementedError(
            f"family {cfg.family!r} has no slot-indexed decode path")

    def decode_fn(params, tokens, positions, cache):
        logits, cache = fam.decode_step_slots(params, tokens, positions,
                                              cache, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, cache

    return decode_fn


def make_prefill_admit_step(cfg, sampling=None):
    """Batched admission prefill for the continuous-batching engine.

    fn(params, tokens (N, Sbucket), plens (N,), cache) ->
        (first (N,) int32, cache)

    All requests of one prefill bucket run as ONE multi-row forward; the
    first generated token of each row (argmax at its true last prompt
    position) is computed on device, so admission costs one dispatch per
    bucket group instead of one prefill + one host argmax per request.

    ``plens`` rides along in the batch: full KV caches ignore it (the
    pad tail hides behind the per-row ``kv_len`` mask), but ring-buffer
    window caches and recurrent state (griffin, xlstm) must take each
    row's state at its TRUE prompt boundary.

    With a non-greedy ``sampling`` (``serve.sampling.SamplingParams``)
    the signature gains per-row chain roots —
    fn(params, tokens, plens, cache, uids (N,), skips (N,)) ->
    (first, cache, keys)
    — each row's PRNG chain is seeded from (sampling.seed, uid) ON
    DEVICE, its first key samples the first token, and the advanced
    chains come back for the admission scatter (keys never round-trip
    through the host).  ``skips`` is the journal-resume hook: row ``i``'s
    chain is advanced ``skips[i]`` splits before its first draw, exactly
    as if it had already sampled that many tokens — a restarted engine
    re-admitting a mid-flight sequence (prompt ‖ committed tokens) then
    draws the SAME next token the uninterrupted run would have (chains
    advance only on real samples, so chain position == committed-token
    count).  Fresh admissions pass zeros.
    """
    from repro.serve import sampling as sampling_lib

    fam = get_family(cfg)
    if not hasattr(fam, "prefill_full"):
        raise NotImplementedError(
            f"family {cfg.family!r} has no full-logits prefill")

    def last_logits(params, tokens, plens, cache):
        logits, cache = fam.prefill_full(
            params, {"tokens": tokens, "plens": plens}, cfg, cache)
        rows = jnp.arange(tokens.shape[0])
        return logits[rows, plens - 1], cache

    if sampling_lib.is_greedy(sampling):
        def prefill_fn(params, tokens, plens, cache):
            logits, cache = last_logits(params, tokens, plens, cache)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        return prefill_fn

    def prefill_sampled(params, tokens, plens, cache, uids, skips):
        logits, cache = last_logits(params, tokens, plens, cache)
        roots = jax.vmap(
            lambda u: sampling_lib.request_key(sampling.seed, u))(uids)

        # a committed token consumed one split of its chain: replay those
        # splits (bounded by the bucket length — a resume's committed run
        # is part of its padded prompt, so skips < tokens.shape[1])
        def advance(i, ks):
            ks_new, _ = sampling_lib.next_keys(ks)
            return jnp.where((i < skips)[:, None], ks_new, ks)

        roots = jax.lax.fori_loop(0, tokens.shape[1], advance, roots)
        keys, subs = sampling_lib.next_keys(roots)
        first = sampling_lib.sample_logits(logits, subs, sampling)
        return first, cache, keys

    return prefill_sampled


def make_slot_decode_loop(cfg, k: int, sampling=None):
    """On-device macro-step: K slot-decode steps under one ``lax.scan``.

    fn(params, tokens (B,), positions (B,), remaining (B,), eos_ids (B,),
       done (B,), cache) ->
        (block (K, B) int32, valid (K, B) bool, poison (B,) bool,
         tokens, positions, remaining, done, cache)

    The host syncs once per K generated tokens instead of once per token:
    eos / max-new-token stopping is applied per slot *inside* the scan.  A
    row that finishes (or starts the block idle) stops advancing — its
    position and token freeze, and the family's ``decode_step_slots``
    turns the row into an exact no-op: full KV caches re-store identical
    bytes and attend with ``kv_len == 0`` (the idle-row short-circuit in
    the attention stack); recurrent families (griffin, xlstm) freeze the
    row's state outright via the ``done`` mask, since a recurrence update
    cannot be re-stored.  ``valid[i, b]`` marks whether ``block[i, b]`` is
    a really generated token; rows emit their eos token as valid and then
    go quiet.

    ``poison`` is the NaN/Inf sentinel: a live row whose logits come back
    non-finite at any step of the block is frozen ON that step exactly
    like an eos row (its garbage token is never committed — ``valid``
    goes quiet, the done-mask turns the row into a no-op for the rest of
    the scan, and a recurrent family's state stops before the poisoned
    update can propagate) and its ``poison`` flag rides the block
    readback, so detection costs zero extra host syncs.  The engine
    quarantine-evicts flagged slots.

    ``eos_ids`` uses -1 for "no eos" (token ids are non-negative).
    ``remaining`` counts decode tokens still owed per row; it hits 0
    exactly when the row's last owed token is emitted.

    With a non-greedy ``sampling`` (``serve.sampling.SamplingParams``)
    the signature gains per-slot PRNG chains —
    fn(..., cache, keys (B,2)) -> (..., cache, keys) — and each step
    draws from the temperature/top-k/top-p-filtered distribution.  A
    chain only advances when its row really samples, so a request's
    tokens are a pure function of (seed, uid, prompt), independent of
    slot placement and interleaving.
    """
    from repro.serve import sampling as sampling_lib
    from repro.serve import tracing

    fam = get_family(cfg)
    if not hasattr(fam, "decode_step_slots"):
        raise NotImplementedError(
            f"family {cfg.family!r} has no slot-indexed decode path")
    greedy = sampling_lib.is_greedy(sampling)

    def step(carry, params, eos_ids):
        if greedy:
            tokens, positions, remaining, done, poison, cache = carry
        else:
            tokens, positions, remaining, done, poison, cache, keys = carry
        live = ~done
        logits, cache = fam.decode_step_slots(
            params, tokens, positions, cache, cfg, done=done)
        with tracing.scope("select"):
            # NaN/Inf sentinel: a poisoned live row freezes HERE — its token
            # is never committed and (crucially, for recurrent state) no
            # further update runs on the row.  The elementwise reduce fuses
            # into the dispatch; nothing extra crosses to the host.
            bad = live & ~jnp.all(jnp.isfinite(
                logits.astype(jnp.float32)), axis=-1)
            live = live & ~bad
            poison = poison | bad
            if greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                keys_new, subs = sampling_lib.next_keys(keys)
                keys = jnp.where(live[:, None], keys_new, keys)
                nxt = sampling_lib.sample_logits(logits, subs, sampling)
            tokens = jnp.where(live, nxt, tokens)
            remaining = jnp.where(live, remaining - 1, remaining)
            done = done | bad | (live & ((tokens == eos_ids)
                                         | (remaining <= 0)))
            positions = jnp.where(live, positions + 1, positions)
        carry = (tokens, positions, remaining, done, poison, cache) \
            if greedy \
            else (tokens, positions, remaining, done, poison, cache, keys)
        return carry, (tokens, live)

    if greedy:
        def loop_fn(params, tokens, positions, remaining, eos_ids, done,
                    cache):
            poison0 = jnp.zeros(tokens.shape, bool)
            carry, (block, valid) = jax.lax.scan(
                lambda c, _: step(c, params, eos_ids),
                (tokens, positions, remaining, done, poison0, cache),
                None, length=k)
            tokens, positions, remaining, done, poison, cache = carry
            return (block, valid, poison, tokens, positions, remaining,
                    done, cache)

        return loop_fn

    def loop_sampled(params, tokens, positions, remaining, eos_ids, done,
                     cache, keys):
        poison0 = jnp.zeros(tokens.shape, bool)
        carry, (block, valid) = jax.lax.scan(
            lambda c, _: step(c, params, eos_ids),
            (tokens, positions, remaining, done, poison0, cache, keys),
            None, length=k)
        tokens, positions, remaining, done, poison, cache, keys = carry
        return (block, valid, poison, tokens, positions, remaining, done,
                cache, keys)

    return loop_sampled


def make_grow_step(gop, cfg_tgt, opt_cfg: OptimizerConfig,
                   n_microbatches: int = 1):
    """Operator-training step (paper Eq. 7): one Adam update on the TR cores.

    fn(op_params, opt_state, small_params, batch, step) ->
        (op_params, opt_state, metrics)

    The big model materializes *inside* the step (sharded by annotation) —
    it never exists outside the jit.  With ``n_microbatches`` > 1 the
    growth contraction is recomputed per microbatch (it is ~1 ms at yi-9b
    scale — see contract_flops) in exchange for an n_micro x smaller
    activation stash of the target model's fwd/bwd.
    """
    from repro.core import grow as growlib

    fam = get_family(cfg_tgt)
    loss_fn = loss_for(cfg_tgt)
    _, update_fn = make_optimizer(opt_cfg)

    def objective(op_params, small_params, batch):
        big = growlib.grow_params(gop, op_params, small_params)
        logits, aux = fam.forward(big, batch, cfg_tgt)
        loss, metrics = loss_fn(logits, aux, batch, cfg_tgt)
        return loss, metrics

    grad_fn = jax.value_and_grad(objective, has_aux=True)

    def step_fn(op_params, opt_state, small_params, batch, step):
        if n_microbatches == 1:
            (_, metrics), grads = grad_fn(op_params, small_params, batch)
        else:
            def split(x):
                return x.reshape(n_microbatches,
                                 x.shape[0] // n_microbatches, *x.shape[1:])
            micro = jax.tree.map(split, batch)

            def acc(carry, mb):
                g_acc, m_acc = carry
                (_, metrics), grads = grad_fn(op_params, small_params, mb)
                return (jax.tree.map(jnp.add, g_acc, grads),
                        jax.tree.map(jnp.add, m_acc, metrics)), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              op_params)
            m0 = jax.eval_shape(lambda: grad_fn(
                op_params, small_params,
                jax.tree.map(lambda x: x[0], micro))[0][1])
            m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                              m0)
            (grads, metrics), _ = jax.lax.scan(acc, (g0, m0), micro)
            grads = jax.tree.map(lambda g: g / n_microbatches, grads)
            metrics = jax.tree.map(lambda m: m / n_microbatches, metrics)
        op_params, opt_state, opt_metrics = update_fn(
            op_params, opt_state, grads, step)
        metrics.update(opt_metrics)
        return op_params, opt_state, metrics

    return step_fn
