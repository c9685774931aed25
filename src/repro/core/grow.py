"""Unified growth API: build / grow / train-operator for all methods.

Procedure (paper §3.2 "Procedures of Applying Mango"):
 (i)   pack the pretrained M(L1,D1) into the weight tensor M1;
 (ii)  train the growth operator on the task loss for ~100 steps (Eq. 7) —
       only Mango and LiGO are trainable; bert2BERT/StackBERT are frozen;
 (iii) recover M2 through the operator;
 (iv)  split M2 into M(L2,D2) initial weights and continue normal training.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import baselines, mango
from repro.models import get_family

METHODS = ("mango", "ligo", "bert2bert", "stackbert", "net2net")


@dataclasses.dataclass(frozen=True)
class GrowthOperator:
    method: str
    op: mango.MangoOperator
    trainable: bool


def build(method: str, cfg_src, cfg_tgt, rank=1, rng=None, noise=None):
    """-> (GrowthOperator, op_params).

    ``noise`` scales the random component of the trainable methods'
    structured init (default 0.01).  ``noise=0`` makes an UNTRAINED
    mango operator coincide with the Net2Net expansion (width
    duplication + depth stacking) — the most function-preserving init
    available, which is what a live hot-swap wants.  Preservation is
    approximate, not exact: depth growth re-applies copied blocks, so
    grown logits drift from the source (measure with
    ``serve/upgrade.py: probe_token_agreement``)."""
    assert method in METHODS, method
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    op = mango.build_operator(cfg_src, cfg_tgt, rank=rank)
    if method == "mango":
        params = mango.init_operator_params(
            rng, op, **({} if noise is None else {"noise": noise}))
        return GrowthOperator(method, op, True), params
    if method == "ligo":
        params = baselines.init_ligo_params(
            rng, op, **({} if noise is None else {"noise": noise}))
        return GrowthOperator(method, op, True), params
    if method == "bert2bert":
        return GrowthOperator(method, op, False), \
            baselines.init_bert2bert_params(op, aki=True)
    if method == "net2net":
        return GrowthOperator(method, op, False), \
            baselines.init_bert2bert_params(op, aki=False)
    if method == "stackbert":
        return GrowthOperator(method, op, False), \
            baselines.init_stackbert_params(op)


def grow_params(gop: GrowthOperator, op_params, params_src, dtype=None):
    """Differentiable for mango/ligo; pure function of frozen cores else."""
    if gop.method == "ligo":
        core_params = baselines.ligo_to_cores(gop.op, op_params)
    else:
        core_params = op_params
    return mango.grow(gop.op, core_params, params_src, dtype=dtype)


def operator_param_count(gop: GrowthOperator, op_params) -> int:
    """Trainable-parameter count (paper Table 1 comparisons)."""
    if not gop.trainable:
        return 0
    leaves = jax.tree.leaves(
        {"groups": op_params["groups"], "width": op_params["aux"]["width"]})
    return sum(int(x.size) for x in leaves)


def grow_from_source(cfg_src, cfg_tgt, *, method="mango", rank=1, steps=0,
                     data_iter=None, params_src=None, rng=None,
                     noise=None, log_fn=print):
    """Full grow bootstrap: source init -> operator -> (optional Eq. 7
    operator training on ``data_iter``) -> grown target params.

    Shared by the train and serve launchers; pass ``params_src`` to grow
    from pretrained (e.g. checkpoint-restored) weights instead of a fresh
    init.  ``noise=0`` (with ``steps=0``) keeps the untrained operator
    maximally function-preserving — see :func:`build`.
    """
    from repro.train.loss import loss_for

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if params_src is None:
        params_src = get_family(cfg_src).init(rng, cfg_src)
    gop, op_params = build(method, cfg_src, cfg_tgt, rank=rank, rng=rng,
                           noise=noise)
    if steps:
        if data_iter is None:
            raise ValueError("operator training (steps > 0) needs data_iter")
        fam_tgt = get_family(cfg_tgt)
        loss_fn = loss_for(cfg_tgt)

        def op_loss(big, batch):
            logits, aux = fam_tgt.forward(big, batch, cfg_tgt)
            return loss_fn(logits, aux, batch, cfg_tgt)[0]

        op_params, losses = train_operator(gop, op_params, params_src,
                                           op_loss, data_iter, steps=steps)
        if losses:
            log_fn(f"[grow] {method} operator trained {len(losses)} "
                   f"steps: {losses[0]:.4f} -> {losses[-1]:.4f}")
    return grow_params(gop, op_params, params_src)


def train_operator(gop: GrowthOperator, op_params, params_src, loss_fn,
                   data_iter, *, steps=100, lr=1e-3, weight_decay=1e-2):
    """Stage-(ii): optimize the operator on the task loss (Eq. 7).

    ``loss_fn(big_params, batch) -> scalar`` — the target model's loss.
    Frozen methods return their params unchanged.
    """
    if not gop.trainable:
        return op_params, []
    from repro.optim import adamw_init, adamw_update

    # the source weights are an argument, not a closure: closed over,
    # they would be baked into the program as constants
    def objective(p, src, batch):
        big = grow_params(gop, p, src)
        return loss_fn(big, batch)

    opt_state = adamw_init(op_params)
    grad_fn = jax.jit(jax.value_and_grad(objective))

    @jax.jit
    def upd(p, s, g, step):
        return adamw_update(p, s, g, step, lr=lr, weight_decay=weight_decay)

    losses = []
    for step in range(steps):
        batch = next(data_iter)
        loss, grads = grad_fn(op_params, params_src, batch)
        op_params, opt_state = upd(op_params, opt_state, grads,
                                   jnp.int32(step + 1))
        losses.append(float(loss))
    return op_params, losses
