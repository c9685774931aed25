"""Beyond-paper optimization: lazy-sync FSDP training step.

Problem (measured in the dry-run baselines): with pjit-automatic FSDP +
gradient accumulation, every microbatch pays (a) a full parameter
all-gather over the data axis and (b) a full gradient cross-data reduction
— 2 × n_microbatches collective rounds per step.  On yi-9b train_4k the
collective term (≈230 GB/dev wire) is 2.3× the compute term: the cell is
collective-bound purely from re-synchronizing state the algorithm does not
need synchronized until the optimizer runs.

Fix: a *partial-auto* ``jax.shard_map`` over the data(+pod) axes only:

    1. all-gather each FSDP-sharded param over data ONCE;
    2. run all microbatches with purely LOCAL gradients (no cross-data
       collectives inside the loop; model-axis TP collectives still inserted
       automatically — the model axis stays in GSPMD "auto" mode);
    3. one psum_scatter per param back to the FSDP layout, then the
       optimizer update runs on the shard.

Collective rounds per step: 2 × n_micro → 2 (gather + reduce-scatter),
an ~n_micro× cut of the dominant roofline term.  This is the manual ZeRO-3
schedule (what DeepSpeed/FSDP implement in CUDA-land), expressed in 60
lines of shard_map.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import get_family
from repro.optim import OptimizerConfig, make_optimizer
from repro.train.loss import loss_for


def _data_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _manual_spec(spec: P, manual: set) -> P:
    """Strip non-manual mesh axes from a PartitionSpec (they stay auto)."""
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a in manual)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            out.append(e if e in manual else None)
    return P(*out)


def _gather_axis(spec: P, manual: set):
    """(dim, mesh-axes) of the leaf dim sharded over manual (data) axes,
    or None if the leaf is replicated across them."""
    for i, e in enumerate(spec):
        es = e if isinstance(e, tuple) else (e,)
        hit = tuple(a for a in es if a in manual)
        if hit:
            return (i, hit)
    return None


def make_lazy_sync_train_step(cfg, opt_cfg: OptimizerConfig, mesh,
                              param_shardings, *, n_microbatches=8,
                              schedule=None):
    """Returns step_fn(params, opt_state, batch, step) with manual FSDP.

    ``param_shardings`` — the pytree of NamedShardings the params live in
    (FSDP layout).  Optimizer state must share the same layout.
    """
    fam = get_family(cfg)
    loss_fn = loss_for(cfg)
    _, update_fn = make_optimizer(opt_cfg, schedule)
    daxes = _data_axes(mesh)
    manual = set(daxes)
    n_data = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in daxes:
        n_data *= sizes[a]

    p_specs = jax.tree.map(lambda s: s.spec, param_shardings,
                           is_leaf=lambda x: isinstance(x, NamedSharding))
    p_manual = jax.tree.map(lambda s: _manual_spec(s, manual), p_specs,
                            is_leaf=lambda x: isinstance(x, P))
    gather_ax = jax.tree.map(lambda s: _gather_axis(s, manual), p_specs,
                             is_leaf=lambda x: isinstance(x, P))

    def body(params_local, opt_local, batch_local, step):
        # (1) one all-gather (only over the axes each leaf is sharded on —
        # leaves replicated over pod/data gather nothing)
        def gather(p, ax):
            if ax is None:
                return p
            dim, axes = ax
            for a in reversed(axes):
                p = jax.lax.all_gather(p, a, axis=dim, tiled=True)
            return p

        params_full = jax.tree.map(gather, params_local, gather_ax)

        # (2) local microbatch gradients — data axis is manual here, so no
        # cross-data collectives appear; model-axis TP stays auto.
        def fwd_loss(p, mb):
            logits, aux = fam.forward(p, mb, cfg)
            loss, metrics = loss_fn(logits, aux, mb, cfg)
            return loss, metrics

        grad_fn = jax.value_and_grad(fwd_loss, has_aux=True)

        def split(x):
            return x.reshape(n_microbatches, x.shape[0] // n_microbatches,
                             *x.shape[1:])

        micro = jax.tree.map(split, batch_local)

        def acc(carry, mb):
            g_acc, m_acc = carry
            (_, metrics), grads = grad_fn(params_full, mb)
            return (jax.tree.map(jnp.add, g_acc, grads),
                    jax.tree.map(jnp.add, m_acc, metrics)), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                          params_full)
        m0 = jax.eval_shape(lambda: grad_fn(
            params_full, jax.tree.map(lambda x: x[0], micro))[0][1])
        m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), m0)
        (grads_full, metrics), _ = jax.lax.scan(
            acc, (g0, m0), micro,
            unroll=cfg.unroll_scans)

        # (3) one reduce-scatter back to the FSDP shard layout; axes the
        # leaf is replicated over (e.g. pod) contribute a plain psum
        def reduce(g, ax):
            done = ()
            if ax is not None:
                dim, axes = ax
                for a in axes:
                    g = jax.lax.psum_scatter(
                        g, a, scatter_dimension=dim, tiled=True)
                done = axes
            for a in daxes:
                if a not in done:
                    g = jax.lax.psum(g, a)
            return g / n_data

        grads_local = jax.tree.map(reduce, grads_full, gather_ax)

        def mean_metric(m):
            for a in daxes:
                m = jax.lax.psum(m, a)
            return m / (n_data * n_microbatches)

        metrics = jax.tree.map(mean_metric, metrics)

        params_local, opt_local, opt_metrics = update_fn(
            params_local, opt_local, grads_local, step)
        metrics.update(opt_metrics)
        return params_local, opt_local, metrics

    batch_spec = P(daxes if len(daxes) > 1 else daxes[0])
    opt_manual = {"m": p_manual, "v": p_manual}
    if opt_cfg.master_weights:
        opt_manual["master"] = p_manual

    return jax.shard_map(
        body, mesh=mesh, axis_names=manual,
        in_specs=(p_manual, opt_manual, batch_spec, P()),
        out_specs=(p_manual, opt_manual, P()), check_vma=False)
