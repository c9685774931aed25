"""Trainer: end-to-end training loop with growth, checkpointing, elastic
resume, straggler watchdog — runs on anything from 1 CPU device to the
production meshes.

This is what the examples drive; the dry-run lowers the same ``train_step``
at production scale.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch gpt-micro --steps 200
  PYTHONPATH=src python -m repro.launch.train --arch gpt-micro-big \
      --grow-from gpt-micro --grow-method mango --steps 200
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import get_config
from repro.data.synthetic import lm_data_iter, vision_batch
from repro.distributed.sharding import (
    params_shardings,
    sharding_rules_for_mesh,
    use_rules,
)
from repro.launch.mesh import make_host_mesh
from repro.models import get_family
from repro.optim import OptimizerConfig, make_optimizer, \
    linear_warmup_cosine
from repro.train.steps import make_train_step
from repro.utils.compile_cache import enable_compile_cache


def data_for(cfg, batch, seq, seed=0, start_step=0):
    if cfg.head == "cls":
        def it():
            step = start_step
            while True:
                n = int(cfg.image_size // cfg.patch_size) ** 2
                b = vision_batch(cfg.n_classes, batch, cfg.image_size,
                                 cfg.patch_size, seed=seed, step=step)
                # stub frontend dims must match continuous_inputs
                b["inputs"] = b["inputs"][..., :cfg.continuous_inputs]
                b["inputs"] = b["inputs"][:, :cfg.learned_pos - 1]
                yield b
                step += 1
        return it()
    return lm_data_iter(cfg.vocab_size, batch, seq, seed=seed,
                        start_step=start_step)


def train(arch: str, *, steps=100, batch=8, seq=None, lr=3e-4,
          warmup=20, ckpt_dir=None, ckpt_every=0, resume=False,
          grow_from=None, grow_method="mango", grow_rank=1,
          grow_steps=50, grow_src_ckpt=None, log_every=10, seed=0,
          watchdog_s=None, n_microbatches=1, log_fn=print):
    cfg = get_config(arch)
    fam = get_family(cfg)
    seq = seq or min(cfg.max_seq_len, 256)
    mesh = make_host_mesh()
    rules = sharding_rules_for_mesh(mesh)

    opt_cfg = OptimizerConfig(lr=lr, weight_decay=1e-2)
    schedule = linear_warmup_cosine(lr, warmup, steps)
    init_fn, _ = make_optimizer(opt_cfg, schedule)
    step_fn = make_train_step(cfg, opt_cfg, schedule,
                              n_microbatches=n_microbatches)

    # ---- init (fresh, grown from a source model, or resumed) ----
    start = 0
    rng = jax.random.PRNGKey(seed)
    history = []
    if grow_from:
        from repro.core import grow as growlib

        cfg_src = get_config(grow_from)
        src_ckpt = grow_src_ckpt or (
            ckpt_dir and os.path.join(ckpt_dir, "..", grow_from))
        fam_src = get_family(cfg_src)
        params_src = fam_src.init(rng, cfg_src)
        if src_ckpt and os.path.isdir(src_ckpt):
            from repro.checkpoint import load_checkpoint
            tree, sstep, _ = load_checkpoint(
                src_ckpt, {"p": params_src, "o": None})
            params_src = tree["p"]
            log_fn(f"[grow] source weights from {src_ckpt} @ step {sstep}")
        params = growlib.grow_from_source(
            cfg_src, cfg, method=grow_method, rank=grow_rank,
            steps=grow_steps, data_iter=data_for(cfg, batch, seq, seed + 1),
            params_src=params_src, rng=rng, log_fn=log_fn)
    else:
        params = fam.init(rng, cfg)
    opt_state = init_fn(params)

    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, keep=3,
                                every=ckpt_every or max(steps // 4, 1),
                                async_save=True)
        if resume:
            restored = mgr.restore_latest({"p": params, "o": opt_state})
            if restored:
                tree, start, extra = restored
                params, opt_state = tree["p"], tree["o"]
                log_fn(f"[resume] restored step {start}")

    p_shard = params_shardings(fam.param_specs(cfg), mesh,
                               rules, shapes=params)
    params = jax.device_put(params, p_shard)

    jstep = jax.jit(step_fn, donate_argnums=(0, 1))
    data = data_for(cfg, batch, seq, seed, start_step=start)
    t_last = time.time()
    for step in range(start, steps):
        b = next(data)
        b = {k: jnp.asarray(v) for k, v in b.items()}
        with use_rules(mesh, rules):
            params, opt_state, metrics = jstep(params, opt_state, b,
                                               jnp.int32(step + 1))
        if watchdog_s and time.time() - t_last > watchdog_s:
            log_fn(f"[watchdog] step {step} exceeded {watchdog_s}s — "
                   "in production this triggers checkpoint + re-mesh")
        t_last = time.time()
        if step % log_every == 0 or step == steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            history.append({"step": step, **m})
            log_fn(f"step {step:5d}  loss {m.get('loss', 0):.4f}  "
                   f"gnorm {m.get('grad_norm', 0):.3f}")
        if mgr:
            mgr.maybe_save(step + 1, {"p": params, "o": opt_state},
                           extra={"arch": arch})
    if mgr:
        mgr.maybe_save(steps, {"p": params, "o": opt_state},
                       extra={"arch": arch}, force=True)
        mgr.wait()
    return params, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grow-from", default=None)
    ap.add_argument("--grow-method", default="mango",
                    choices=["mango", "ligo", "bert2bert", "stackbert",
                             "net2net"])
    ap.add_argument("--grow-rank", type=int, default=1)
    ap.add_argument("--grow-steps", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--history-out", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    _, hist = train(
        args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
        lr=args.lr, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        resume=args.resume, grow_from=args.grow_from,
        grow_method=args.grow_method, grow_rank=args.grow_rank,
        grow_steps=args.grow_steps, n_microbatches=args.microbatches)
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(hist, f, indent=1)


if __name__ == "__main__":
    main()
