"""Lazy-sync (manual ZeRO-3) step must match the pjit-automatic step."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.slow
def test_lazy_sync_matches_baseline():
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config
        from repro.models import get_family
        from repro.optim import OptimizerConfig, make_optimizer
        from repro.train.steps import make_train_step
        from repro.train.lazy_sync import make_lazy_sync_train_step
        from repro.distributed.sharding import (params_shardings,
            sharding_rules_for_mesh, use_rules)
        from repro.data.synthetic import lm_batch

        cfg = get_config("qwen3-0.6b-smoke")
        fam = get_family(cfg)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        opt_cfg = OptimizerConfig(lr=1e-3, clip_norm=None,
                                  master_weights=False)
        init_fn, _ = make_optimizer(opt_cfg)
        batch = {"tokens": jnp.asarray(lm_batch(cfg.vocab_size, 16, 32))}

        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        rules = sharding_rules_for_mesh(mesh, fsdp=True)
        p_sh = params_shardings(fam.param_specs(cfg), mesh, rules,
                                shapes=params)
        params_s = jax.device_put(params, p_sh)

        base = make_train_step(cfg, opt_cfg, n_microbatches=4)
        with mesh, use_rules(mesh, rules):
            p1, o1, m1 = jax.jit(base)(params_s, init_fn(params_s), batch,
                                       jnp.int32(1))

        lazy = make_lazy_sync_train_step(cfg, opt_cfg, mesh, p_sh,
                                         n_microbatches=4)
        with mesh, use_rules(mesh, rules):
            p2, o2, m2 = jax.jit(lazy)(params_s, init_fn(params_s), batch,
                                       jnp.int32(1))
        a, b = float(m1["loss"]), float(m2["loss"])
        assert abs(a - b) < 2e-3, (a, b)
        d = max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                      - y.astype(jnp.float32))))
                for x, y in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert d < 2e-3, d
        print("LAZY-MATCH", a, b, d)
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "LAZY-MATCH" in out.stdout
