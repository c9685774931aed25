"""Sharding-rule unit tests + multi-device subprocess tests.

The in-process jax here sees 1 CPU device (the dry-run's 512-device flag
must never leak into tests), so anything needing a real multi-device mesh
runs in a subprocess with a small forced host device count.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.distributed.sharding import (
    LOGICAL_RULES_SINGLE_POD,
    fsdp_rules,
    inference_rules,
    logical_to_spec,
)
from repro.models import get_family

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class _FakeMesh:
    axis_names = ("data", "model")

    class devices:
        shape = (16, 16)


def test_divisibility_guard():
    rules = LOGICAL_RULES_SINGLE_POD
    # kv_heads=8 cannot shard over model=16 -> replicated
    spec = logical_to_spec(("layers", "batch", "seq", "kv_heads"),
                           (32, 128, 4096, 8), _FakeMesh, rules)
    assert spec == P(None, "data", None, None)
    # heads=32 shards fine
    spec = logical_to_spec(("layers", "embed", "heads"), (32, 4096, 4096),
                           _FakeMesh, rules)
    assert spec == P(None, None, "model")


def test_used_axis_tracking():
    rules = fsdp_rules(LOGICAL_RULES_SINGLE_POD)
    # activations: batch claims data, so embed must NOT double-use it
    spec = logical_to_spec(("batch", "seq", "embed"), (256, 4096, 4096),
                           _FakeMesh, rules)
    assert spec == P("data", None, None)
    # params: no batch axis -> embed gets data (FSDP)
    spec = logical_to_spec(("layers", "embed", "mlp"), (32, 4096, 11008),
                           _FakeMesh, rules)
    assert spec == P(None, "data", "model")


def test_inference_rules_cache_layout():
    rules = inference_rules(LOGICAL_RULES_SINGLE_POD)
    spec = logical_to_spec(("layers", "batch", "cache_seq", "kv_heads",
                            "head_dim"), (32, 128, 32768, 8, 128),
                           _FakeMesh, rules)
    assert spec == P(None, "data", "model", None, None)
    # weights still shard kv over model when divisible
    spec = logical_to_spec(("layers", "embed", "kv_heads"),
                           (32, 4096, 1024), _FakeMesh, rules)
    assert spec == P(None, None, "model")


def _run_subprocess(code, devices=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """The pjit train step on an 8-device mesh computes the same loss as
    1 device (data parallel + tensor parallel correctness)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config
        from repro.models import get_family
        from repro.optim import OptimizerConfig, make_optimizer
        from repro.train.steps import make_train_step
        from repro.distributed.sharding import (params_shardings,
            sharding_rules_for_mesh, use_rules)
        from repro.data.synthetic import lm_batch

        cfg = get_config("qwen3-0.6b-smoke")
        fam = get_family(cfg)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        opt_cfg = OptimizerConfig(lr=1e-3)
        init_fn, _ = make_optimizer(opt_cfg)
        opt = init_fn(params)
        batch = {"tokens": jnp.asarray(lm_batch(cfg.vocab_size, 8, 32))}
        step = make_train_step(cfg, opt_cfg)

        # single device result
        p1, o1, m1 = jax.jit(step)(params, opt, batch, jnp.int32(1))

        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        rules = sharding_rules_for_mesh(mesh)
        p_sh = params_shardings(fam.param_specs(cfg), mesh, rules,
                                shapes=params)
        params_s = jax.device_put(params, p_sh)
        with mesh, use_rules(mesh, rules):
            p2, o2, m2 = jax.jit(step)(params_s, init_fn(params_s), batch,
                                       jnp.int32(1))
        a, b = float(m1["loss"]), float(m2["loss"])
        assert abs(a - b) < 1e-3, (a, b)
        d = max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                      - y.astype(jnp.float32))))
                for x, y in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert d < 2e-3, d
        print("MATCH", a, b, d)
    """)
    assert "MATCH" in out


def test_elastic_reshard_roundtrip(tmp_path):
    """Save on an 8-device mesh, restore on 4 devices (elastic restart)."""
    code = f"""
        import jax, jax.numpy as jnp, numpy as np, os
        from repro.configs.base import get_config
        from repro.models import get_family
        from repro.checkpoint import save_checkpoint
        from repro.distributed.elastic import reshard_restore, \\
            choose_mesh_shape
        assert choose_mesh_shape(256, 16) == (16, 16)
        assert choose_mesh_shape(8, 16) == (1, 8)
        assert choose_mesh_shape(12, 16) == (3, 4)

        cfg = get_config("qwen3-0.6b-smoke")
        fam = get_family(cfg)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        save_checkpoint(r"{tmp_path}", 5, params)
        tree, mesh, step, extra = reshard_restore(
            r"{tmp_path}", params, fam.param_specs(cfg), prefer_model=2)
        assert step == 5
        assert mesh.devices.size == len(jax.devices())
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(tree)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32))
        print("ELASTIC-OK", mesh.devices.shape)
    """
    assert "ELASTIC-OK" in _run_subprocess(code, devices=4)


def test_gradient_compression():
    """bf16 + int8(+error feedback) cross-pod psum on a pod-axis mesh."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.compression import (make_crosspod_psum,
            init_error_feedback)

        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        grads = {"w": jnp.asarray(np.random.default_rng(0)
                                  .standard_normal((8, 16)), jnp.float32)}
        # replicated grads: psum/n == identity -> lossless check of plumbing
        f16 = make_crosspod_psum(mesh, method="bf16")
        with mesh:
            out16 = f16(grads)
        err = np.max(np.abs(np.asarray(out16["w"]) - np.asarray(grads["w"])))
        assert err < 1e-2, err

        f8 = make_crosspod_psum(mesh, method="int8")
        ef = init_error_feedback(grads)
        with mesh:
            out8, ef = f8(grads, ef)
        err8 = np.max(np.abs(np.asarray(out8["w"]) - np.asarray(grads["w"])))
        assert err8 < 0.1, err8
        # error feedback carries the quantization residual
        assert float(jnp.sum(jnp.abs(ef["w"]))) > 0
        print("COMPRESS-OK", err, err8)
    """)
    assert "COMPRESS-OK" in out
