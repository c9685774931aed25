"""``chip_smoke.py`` on the CPU at smoke sizes, so the script cannot rot.

The phases run here with the Pallas interpreter (``decode_kernel=
"interpret"``); on the chip ``main()`` runs them at published widths with
the compiled kernels.  ``mode="auto"`` must refuse to run off a TPU.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest

from repro.kernels import ops

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SERVE = dict(n_requests=4, prompt_lens=(8, 24), gen=6, capacity=4,
             max_len=48, k=4, prefill_bucket=16)


def test_serve_phase(chip_smoke):
    rec = chip_smoke.serve_phase("qwen1.5-0.5b-smoke", "interpret", **SERVE)
    for mode in ("jnp", "interpret"):
        run = rec["runs"][mode]
        assert run["decode_kernel"] == mode and run["pool"] == "paged"
        assert run["outcomes"] == {"finished": 4}
        assert run["tokens"] == 4 * 6
        assert run["programs_compiled_in_timed_run"] == 0
    # float32 smoke widths: the interpreted kernels are token-exact
    assert rec["token_agreement"] == 1.0
    assert rec["logit_gap"] < 1e-4


def test_growth_phase(chip_smoke):
    rec = chip_smoke.growth_phase(
        "gpt-micro", "gpt-micro-big", "interpret", n_requests=4,
        prompt_lens=(8, 16), gen=8, capacity=2, max_len=32, k=2,
        prefill_bucket=32, grow_steps=1, upgrade_at=2, spec_d=2)
    assert rec["state"] == "swapped" and rec["dropped"] == 0
    assert rec["draft"] == "gpt-micro"
    assert rec["outcomes"] == {"finished": 4}
    assert rec["grow_seconds"] > 0 and rec["pause_ms"] > 0
    assert rec["resumed"] > 0 and rec["spec_proposed"] > 0
    assert 0 <= rec["fp_token_agreement"] <= 1
    # float32 micro widths: the interpreted verify kernel matches jnp
    assert rec["verify_logit_gap"] < 1e-4
    # nested compile events are counted once
    assert 0 < rec["compile_s"] <= rec["wall_s"] and rec["run_s"] >= 0


def test_mesh_phase_on_host_devices():
    """The ``--chips 4`` path at smoke size on 4 forced CPU devices (a
    fresh process: the device count is fixed when JAX starts)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.mesh_phase("qwen1.5-0.5b-smoke", "interpret", n_requests=4,
                       prompt_lens=(8, 24), gen=6, capacity=4, max_len=48,
                       k=4, prefill_bucket=16)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    recs = [json.loads(line) for line in out.stdout.splitlines()]
    assert [r["pool"] for r in recs] == ["dense", "paged"]
    for r in recs:
        assert r["mesh"] == "2x2" and r["n_devices"] == 4
        assert r["kernel_tp_fallback"] and r["decode_kernel"] == "jnp"
        assert r["token_agreement"] == 1.0 and r["logit_gap"] < 1e-4
        assert r["own_cache_rows"] > 0 and r["logit_gap_own_cache"] < 1e-4


def test_main_refuses_without_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def _f32(*shape):
    return jnp.zeros(shape, jnp.float32)


_I4 = jnp.zeros((2,), jnp.int32)
_BT = jnp.zeros((2, 2), jnp.int32)
AUTO_CALLS = {
    "tr_sandwich": lambda: ops.tr_sandwich(
        _f32(1, 128, 128), _f32(128, 128), _f32(128, 128)),
    "flash_attention": lambda: ops.flash_attention(
        _f32(1, 2, 128, 64), _f32(1, 2, 128, 64), _f32(1, 2, 128, 64)),
    "decode_attention": lambda: ops.decode_attention(
        _f32(2, 2, 64), _f32(2, 2, 16, 64), _f32(2, 2, 16, 64), _I4),
    "slot_decode_attention": lambda: ops.slot_decode_attention(
        _f32(2, 2, 64), _f32(2, 16, 2, 64), _f32(2, 16, 2, 64), _I4),
    "ring_decode_attention": lambda: ops.ring_decode_attention(
        _f32(2, 2, 64), _f32(2, 16, 2, 64), _f32(2, 16, 2, 64), _I4,
        window=8),
    "chunk_verify_attention": lambda: ops.chunk_verify_attention(
        _f32(2, 3, 2, 64), _f32(2, 16, 2, 64), _f32(2, 16, 2, 64),
        _f32(2, 3, 2, 64), _f32(2, 3, 2, 64), _I4, ring=False),
    "paged_slot_decode_attention": lambda: ops.paged_slot_decode_attention(
        _f32(2, 2, 64), _f32(4, 8, 2, 64), _f32(4, 8, 2, 64), _BT, _I4),
    "paged_ring_decode_attention": lambda: ops.paged_ring_decode_attention(
        _f32(2, 2, 64), _f32(4, 8, 2, 64), _f32(4, 8, 2, 64), _BT, _I4,
        window=8),
    "paged_chunk_verify_attention":
        lambda: ops.paged_chunk_verify_attention(
            _f32(2, 3, 2, 64), _f32(4, 8, 2, 64), _f32(4, 8, 2, 64), _BT,
            _f32(2, 3, 2, 64), _f32(2, 3, 2, 64), _I4, ring=False),
    "rglru_scan": lambda: ops.rglru_scan(_f32(1, 128, 128),
                                         _f32(1, 128, 128)),
}


@pytest.mark.parametrize("name", sorted(AUTO_CALLS))
def test_auto_mode_refuses_off_tpu(name):
    """``mode="auto"`` means compiled Pallas: off a TPU it raises, never
    falls back to the interpreter."""
    with pytest.raises(ops.NoTPUError):
        AUTO_CALLS[name]()
