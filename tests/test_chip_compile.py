"""The main-path Pallas kernels compile for a TPU v5e.

Interpret mode never checks Mosaic's tiling rules or VMEM limits, so every
kernel the serve engine dispatches under ``decode_kernel="auto"`` is
compiled here ahead of time for a described (not attached) ``v5e:2x2``
topology, at published widths:

  * qwen1.5-0.5b — bf16, 16 kv heads of 64 (no grouping);
  * qwen3-0.6b   — bf16, 8 kv heads of 128 shared by 2 query heads each;
  * gpt-base     — float32, 12 heads of 64 (the live-growth target).

Nothing runs, so this says nothing about results or speed; the kernels'
numerics are covered in interpret mode by ``test_kernels.py``.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels.flash_attention import flash_attention

# (heads, kv heads, head dim, dtype) of each width under test
WIDTHS = {
    "qwen1.5-0.5b": (16, 16, 64, jnp.bfloat16),
    "qwen3-0.6b": (16, 8, 128, jnp.bfloat16),
    "gpt-base": (12, 12, 64, jnp.float32),
}
B, MAX_LEN, PAGE, CHUNK = 8, 1024, 64, 5  # capacity, pool, page, d + 1


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _args(width, sharding):
    H, KV, hd, dt = WIDTHS[width]
    n_pages = B * MAX_LEN // PAGE
    return dict(
        q=_sds((B, H, hd), dt, sharding),
        qc=_sds((B, CHUNK, H, hd), dt, sharding),
        pool=_sds((B, MAX_LEN, KV, hd), dt, sharding),
        arena=_sds((n_pages, PAGE, KV, hd), dt, sharding),
        chunk=_sds((B, CHUNK, KV, hd), dt, sharding),
        bt=_sds((B, MAX_LEN // PAGE), jnp.int32, sharding),
        rows=_sds((B,), jnp.int32, sharding),
        prefill_q=_sds((B, H, 512, hd), dt, sharding),
        prefill_kv=_sds((B, KV, 512, hd), dt, sharding),
    )


KERNELS = {
    "slot_decode": lambda a: da.slot_decode_attention.lower(
        a["q"], a["pool"], a["pool"], a["rows"]),
    "ring_decode": lambda a: da.ring_decode_attention.lower(
        a["q"], a["pool"], a["pool"], a["rows"], window=512),
    "chunk_verify": lambda a: da.chunk_verify_attention.lower(
        a["qc"], a["pool"], a["pool"], a["chunk"], a["chunk"], a["rows"],
        ring=False),
    "paged_slot_decode": lambda a: da.paged_slot_decode_attention.lower(
        a["q"], a["arena"], a["arena"], a["bt"], a["rows"]),
    "paged_ring_decode": lambda a: da.paged_ring_decode_attention.lower(
        a["q"], a["arena"], a["arena"], a["bt"], a["rows"], window=512),
    "paged_chunk_verify": lambda a: da.paged_chunk_verify_attention.lower(
        a["qc"], a["arena"], a["arena"], a["bt"], a["chunk"], a["chunk"],
        a["rows"], ring=True, window=512),
    "flash_attention": lambda a: flash_attention.lower(
        a["prefill_q"], a["prefill_kv"], a["prefill_kv"], causal=True),
}


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(kernel, width, one_chip):
    compiled = KERNELS[kernel](_args(width, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
