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

The qwen1.5-0.5b paged slot-decode loop is compiled whole as well, and its
compiled text is checked for data movement of arena size: the paged K/V
arenas must be updated in place through the decode loop, not copied or
sliced out per layer and step.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import decode_attention as da
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.models import get_family
from repro.serve.paged import build_paged_pool
from repro.train.steps import make_slot_decode_loop

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


# one HLO instruction: name, result type, opcode, first operand
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\("
                    r"%?([\w.\-]*)")
_MOVES = ("copy", "copy-start", "copy-done", "dynamic-slice",
          "dynamic-update-slice")


def _arena_moves(text, arena_shapes):
    """(in_entry, name, opcode, result type, first operand) of every
    data-movement instruction in compiled HLO ``text`` whose result has
    one of ``arena_shapes``, and the result type of every instruction of
    the entry computation by name."""
    moves, layouts, entry = [], {}, False
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            entry = True
        m = _INSTR.match(line)
        if not m:
            continue
        name, rtype, op, operand = m.groups()
        if entry:
            layouts[name] = rtype
        if op not in _MOVES:
            continue
        for dims in re.findall(r"\w+\[([\d,]*)\]", rtype):
            if tuple(int(d) for d in dims.split(",") if d) in arena_shapes:
                moves.append((entry, name, op, rtype, operand))
    return moves, layouts


def test_paged_decode_loop_keeps_arenas_in_place(one_chip, monkeypatch):
    """The qwen1.5-0.5b paged slot-decode loop (published widths, page
    64, capacity 8, max_len 2560, k 2) moves no arena inside its loops:
    no copy, dynamic slice or dynamic update of a whole arena or of one
    layer's arena per step.  All that is left is one layout conversion of
    each arena on entry and one on exit: the pool's default device layout
    puts the page axis minor (hd 64 is narrower than a lane tile), and
    the kernel reads pages row-major."""
    # ``auto`` checks the process's default backend, which is not the
    # described chip: take the compiled-kernel branch directly
    monkeypatch.setattr(ops, "_interp", lambda mode: mode == "interpret")
    cfg = get_config("qwen1.5-0.5b").replace(decode_kernel="auto")
    fam = get_family(cfg)

    def on_chip(shapes):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                            shapes)

    params = on_chip(jax.eval_shape(
        lambda: fam.init(jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(
        lambda: build_paged_pool(fam, cfg, B, 2560)[0]))
    rows = _sds((B,), jnp.int32, one_chip)
    done = _sds((B,), jnp.bool_, one_chip)
    # the engine's donation: state and pool in, state and pool out
    loop = jax.jit(make_slot_decode_loop(cfg, 2),
                   donate_argnums=(1, 2, 3, 5, 6))
    text = loop.lower(params, rows, rows, rows, rows, done,
                      pool).compile().as_text()
    assert text.startswith("HloModule jit_loop_fn")
    assert "tpu_custom_call" in text
    L, n_pages = pool["dense"]["k"].shape[:2]
    tail = pool["dense"]["k"].shape[2:]
    moves, layouts = _arena_moves(text, {(L, n_pages) + tail,
                                         (n_pages,) + tail,
                                         (L * n_pages,) + tail})
    per_step = [m for m in moves if not m[0]]
    assert not per_step, "\n".join(f"{m[2]} {m[1]} {m[3]}"
                                    for m in per_step)
    # entry and exit: K and V each converted once, layout to layout
    assert len(moves) <= 4, moves
    for _, name, op, rtype, operand in moves:
        layout = re.search(r"\{[^}]*\}", rtype).group(0)
        assert op == "copy" and layout not in layouts[operand], (
            name, rtype, layouts[operand])
