"""The reduction from a profiler trace to the per-layer readings: HLO
text parsing, program attribution of kernels, busy time and idle gaps,
checked by hand on small traces and on a slice recorded on a TPU v5e."""
import json
import pathlib

import pytest

from bench import trace as T

DATA = pathlib.Path(__file__).resolve().parent / "data"
KERNEL = ('%paged_slot_decode_attention.11 = bf16[8,16,1,64]{3,2,1,0} '
          'custom-call(s32[8]{0} %add_select_fusion.7, bf16[320,64,16,64]'
          '{3,2,1,0} %copy-done), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={}')
ALLOC = ('%custom-call.2 = bf16[24,1,2560,16,64]{2,4,3,1,0} custom-call(), '
         'custom_call_target="AllocateBuffer"')
FUSION = ('%maximum_bitcast_fusion.2 = bf16[1,16,256,64]{3,2,1,0} '
          'fusion(bf16[8]{0} %custom-call.3), kind=kLoop')


def test_hlo_text_parsing():
    assert T.op_name(KERNEL) == "paged_slot_decode_attention.11"
    assert T.is_kernel(KERNEL)
    assert not T.is_kernel(ALLOC) and not T.is_kernel(FUSION)
    assert T.is_container("while.49") and not T.is_container("fusion.3")
    assert T.module_base("jit_loop_fn(3559281853987654728)") == \
        "jit_loop_fn"


def _small():
    # two programs on device 0; a gap between them while the host reads
    modules = [[("jit_prefill_fn", 0, 100), ("jit_loop_fn", 150, 400)]]
    ops = [[("while.1", 0, 100, "container"),
            ("flash_attention.6", 10, 40, "kernel"),
            ("fusion.1", 40, 90, "op"),
            ("while.2", 150, 400, "container"),
            ("paged_slot_decode_attention.11", 160, 260, "kernel"),
            ("copy.91", 260, 390, "op")]]
    host = [("bench.window", 0, 500), ("bench.readback", 95, 160),
            ("bench.admit", 400, 430)]
    return T.Trace(modules, ops, host)


def test_reductions_on_a_small_trace():
    tr = _small()
    lo, hi = tr.window()
    assert (lo, hi) == (0, 500)
    assert tr.busy_ns(lo, hi) == 100 + 250
    assert tr.program_ns("jit_loop_fn", lo, hi) == (250, 1)
    assert tr.kernel_ns("jit_loop_fn", lo, hi) == (100, 1)
    assert tr.kernel_ns("jit_prefill_fn", lo, hi) == (30, 1)
    assert tr.module_of(0, 200) == "jit_loop_fn"
    assert tr.module_of(0, 120) is None
    top = dict(tr.top_ops(lo, hi))
    assert top["jit_loop_fn/copy.91"] == pytest.approx(130e-9)
    assert not any(k.split("/")[1].startswith("while") for k in top)
    idle = dict(tr.idle_by_host(lo, hi))
    assert idle == pytest.approx({"bench.readback": 50e-9,
                                  "bench.admit": 100e-9})
    assert sum(idle.values()) * 1e9 == pytest.approx(
        (hi - lo) - tr.busy_ns(lo, hi))
    again = T.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert again.busy_ns(lo, hi) == tr.busy_ns(lo, hi)


def test_no_device_plane_reads_nothing():
    tr = T.Trace([], [], [("bench.window", 0, 10)])
    assert tr.busy_ns(0, 10) == 0
    assert tr.program_ns("jit_loop_fn", 0, 10) == (0, 0)
    assert tr.top_ops(0, 10) == []


def test_recorded_v5e_slice():
    """60 ms of a qwen-chat-batch run on one TPU v5e chip: one admission
    prefill (24 layers, one flash kernel call each) and its scatter."""
    tr = T.Trace.from_json(json.loads(
        (DATA / "trace_slice_v5e.json").read_text()))
    lo, hi = tr.window()
    ns, runs = tr.program_ns("jit_prefill_fn", lo, hi)
    assert runs == 1 and ns == 44_013_964
    kns, calls = tr.kernel_ns("jit_prefill_fn", lo, hi)
    assert calls == 24 and 0 < kns < ns
    assert tr.kernel_ns("jit_loop_fn", lo, hi) == (0, 0)
    assert tr.program_ns("jit_admit_fn", lo, hi)[1] == 1
    busy = tr.busy_ns(lo, hi)
    assert 0 < busy <= hi - lo
    idle = tr.idle_by_host(lo, hi)
    assert sum(v for _, v in idle) == pytest.approx((hi - lo - busy) / 1e9)
    top = tr.top_ops(lo, hi, n=3)
    assert len(top) == 3 and all(v > 0 for _, v in top)
    assert top == sorted(top, key=lambda kv: -kv[1])
