"""The reduction from a profiler trace to the per-layer readings: HLO
text parsing, program attribution of kernels, busy time and idle gaps,
each op's scope from its program's compiled text, checked by hand on
small traces and on a slice recorded on a TPU v5e."""
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import pytest

from bench import common, harness, work
from bench import trace as T
from bench.recorder import Recorder

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


def _slice():
    return T.Trace.from_json(json.loads(
        (DATA / "trace_slice_v5e.json").read_text()))


def test_recorded_v5e_slice_reads_what_it_read_before_the_scope_map():
    """The readings of the recorded slice as the reduction gave them
    before ops had scopes and before the work came from the reference
    module."""
    tr = _slice()
    lo, hi = tr.window()
    assert tr.top_ops(lo, hi, n=3) == [
        ["jit_prefill_fn/flash_attention.6", 0.027024431],
        ["jit_admit_fn/copy.1", 0.007029775],
        ["jit_admit_fn/copy.6", 0.005682775]]
    assert tr.idle_by_host(lo, hi) == [["bench.admit", 0.001645928],
                                       ["bench.readback", 7e-09]]
    assert tr.kernel_ns("jit_prefill_fn", lo, hi) == (27024431.0, 24)
    assert tr.busy_ns(lo, hi) == 58354065.0
    assert tr.spans == [] and tr.scope_ns("jit_prefill_fn", lo, hi) is None


def test_useful_flops_come_from_the_configurations_reference():
    conf = common.load_json(common.BENCH_DIR / "configs/qwen1.5-0.5b.json")
    ref = common.load_reference(common.ROOT, conf)
    rec = Recorder()
    for uid, (plen, times) in enumerate([(1000, [1.0, 1.1, 1.2, 5.0]),
                                         (300, [0.5, 2.0]),
                                         (2048, [2.5, 2.6, 2.7])]):
        rec.expect(uid, 0.0, plen, True)
        rec.trails[uid].token_times = times
        rec.trails[uid].tokens = list(range(len(times)))
    run = types.SimpleNamespace(rec=rec, model=ref.describe(conf),
                                reference=ref,
                                in_trace=lambda t: 0.9 <= t <= 3.0)
    # prefills of 1000 and 2048 tokens, decodes over 1001, 1002, 301,
    # 2049 and 2050 keys (qwen1.5-0.5b, as bench/flops.py counts them)
    assert work.useful_flops(run) == 3088442097664


HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={()}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_type="mul" op_name="jit(step)/mlp/mul" source_file="x.py" source_line=3}
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %copy.2 = f32[8]{0} copy(%p)
  %fusion.1 = f32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/while/body/attn.core/mul" stack_frame_id=6}
  ROOT %add.4 = f32[8]{0} add(%fusion.1, %p), metadata={op_type="add" op_name="jit(step)/head/add"}
}
"""


def test_scopes_parsed_from_compiled_text():
    module, table, heads = T.parse_program(HLO)
    assert module == "jit_step"
    assert table["fusion.1"] == "jit(step)/while/body/attn.core/mul"
    assert table["add.4"] == "jit(step)/head/add"
    assert table["copy.2"] == "" and table["mul.3"] == "jit(step)/mlp/mul"


def test_scope_ns_places_ops_and_counts_the_unplaced():
    modules = [[("jit_step", 0, 100), ("jit_other", 100, 150)]]
    ops = [[("while.1", 0, 100, "container"), ("fusion.1", 0, 40, "op"),
            ("copy.2", 40, 50, "op"), ("add.4", 50, 80, "op"),
            ("fusion.7", 80, 100, "op"), ("copy.1", 100, 150, "op")]]
    tr = T.Trace(modules, ops, [("bench.window", 0, 150)])
    assert tr.scope_ns("jit_step", 0, 150) is None  # no map yet
    tr.add_program_text(HLO)
    assert tr.scope_ns("jit_step", 0, 150) == {
        "jit(step)/while/body/attn.core/mul": 40, T.UNSCOPED: 10,
        "jit(step)/head/add": 30, T.UNPLACED: 20}
    assert tr.unplaced_ops(0, 150) == {"jit_step": [["fusion.7"], 1],
                                       "jit_other": [["copy.1"], 1]}
    # a second executable of the program that disagrees leaves the op
    # unplaced; one that agrees changes nothing
    tr.add_program_text(HLO)
    assert tr.scope_ns("jit_step", 0, 150)[T.UNSCOPED] == 10
    tr.add_program_text(HLO.replace("head/add", "mlp/add"))
    assert tr.scope_ns("jit_step", 0, 150)[T.UNPLACED] == 50
    again = T.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert again.scope_ns("jit_step", 0, 150) == \
        tr.scope_ns("jit_step", 0, 150)


def test_programs_give_back_the_compiled_text_of_what_ran():
    """Every distinct call of a watched jitted function is compiled again
    from its abstract arguments, and its text holds the named scopes."""
    def step(x, n):
        with jax.named_scope("mlp"):
            x = jnp.tanh(x) * n
        with jax.named_scope("head"):
            return x.sum()

    obj = types.SimpleNamespace(_step=jax.jit(step), other=len)
    progs = T.Programs()
    progs.watch(obj)
    assert obj.other is len
    for shape in [(4,), (4,), (8,)]:
        obj._step(jnp.ones(shape), 2.0)
    assert len(progs.calls) == 2
    assert list(progs.texts({"jit_nothing"})) == []
    texts = list(progs.texts({"jit_step"}))
    assert len(texts) == 2
    paths = set(T.parse_program(texts[0])[1].values())
    assert any("/mlp/" in p for p in paths)
    assert any("/head/" in p for p in paths)


def test_programs_tell_calls_apart_by_a_static_argument():
    """Calls that differ only in a static value run different programs,
    and each is recorded; a dynamic scalar is recorded by its value too,
    which at worst compiles one program twice."""
    def scale(x, n):
        return x * n

    obj = types.SimpleNamespace(
        _scale=jax.jit(scale, static_argnums=(1,)))
    progs = T.Programs()
    progs.watch(obj)
    for n in (2, 3, 2):
        obj._scale(jnp.ones((4,)), n)
    assert len(progs.calls) == 2
    texts = list(progs.texts({"jit_scale"}))
    assert len(texts) == 2 and texts[0] != texts[1]


LOOP_HLO = """HloModule jit_loop_fn, is_scheduled=true

ENTRY %main (p: f32[8]) -> f32[8] {
  %copy.26 = f32[8]{0} copy(%p)
  %paged_slot_decode_attention.11 = f32[8]{0} custom-call(%copy.26), custom_call_target="tpu_custom_call", metadata={op_name="jit(loop_fn)/while/body/attn.core/pallas_call"}
  %dynamic-slice.3 = f32[8]{0} dynamic-slice(%p), metadata={op_name="jit(loop_fn)/while/body/dynamic_slice"}
  ROOT %fusion.110 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(loop_fn)/while/body/head/dot_general"}
}
"""


def test_decode_unscoped_ms_reads_ops_outside_every_compute_scope():
    # two runs of the loop of k = 4 steps: 8 scan steps
    modules = [[("jit_loop_fn", 0, 1000), ("jit_loop_fn", 1000, 2000)]]
    ops = [[("while.1", 0, 1000, "container"),
            ("copy.26", 0, 300, "op"),
            ("paged_slot_decode_attention.11", 300, 800, "kernel"),
            ("dynamic-slice.3", 800, 900, "op"),
            ("fusion.110", 900, 1000, "op"),
            ("copy.26", 1000, 1300, "op"),
            ("fusion.999", 1300, 1400, "op")]]
    tr = T.Trace(modules, ops, [("bench.window", 0, 2000)])
    reader = common.load_reader(common.ROOT, "decode_unscoped_ms")
    run = types.SimpleNamespace(trace=tr, tlo=0, thi=2000,
                                cell=types.SimpleNamespace(serve={"k": 4}))
    assert reader.read(run) is None  # no scope map: nothing to read
    tr.add_program_text(LOOP_HLO)
    # copies 600, the slice 100 and the unplaced fusion.999 100 ns
    assert reader.read(run) == pytest.approx(800 / 1e6 / 8)
    assert reader.read(types.SimpleNamespace(trace=None)) is None
    # the harness compiles again only the programs that readers name
    assert harness.scoped_programs(common.ROOT, [
        {"name": "decode_unscoped_ms"}, {"name": "decode_step_ms"},
        {"name": "mfu"}]) == {"jit_loop_fn"}


def test_recorded_v5e_slice_ops_take_the_scopes_of_their_compiled_text():
    """The slice's admission prefill placed by its program's compiled
    text (qwen1.5-0.5b on one TPU v5e chip, trimmed to the instructions
    the slice runs, less ``slice.18`` and ``fusion.48``)."""
    tr = _slice()
    lo, hi = tr.window()
    text = (DATA / "prefill_v5e.hlo.txt").read_text()
    _, paths, _ = T.parse_program(text)
    tr.add_program_text(text)
    by_path = tr.scope_ns("jit_prefill_fn", lo, hi)
    want = {}
    for name, s, e, kind in tr.ops[0]:
        if kind != "container" and tr.module_of(0, s) == "jit_prefill_fn":
            key = (T.UNPLACED if name not in paths
                   else paths[name] or T.UNSCOPED)
            want[key] = want.get(key, 0) + e - s
    assert by_path == want
    assert tr.unplaced_ops(lo, hi)["jit_prefill_fn"] == [
        ["fusion.48", "slice.18"], 2]
    assert sum(by_path.values()) == sum(
        e - s for _, s, e, k in tr.ops[0]
        if k != "container" and tr.module_of(0, s) == "jit_prefill_fn")
    core = sum(v for p, v in by_path.items() if "/attn.core/" in p)
    assert core >= tr.kernel_ns("jit_prefill_fn", lo, hi)[0]  # the flash
    assert by_path[T.UNPLACED] > 0 and by_path[T.UNSCOPED] > 0
    # programs without a text stay out of the map
    assert tr.scope_ns("jit_admit_fn", lo, hi) is None


def test_each_executable_of_a_program_is_told_apart_by_its_heads():
    """Two executables of one program name the same instruction
    differently; each traced run takes the one whose heads it shows."""
    small = HLO.replace("f32[8]", "f32[4]").replace("head/add", "mlp/add")
    modules = [[("jit_step", 0, 100, "jit_step(1)"),
                ("jit_step", 100, 200, "jit_step(2)")]]
    ops = [[("add.4", 50, 80, "op"), ("add.4", 150, 180, "op")]]
    heads = {"jit_step(1)": {"add.4": "%add.4 = f32[8]{0} add"},
             "jit_step(2)": {"add.4": "%add.4 = f32[4]{0} add"}}
    tr = T.Trace(modules, ops, [], heads=heads)
    tr.add_program_text(HLO)
    tr.add_program_text(small)
    assert tr.scope_ns("jit_step", 0, 100) == {"jit(step)/head/add": 30}
    assert tr.scope_ns("jit_step", 100, 200) == {"jit(step)/mlp/add": 30}
    again = T.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert again.scope_ns("jit_step", 100, 200) == {"jit(step)/mlp/add": 30}
    # without heads the two disagree, and the op is not placed
    bare = T.Trace([[m[:3] for m in modules[0]]], ops, [])
    bare.add_program_text(HLO)
    bare.add_program_text(small)
    assert bare.scope_ns("jit_step", 0, 200) == {T.UNPLACED: 60}
    # heads that no compiled text shows: that run's ops stay unplaced,
    # even where one text alone would name a path
    other = dict(heads, **{"jit_step(2)": {"add.4": "%add.4 = f32[16]{0} add"}})
    alone = T.Trace(modules, ops, [], heads=other)
    alone.add_program_text(HLO)
    assert alone.scope_ns("jit_step", 0, 100) == {"jit(step)/head/add": 30}
    assert alone.scope_ns("jit_step", 100, 200) == {T.UNPLACED: 30}
    assert alone.unplaced_ops(0, 200) == {"jit_step": [["add.4"], 1]}


def _event(meta_id, offset_ns, duration_ns):
    return (f"events {{ metadata_id: {meta_id} offset_ps: {offset_ns * 1000}"
            f" duration_ps: {duration_ns * 1000} }}")


def _meta(meta_id, name):
    name = name.replace('"', '\\"')
    return (f'event_metadata {{ key: {meta_id} value {{ id: {meta_id} '
            f'name: "{name}" }} }}')


def test_load_keeps_executables_heads_and_the_programs_spans(tmp_path):
    """An ``.xplane.pb`` as a TPU writes it: two executables of one
    program, a container and an op in each, and host spans of the
    harness, of the program and of neither."""
    xspace = f"""
    planes {{
      id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
               {_event(1, 0, 100)} {_event(2, 100, 100)} }}
      lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
               {_event(3, 0, 100)} {_event(4, 10, 30)} {_event(5, 110, 30)} }}
      {_meta(1, "jit_step(11)")} {_meta(2, "jit_step(22)")}
      {_meta(3, "%while.1 = (f32[8]{0}) while(%t), body=%b")}
      {_meta(4, "%add.4 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)")}
      {_meta(5, "%add.4 = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)")}
    }}
    planes {{
      id: 2 name: "/host:CPU"
      lines {{ id: 1 name: "python" timestamp_ns: 1000
               {_event(6, 0, 300)} {_event(7, 5, 10)} {_event(8, 20, 10)} }}
      {_meta(6, "bench.window")} {_meta(7, "serve.step")} {_meta(8, "other")}
    }}
    """
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(xspace))
    tr = T.load(str(path))
    assert tr.modules == [[("jit_step", 1000, 1100), ("jit_step", 1100, 1200)]]
    assert tr.module_ids == [["jit_step(11)", "jit_step(22)"]]
    assert [o[0] for o in tr.ops[0]] == ["while.1", "add.4", "add.4"]
    assert tr.host == [("bench.window", 1000, 1300)]
    assert tr.spans == [("serve.step", 1005, 1015)]
    assert tr.heads == {"jit_step(11)": {"add.4": "%add.4 = f32[8]{0} add"},
                        "jit_step(22)": {"add.4": "%add.4 = f32[4]{0} add"}}
    tr.add_program_text(HLO)
    tr.add_program_text(HLO.replace("f32[8]", "f32[4]")
                        .replace("head/add", "mlp/add"))
    assert tr.scope_ns("jit_step", 1000, 1300) == {
        "jit(step)/head/add": 30, "jit(step)/mlp/add": 30}
