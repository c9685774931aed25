"""DeepSeek-V3 at one chip's share: the serving paths against the plain
reference (bench/reference/deepseek_v3.py), on the smoke twin with
seeded random weights drawn as the benchmark draws them.

Tolerance: the smoke twin computes in float32, as does the reference, so
the serving paths differ from it only by the order of accumulation (and
the reference's unabsorbed attention against the served absorbed one):
under 1e-5 of a logit here.  LOGIT_TOL = 1e-4 leaves that room, and the
reference computed in bfloat16 misses it by far more (checked below), so
a lower precision than the configuration's fails it.
"""
import copy
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import common, harness, weights
from repro.configs.base import get_config
from repro.models import get_family
from repro.serve import ContinuousBatchingEngine, Request

ROOT = pathlib.Path(__file__).resolve().parents[2]
CONFIG = json.loads(
    (ROOT / "bench/configs/deepseek-v3-ep32.json").read_text())
LOGIT_TOL = 1e-4
MAX_LEN = 48


def smoke_config():
    """The configuration file with the smoke twin's sizes (its program is
    ``deepseek-v3-ep32-smoke``): every other key as the file has it."""
    c = copy.deepcopy(CONFIG)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=128, moe_intermediate_size=64,
             vocab_size=128, experts_routed_over=8, n_routed_experts=2,
             num_experts_per_tok=2, n_group=4, topk_group=2, q_lora_rank=32,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, num_hidden_layers=3, torch_dtype="float32",
             program={"arch": "deepseek-v3-ep32-smoke",
                      "decode_kernel": "jnp"})
    return c


@pytest.fixture(scope="module")
def model():
    ref = common.load_reference(ROOT, CONFIG)
    spec = ref.describe(smoke_config())
    cfg = get_config("deepseek-v3-ep32-smoke")
    harness._same_widths(cfg, ref.program_widths(spec))
    fam = get_family(cfg)
    shapes = jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), cfg))
    params = weights.make_params(shapes, weights.seed_key(2**33 + 17))
    return ref, spec, cfg, params


def test_published_widths_are_the_programs():
    ref = common.load_reference(ROOT, CONFIG)
    spec = ref.describe(CONFIG)
    harness._same_widths(get_config("deepseek-v3-ep32"),
                         ref.program_widths(spec))
    assert (spec["routed_over"], spec["held"], spec["vocab"]) == \
        (256, 8, 129280)


def _reqs(cfg):
    rng = np.random.default_rng(5)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, n,
                                               dtype=np.int32),
                    max_new_tokens=12)
            for i, n in enumerate((5, 13, 9))]


def test_paged_engine_logits_match_the_reference(model):
    """Admission prefill, then decode through the paged pool (teacher-
    forced with the reference's own greedy tokens), against the
    reference's full forward pass on logits."""
    ref, spec, cfg, params = model
    fam = get_family(cfg)
    reqs = _reqs(cfg)
    eng = ContinuousBatchingEngine(cfg, params, capacity=4, max_len=MAX_LEN,
                                   prefill_bucket=8, k=4, pool="paged")
    assert eng.pool_kind == "paged"
    for r in reqs:
        eng.submit(r)
    eng._admit_batch(None)
    slot = {seq.req.uid: s for s, seq in eng.active.items()}
    # the reference's greedy continuation of each prompt, and its logits
    steps = 8
    seqs, want = {}, {}
    for r in reqs:
        toks = list(r.prompt)
        for _ in range(steps + 1):
            lg = np.asarray(ref.logits_at(spec, params, toks,
                                          [len(toks) - 1]))[0]
            toks.append(int(lg.argmax()))
        seqs[r.uid] = toks
        pos = np.arange(len(r.prompt) - 1, len(toks) - 1)
        want[r.uid] = np.asarray(ref.logits_at(spec, params, toks[:-1],
                                               pos))
    # admission: the first token is the reference's argmax, within the
    # tolerance where the top two are that close
    for r in reqs:
        first = eng.active[slot[r.uid]].tokens[0]
        w = want[r.uid][0]
        assert w.max() - w[first] <= LOGIT_TOL
    step = jax.jit(fam.decode_step_slots, static_argnums=4)
    pool = eng._pools[0]
    cap = eng.capacity
    done = np.ones(cap, bool)
    for r in reqs:
        done[slot[r.uid]] = False
    worst = 0.0
    for j in range(steps):
        toks = np.zeros(cap, np.int32)
        pos = np.zeros(cap, np.int32)
        for r in reqs:
            toks[slot[r.uid]] = seqs[r.uid][len(r.prompt) + j]
            pos[slot[r.uid]] = len(r.prompt) + j
        logits, pool = step(params, jnp.asarray(toks), jnp.asarray(pos),
                            pool, cfg, jnp.asarray(done))
        for r in reqs:
            got = np.asarray(logits[slot[r.uid]])
            worst = max(worst, float(np.abs(got - want[r.uid][j + 1]).max()))
    assert worst <= LOGIT_TOL, worst
    # the bfloat16 reference in the float32 one's place fails the check
    r = reqs[1]
    pos = np.arange(len(r.prompt) - 1, len(seqs[r.uid]) - 1)
    low = np.asarray(ref.logits_at(spec, params, seqs[r.uid][:-1], pos,
                                   "bfloat16"))
    assert float(np.abs(low - want[r.uid]).max()) > 10 * LOGIT_TOL


def _dsv3_root(tmp_path):
    """A tiny benchmark root with the DeepSeek family as files: the smoke
    configuration, a reasoning-shaped mix and a cell."""
    root = bench_tiny.make_root(tmp_path)
    (root / "bench/configs/dsv3-tiny.json").write_text(
        json.dumps(smoke_config()))
    traffic = json.loads(
        (ROOT / "bench/traffic/reason-closed.json").read_text())
    traffic.update(prompt=dict(traffic["prompt"], median=12, min=6, max=20),
                   output=dict(traffic["output"], median=10, min=4, max=16),
                   block=8)
    (root / "bench/traffic/reason-tiny.json").write_text(json.dumps(traffic))
    (root / "bench/cells/dsv3-tiny.json").write_text(json.dumps(
        {"capacity": 2, "max_len": 40, "prefill_bucket": 16, "k": 4,
         "pool": "paged", "limits": {"gap_mean_req": 1e-4}}))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "dsv3-tiny", "source": "test",
                           "file": "bench/configs/dsv3-tiny.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "dsv3-tiny", "config": "dsv3-tiny",
                             "traffic": "reason-tiny", "chips": 1,
                             "why": "test"})
    for m in man["per_layer"]:
        if m["name"].startswith("moe_"):
            m["workloads"] = ["dsv3-tiny"]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def test_a_run_of_the_family_is_correct_and_counts_expert_work(tmp_path):
    """The harness drives the smoke twin through the engine from the
    family's files: the run is correct by the reference, and the
    family's useful work grows with the keys attended."""
    root = _dsv3_root(tmp_path)
    cell = common.Cell(root, "dsv3-tiny")
    res = harness.run_cell(root, "dsv3-tiny", 2**32 + 3, 1.0, False,
                           require_chip=False)
    assert res["correct"] is True, res["check"]
    assert res["check"]["tokens"]["value"] > 0
    spec = cell.reference.describe(cell.config)
    assert cell.reference.decode_flops(spec, 100) > \
        cell.reference.decode_flops(spec, 10) > 0
    assert cell.reference.prefill_flops(spec, 20) > 0


def test_expert_readers_cover_the_blocks_read_back():
    """The readers take device time over the first ``n_blocks_read`` loop
    runs of the capture (ops under ``moe.experts`` plus XLA's
    grouped-matmul kernels), scale the counters where the profiler kept
    fewer runs, and return nothing on a program without the counters or
    a capture without a device."""
    import types

    from bench import experts
    from bench.trace import Trace

    modules = [[("jit_loop_fn", 100, 200, "jit_loop_fn(1)"),
                ("jit_loop_fn", 300, 400, "jit_loop_fn(1)"),
                ("jit_loop_fn", 500, 600, "jit_loop_fn(1)")]]
    ops = [[("ragged-dot-none.1", 110, 130, "kernel"),
            ("fusion.1", 130, 140, "op"), ("fusion.2", 140, 150, "op"),
            ("ragged-dot-none.1", 310, 330, "kernel"),
            ("fusion.1", 330, 340, "op"),
            ("ragged-dot-none.1", 510, 590, "kernel")]]
    tr = Trace(modules, ops, [("bench.window", 50, 700)])
    tr.scopes["jit_loop_fn(1)"] = {
        "ragged-dot-none.1": "ragged-dot-none",
        "fusion.1": "jit(loop_fn)/while/body/mlp/moe.experts/mul",
        "fusion.2": "jit(loop_fn)/while/body/mlp/moe.route/sort"}
    cell = types.SimpleNamespace(serve={"k": 4})
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = types.SimpleNamespace(
        trace=tr, tlo=50, thi=700, cell=cell,
        counters={"n_blocks_read": 2, "n_moe_pairs": 80,
                  "n_moe_experts_hit": 10},
        model={"d_model": 7168, "expert_ff": 2048}, value_bytes=2,
        peak=peak)
    # two blocks read back; the third run was in flight
    assert experts.device_ns(run) == (20 + 10 + 20 + 10, 8, 1.0)
    ms = common.load_reader(ROOT, "moe_expert_ms").read(run)
    assert ms == pytest.approx(60 / 1e6 / 8)
    share = common.load_reader(ROOT, "moe_expert_roofline").read(run)
    least = 10 * 3 * 7168 * 2048 * 2 / 819e9
    assert share == pytest.approx(100 * least / 60e-9)
    # the profiler kept 3 of 4 runs read back: the counters are scaled
    run.counters = dict(run.counters, n_blocks_read=4)
    assert experts.device_ns(run) == (60 + 80, 12, 0.75)
    share = common.load_reader(ROOT, "moe_expert_roofline").read(run)
    assert share == pytest.approx(100 * least * 0.75 / 140e-9)
    run.counters = {"n_tokens": 5}  # a program without the counters
    assert experts.device_ns(run) is None
    run.counters = {"n_blocks_read": 2}
    run.trace = Trace([], [], [("bench.window", 50, 700)])  # no device
    assert experts.device_ns(run) is None
