"""A cell, a configuration, a traffic mix, a metric or a model family is
added by adding files and entries: the harness finds each by its name,
and no existing file is edited."""
import json
import pathlib

import pytest

import bench_tiny
from bench import common, harness

HERE = pathlib.Path(__file__).resolve().parent

# qwen3-0.6b-smoke as a configuration file of the qwen3 family: q/k
# RMSNorm, GQA 2:1, head_dim 32 where hidden_size / heads is 16
QWEN3 = {
    "model_type": "qwen3", "hidden_size": 64, "intermediate_size": 160,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "num_hidden_layers": 2, "vocab_size": 256,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0, "tie_word_embeddings": True,
    "hidden_act": "silu", "torch_dtype": "float32",
    "program": {"arch": "qwen3-0.6b-smoke", "decode_kernel": "jnp"},
    "reference": "bench/reference/qwen3.py",
}

READER = '''"""Prefill dispatches per admitted request (engine counters)."""


def read(run):
    c = run.counters
    return c["n_prefills"] / c["n_admitted"] if c["n_admitted"] else None
'''


def test_new_files_and_entries_make_a_new_cell(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    man = json.loads((root / "BENCHMARK.json").read_text())
    # a new configuration file, a new traffic mix, a new cell, a metric
    conf = dict(bench_tiny.QWEN, name="qwen-tiny-b")
    (root / "bench/configs/qwen-tiny-b.json").write_text(json.dumps(conf))
    traffic = dict(bench_tiny.TRAFFIC["longdoc-open"], rate_per_s=6.0)
    (root / "bench/traffic/short-open.json").write_text(json.dumps(traffic))
    (root / "bench/cells/tiny-short.json").write_text(
        json.dumps(bench_tiny.CELLS["qwen-longdoc"]))
    (root / "bench/metrics/prefills_per_req.py").write_text(READER)
    man["configs"].append({"name": "qwen-tiny-b", "source": "test",
                           "file": "bench/configs/qwen-tiny-b.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tiny-short", "config": "qwen-tiny-b",
                             "traffic": "short-open", "chips": 1,
                             "why": "test"})
    man["per_layer"].append({"name": "prefills_per_req", "unit": "count",
                             "better": "lower", "source": "program_counter",
                             "layer": "admission", "moves": "tok_s",
                             "workloads": ["tiny-short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    cell = common.Cell(root, "tiny-short")
    assert cell.traffic["rate_per_s"] == 6.0
    assert "prefills_per_req" in [m["name"] for m in cell.per_layer]
    res = harness.run_cell(root, "tiny-short", 4, 1.0, True,
                           require_chip=False)
    assert res["correct"] is True, res["check"]
    assert 0 < res["metrics"]["prefills_per_req"]["value"] <= 1
    # nothing that was there before was edited
    for p, body in before.items():
        assert p.read_bytes() == body, p


def _add_qwen3_family(root, conf):
    """New files and entries only: the family's reference module, its
    configuration file and a cell that serves it."""
    (root / "bench/reference/qwen3.py").write_text(
        (HERE / "qwen3_reference.py").read_text())
    (root / "bench/configs/qwen3-tiny.json").write_text(json.dumps(conf))
    (root / "bench/cells/qwen3-chat.json").write_text(
        json.dumps(bench_tiny.CELLS["qwen-chat-batch"]))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "qwen3-tiny", "source": "test",
                           "file": "bench/configs/qwen3-tiny.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "qwen3-chat", "config": "qwen3-tiny",
                             "traffic": "chat-closed", "chips": 1,
                             "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def test_a_second_model_family_is_new_files_only(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    _add_qwen3_family(root, QWEN3)
    cell = common.Cell(root, "qwen3-chat")
    assert cell.reference.describe(cell.config)["head_dim"] == 32
    res = harness.run_cell(root, "qwen3-chat", 2**31 + 5, 1.0, False,
                           require_chip=False)
    assert res["correct"] is True, res["check"]
    assert res["check"]["tokens"]["value"] > 0
    assert all(m["value"] > 0 for m in res["metrics"].values())
    for p, body in before.items():
        assert p.read_bytes() == body, p


def test_widths_that_disagree_with_the_program_are_refused(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    _add_qwen3_family(root, dict(QWEN3, head_dim=16))
    with pytest.raises(ValueError, match="head_dim"):
        harness.run_cell(root, "qwen3-chat", 7, 1.0, False,
                         require_chip=False)
