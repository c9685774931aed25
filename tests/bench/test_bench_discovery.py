"""A cell, a configuration, a traffic mix or a metric is added by adding
files and entries: the harness finds each by its name, and no existing
file is edited."""
import json

import bench_tiny
from bench import common, harness

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
