"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
resolves to a file of its own."""
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source",
                   "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}
CELLS = [w["name"] for w in MAN["workloads"]]
E2E = {m["name"]: m for m in MAN["end_to_end"]}


def _cells_of(metric):
    return metric.get("workloads", CELLS)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = MAN["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MAN["paths"])
            assert (ROOT / word).is_file()


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_only_their_keys_and_valid_names(section):
    entries = MAN[section]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        assert set(e) <= KEYS[section], set(e) - KEYS[section]
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e and section in ("configs", "workloads", "per_layer"):
                assert _line(e[k]), (e["name"], k)


def test_configs_and_cells_resolve_to_files():
    confs = {c["name"]: c for c in MAN["configs"]}
    used = set()
    for c in MAN["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in MAN["paths"]))
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = set()
    for w in MAN["workloads"]:
        assert w["config"] in confs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "cells" / f"{w['name']}.json").is_file()
    assert used == set(confs)
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_every_metric_has_a_reader():
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        for c in m.get("workloads", []):
            assert c in CELLS


def test_bounds_and_sources_of_end_to_end_metrics():
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_each_cell_reports_what_its_layer_metrics_move():
    for m in MAN["per_layer"]:
        moves = E2E[m["moves"]]
        for c in _cells_of(m):
            assert c in _cells_of(moves), (m["name"], c, m["moves"])
    layers = {}
    for m in MAN["per_layer"]:
        layers.setdefault(m["layer"], []).append(m["name"])
    for c in CELLS:
        e2e = [m["name"] for m in MAN["end_to_end"] if c in _cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(c in _cells_of(m) for m in MAN["per_layer"])


def test_a_full_check_fits_its_budget_at_24_cells():
    s = MAN["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    runs = 2 + 14 * 24
    assert runs * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len(json.dumps(MAN)) <= 64 * 1024
