"""Whole runs of the serving cells on the CPU at test sizes (the harness's
look for a chip skipped): correct as served, and not correct with a token
altered where it is produced."""
import json

import pytest

import bench_faults
import bench_tiny
from bench import harness

SEED = 2**31 + 99


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, seconds=2.0, traced=False):
    return harness.run_cell(root, cell, SEED, seconds, traced,
                            require_chip=False)


def test_chat_cell_is_correct_and_keeps_the_result_contract(root):
    res = _run(root, "qwen-chat-batch")
    assert res["correct"] is True, res["check"]
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "check"
    assert res["failed"] == 0 and res["attempted"] > 4
    assert set(res["metrics"]) == {"tok_s", "tpot_mean_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["check"]["gap_mean"]["value"] <= res["check"]["gap_mean"]["limit"]
    json.dumps(res)


def test_chat_cell_with_an_altered_token_is_not_correct(root, monkeypatch):
    bench_faults.alter_tokens(monkeypatch)
    res = _run(root, "qwen-chat-batch")
    assert res["correct"] is False
    assert res["check"]["gap_mean"]["value"] > res["check"]["gap_mean"]["limit"]


def test_longdoc_traced_run_reports_only_what_the_cpu_can_read(root):
    res = _run(root, "qwen-longdoc", traced=True)
    assert res["correct"] is True, res["check"]
    # the per-layer metrics of a traced run; device readings need a chip
    assert set(res["metrics"]) == {"compile_s", "host_syncs_per_tok"}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "window_s" in res["device"]


def test_no_chip_no_result(root):
    with pytest.raises(harness.NoChip):
        harness.run_cell(root, "qwen-chat-batch", SEED, 1.0, False)
