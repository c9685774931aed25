"""A copy of the benchmark's data files at CPU-test sizes.

``make_root(dst)`` writes ``BENCHMARK.json`` and ``bench/`` data (configs,
traffic, cells; the metric readers and the reference modules are copied
as they are) into ``dst``,
with each configuration swapped for the repository's smoke-size twin and
the Pallas kernels for the jnp path, so the harness can drive a whole
run on the CPU.
"""
import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

QWEN = {
    "model_type": "qwen2", "hidden_size": 64, "intermediate_size": 160,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 2, "vocab_size": 256,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0, "tie_word_embeddings": True,
    "hidden_act": "silu", "torch_dtype": "float32",
    "program": {"arch": "qwen1.5-0.5b-smoke", "decode_kernel": "jnp"},
    "reference": "bench/reference/transformer.py",
}


TRAFFIC = {
    "chat-closed": {"loop": "closed", "clients_per_slot": 2,
                    "prompt": {"dist": "lognormal", "median": 24,
                               "sigma": 0.8, "min": 8, "max": 40},
                    "output": {"dist": "lognormal", "median": 6,
                               "sigma": 0.5, "min": 2, "max": 10},
                    "block": 8},
    "longdoc-open": {"loop": "open", "rate_per_s": 4.0,
                     "prompt": {"dist": "uniform", "min": 40, "max": 60},
                     "output": {"dist": "uniform", "min": 2, "max": 6}},
}

CELLS = {
    "qwen-chat-batch": {"capacity": 2, "max_len": 64, "prefill_bucket": 32,
                        "k": 4, "pool": "paged", "limits": {"gap_mean": 1e-3}},
    "qwen-longdoc": {"capacity": 2, "max_len": 72, "prefill_bucket": 64,
                     "k": 4, "pool": "paged", "limits": {"gap_mean": 1e-3}},
}


# both kinds of loop the harness drives, listed here too so the CPU tests
# cover the open loop, which no cell of BENCHMARK.json measures yet
KINDS = {
    "configs": [{"name": "qwen1.5-0.5b", "source": "test",
                 "file": "bench/configs/qwen1.5-0.5b.json", "reduced": [],
                 "why": "test"}],
    "workloads": [{"name": "qwen-chat-batch", "config": "qwen1.5-0.5b",
                   "traffic": "chat-closed", "chips": 1, "why": "test"},
                  {"name": "qwen-longdoc", "config": "qwen1.5-0.5b",
                   "traffic": "longdoc-open", "chips": 1, "why": "test"}],
}


def make_root(dst):
    dst = pathlib.Path(dst)
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    for section, entries in KINDS.items():
        have = {e["name"] for e in man[section]}
        man[section] += [e for e in entries if e["name"] not in have]
    (dst / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (dst / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    (dst / "bench" / "cells").mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "reference"):
        shutil.copytree(REPO / "bench" / sub, dst / "bench" / sub,
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for c in man["configs"]:
        (dst / c["file"]).write_text(json.dumps(QWEN))
    for name, body in TRAFFIC.items():
        (dst / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(body))
    for name, body in CELLS.items():
        (dst / "bench" / "cells" / f"{name}.json").write_text(
            json.dumps(body))
    (dst / "BENCHMARK.json").write_text(json.dumps(man))
    return dst
