"""Manifest and file discovery: everything a cell needs is found by name.

``BENCHMARK.json`` names the cells, configurations, traffic mixes and
metrics.  Each of those is a file of its own under ``bench/``:

  configs/<config>.json     sizes as run, source, reference, program arch
  traffic/<traffic>.json    parameters of the general traffic generator
  cells/<workload>.json     serving geometry of one cell and its limits
  metrics/<metric>.py       a reader: ``read(run) -> float | None``;
                            ``SCOPE_MAP``, where it has one, names the
                            programs whose ops it reads by scope
  reference/<family>.py     the plain reference of a model family, named
                            by the configuration's ``"reference"`` key

A new cell, metric or model family is new files plus new entries; no
existing file is edited.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path):
    return json.loads(pathlib.Path(path).read_text())


def manifest(root=ROOT):
    return load_json(pathlib.Path(root) / "BENCHMARK.json")


def bench_dir(root=ROOT):
    return pathlib.Path(root) / "bench"


class Cell:
    """One workload of the manifest with its files resolved."""

    def __init__(self, root, name):
        man = manifest(root)
        cells = {w["name"]: w for w in man["workloads"]}
        if name not in cells:
            raise KeyError(f"unknown workload {name!r}; known: "
                           f"{sorted(cells)}")
        w = cells[name]
        confs = {c["name"]: c for c in man["configs"]}
        base = bench_dir(root)
        self.root = pathlib.Path(root)
        self.name = name
        self.chips = int(w["chips"])
        self.config_name = w["config"]
        self.traffic_name = w["traffic"]
        self.config = load_json(pathlib.Path(root) /
                                confs[w["config"]]["file"])
        self.traffic = load_json(base / "traffic" / f"{w['traffic']}.json")
        self.serve = load_json(base / "cells" / f"{name}.json")
        self.end_to_end = _applying(man["end_to_end"], name)
        self.per_layer = _applying(man["per_layer"], name)
        self.reference = load_reference(root, self.config)


def _applying(metrics, cell):
    """Metrics reported in ``cell``: those listing it, or listing none."""
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ident(name):
    return name.replace(".", "_").replace("-", "_")


def load_reader(root, metric):
    """The reader module of ``metric`` (bench/metrics/<metric>.py)."""
    return _load_module(bench_dir(root) / "metrics" / f"{metric}.py",
                        f"bench_metric_{_ident(metric)}")


def load_reference(root, config):
    """The reference module that ``config["reference"]`` names (a path
    from the checkout root, ``bench/reference/<family>.py``).  It imports
    nothing of the program and provides:

      describe(config) -> spec
          a plain dict of the model from its configuration file, holding
          at least ``dtype`` (the served precision, e.g. "bfloat16") and
          ``vocab``
      program_widths(spec) -> {ModelConfig field: value}
          what the program's configuration must hold for it to serve
          this model; checked before any weights are made
      logits_at(spec, params, tokens, positions, precision="float32")
          float32 logits (len(positions), vocab) of the prefixes of one
          sequence ending at each position, reading the weights in the
          program's parameter layout; ``precision`` may name the step
          below the served one (check.LOWER) for the control
      prefill_flops(spec, n), decode_flops(spec, kv_len)
          the useful operations of an ``n``-token prompt and of one
          token that attends ``kv_len`` keys (a multiply-add is 2)
    """
    path = pathlib.Path(root) / config["reference"]
    return _load_module(path, f"bench_reference_{_ident(path.stem)}")
