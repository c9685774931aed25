"""Readings that the correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--control 3]

In one process (set-up paid once): for each seed, one window of the
cell at its own load, then the numbers the cell compares — for the
program as served and, on the first ``--control`` seeds, for the
control (the reference one precision step below the configuration's, in
the program's place, on the same sample), each with the verdict that the
cell's own limits give it.  One JSON line per seed on stdout.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)

    import jax

    from bench import common, harness

    cell = common.Cell(ROOT, args.workload)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_cache(ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        t0 = time.monotonic()
        sess = harness.Session(cell, seed, args.seconds)
        if i == 0:
            sess.setup()
        t_setup = time.monotonic()
        w, _ = harness.run_window(sess, args.seconds, False, ROOT)
        t1 = time.monotonic()
        limits = cell.serve["limits"]
        ok, numbers = harness.compare(sess, w["rec"], limits)
        t2 = time.monotonic()
        rec = {"seed": seed, "correct": ok, "program": numbers,
               "window_programs": w["window_programs"],
               "attempted": len(w["rec"].measured()),
               "setup_s": t_setup - t0, "window_s": t1 - t_setup,
               "reference_s": t2 - t1}
        if i < args.control:
            # the control judged by the cell's own limits: it has to come
            # out not correct
            rec["control_correct"], rec["control"] = harness.compare(
                sess, w["rec"], limits, control=True)
        print(json.dumps(rec), flush=True)
        del sess, w
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
