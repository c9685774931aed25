"""Useful model FLOPs in the traced window (prompt and delivered tokens
through the model) over window x bf16 peak."""
from bench import work


def read(run):
    if run.trace is None or not run.peak:
        return None
    total = work.useful_flops(run)
    if not total:
        return None
    return 100.0 * total / (run.trace_window_s
                            * run.peak["bf16_flops_per_s"])
