"""The expert layer of the macro decode loop, for its readers: device
time, useful work and least time.

The engine reads back, with each plain macro block's tokens, the expert
layers' counts summed over the block's steps and layers
(``n_moe_pairs``: token-expert pairs computed on this chip,
``n_moe_experts_hit``: held experts that got at least one pair) and
counts the blocks (``n_blocks_read``).  The device time is taken over
those blocks (``device_ns``).

The layer's ops are those under the ``moe.experts`` scope, and the
grouped-matmul kernels that XLA expands ``jax.lax.ragged_dot`` into on
the TPU, whose op_name is XLA's own (``ragged-dot-*``), not the scope's.
"""
from __future__ import annotations

PROGRAM = "jit_loop_fn"
SCOPE = "moe.experts"
KERNEL_PREFIX = "ragged-dot"


def is_expert_op(path):
    return path.startswith(KERNEL_PREFIX) or SCOPE in path.split("/")


def device_ns(run):
    """(device ns of the expert ops, scan steps, share of the read-back
    blocks that the ns cover), or None where there is nothing to read (no
    trace, a program without the counters, no scope map).

    The capture holds the measured engine's loop runs alone, in order,
    the run still in flight when the window closed (never read back)
    last: the ns cover the first ``n_blocks_read`` of them.  Where the
    profiler kept fewer runs than that (it kept 621 of 686 in a 109 s
    window on one v5e), the ns cover the runs it kept, and the share
    scales the counters to them."""
    if run.trace is None:
        return None
    blocks = run.counters.get("n_blocks_read")
    if not blocks:
        return None
    modules = run.trace.modules[0] if run.trace.modules else []
    runs = [(s, e) for name, s, e in modules
            if name.startswith(PROGRAM)][:blocks]
    if not runs:
        return None
    by_path = run.trace.scope_ns(PROGRAM, runs[0][0], runs[-1][1])
    if by_path is None:
        return None
    ns = sum(v for path, v in by_path.items() if is_expert_op(path))
    return ns, len(runs) * run.cell.serve["k"], len(runs) / blocks


def cost(spec, pairs, hit, value_bytes):
    """(flops, bytes) the expert layer needs for ``pairs`` token-expert
    pairs over ``hit`` distinct experts: three (d_model x expert_ff)
    matmuls a pair (a multiply-add is 2), each hit expert's three weight
    matrices read once."""
    D, F = spec["d_model"], spec["expert_ff"]
    return pairs * 6 * D * F, hit * 3 * D * F * value_bytes
