"""Expert layer in the macro decode loop: least time for the work the
engine counted (held experts' weights at HBM bandwidth, or the pairs'
FLOPs at peak) over its device time on the same blocks
(bench/experts.py)."""
from bench import experts, flops

# the programs whose ops this reader reads by scope
SCOPE_MAP = (experts.PROGRAM,)


def read(run):
    got = experts.device_ns(run)
    if got is None or not got[0] or not run.peak:
        return None
    ns, _, share = got
    c = run.counters
    f, b = experts.cost(run.model, c["n_moe_pairs"] * share,
                        c["n_moe_experts_hit"] * share, run.value_bytes)
    return 100.0 * flops.roofline_share(f, b, ns / 1e9, run.peak)
