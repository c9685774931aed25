"""Device time per decode scan step of the expert layer's grouped
matmuls in the macro decode loop (bench/experts.py)."""
from bench import experts

# the programs whose ops this reader reads by scope
SCOPE_MAP = (experts.PROGRAM,)


def read(run):
    got = experts.device_ns(run)
    if got is None or not got[0]:
        return None
    ns, steps, _ = got
    return ns / 1e6 / steps
