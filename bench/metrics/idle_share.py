"""Share of the traced window in which no operation ran on the device."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    busy = run.trace.busy_ns(run.tlo, run.thi)
    return 100.0 * (1.0 - busy / (run.thi - run.tlo))
