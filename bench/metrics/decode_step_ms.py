"""Device time of the macro decode loop program per scan step."""


def read(run):
    if run.trace is None:
        return None
    ns, runs = run.trace.program_ns("jit_loop_fn", run.tlo, run.thi)
    if not runs:
        return None
    return ns / 1e6 / (runs * run.cell.serve["k"])
