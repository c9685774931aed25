"""Device time per scan step of the macro decode loop's ops that sit in
none of the program's compute scopes: state movement (layout
conversions, copies, slices of stacked weights, the loop's carry).  An
op the scope map cannot place counts here too."""

# the compute scopes of the decode loop (the program's
# repro.serve.tracing.SCOPES as this metric was admitted); a copy, so
# that a scope added later does not move what the metric counts
SCOPES = frozenset(("embed", "attn.qkv", "attn.kv_write", "attn.core",
                    "attn.out", "mlp", "head", "select"))

# the programs whose ops this reader reads by scope: the harness compiles
# these again after a traced window for the scope map
SCOPE_MAP = ("jit_loop_fn",)


def read(run):
    if run.trace is None:
        return None
    by_path = run.trace.scope_ns("jit_loop_fn", run.tlo, run.thi)
    _, runs = run.trace.program_ns("jit_loop_fn", run.tlo, run.thi)
    if by_path is None or not runs:
        return None
    ns = sum(v for path, v in by_path.items()
             if SCOPES.isdisjoint(path.split("/")))
    return ns / 1e6 / (runs * run.cell.serve["k"])
