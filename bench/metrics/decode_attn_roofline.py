"""Paged slot-decode kernel: least time for the keys and values of the
positions actually decoded (bytes at HBM bandwidth, or FLOPs at peak)
over the kernel's device time in the macro decode loop."""
from bench import flops, work


def read(run):
    if run.trace is None or not run.peak:
        return None
    ns, calls = run.trace.kernel_ns("jit_loop_fn", run.tlo, run.thi)
    f = b = 0
    for keys in work.decode_tokens(run):
        df, db = flops.decode_attn_cost(run.model, keys,
                                        run.value_bytes)
        f, b = f + df, b + db
    if not calls or not b:
        return None
    return 100.0 * flops.roofline_share(f, b, ns / 1e9, run.peak)
