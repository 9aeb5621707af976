"""Share of its roofline that the decode program (``jit(_decode)``, one
token for every row of the batch) reaches: the least time its needed
work takes (weights once, the K/V of the positions in context, the new
K/V written) over its device time per call in the trace, in percent."""
from bench.roofline import least_seconds


def read(ctx):
    c = ctx.counts
    name = c.get("decode_program")
    if not name or not c.get("decode_calls"):
        return None
    runs = ctx.trace.module_runs(lambda n: n == "jit_" + name)
    if not runs:
        return None
    need = least_seconds(c["decode_needed_flops"], c["decode_needed_bytes"],
                         ctx.peaks) / c["decode_calls"]
    return 100.0 * len(runs) * need / (sum(e.dur_ns for e in runs) * 1e-9)
