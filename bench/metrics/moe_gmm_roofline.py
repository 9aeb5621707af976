"""Share of its roofline that the held experts' grouped matmul reaches in
decode: the ``moe_gmm`` kernel's ops that run inside the decode program
(``jit(_decode)``), against the least time their needed work takes. The
work comes from the decode calls' routing counters
(``bench/counts/mla_moe.py``): 6 d f flops for each pair the held experts
computed, and the bytes of each active expert's three matrices once a
layer plus the pairs' rows in and out. Per decode call, needed over
measured, in percent; None where the trace or the counts hold none."""
from bench.roofline import least_seconds

KERNEL = "moe_gmm"


def read(ctx):
    c = ctx.counts
    name = c.get("decode_program")
    if not name or not c.get("decode_calls") or \
            not c.get("moe_gmm_needed_bytes"):
        return None
    runs = ctx.trace.module_runs(lambda n: n == "jit_" + name)
    ops = [e for e in ctx.trace.ops
           if (e.name == KERNEL or e.name.startswith(KERNEL + "."))
           and any(r.plane == e.plane and r.start_ns <= e.start_ns < r.end_ns
                   for r in runs)]
    if not ops:
        return None
    need = least_seconds(c["moe_gmm_needed_flops"],
                         c["moe_gmm_needed_bytes"],
                         ctx.peaks) / c["decode_calls"]
    return 100.0 * len(runs) * need / (sum(e.dur_ns for e in ops) * 1e-9)
