"""Roofline shares from a reduced trace and the needed work the driver
counted from shapes (``bench/counts``): the least time the chip could
take, the larger of flops over peak flop/s and bytes over peak bytes/s,
over the device time the trace measured."""
from __future__ import annotations


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def kernel_share(ctx, kernel: str):
    """Percent of the roofline that the ops named ``kernel`` (``kernel``
    or ``kernel.<n>``) reach; None where the trace holds none of them or
    the driver counted another kernel."""
    if ctx.counts.get("kernel") != kernel:
        return None
    runs = [e for e in ctx.trace.ops
            if e.name == kernel or e.name.startswith(kernel + ".")]
    if not runs:
        return None
    need = least_seconds(ctx.counts["needed_flops"],
                         ctx.counts["needed_bytes"], ctx.peaks)
    return 100.0 * len(runs) * need / (sum(e.dur_ns for e in runs) * 1e-9)
