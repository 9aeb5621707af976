"""Share of its roofline that the ``longrange3d`` Pallas kernel reaches:
the least time one leapfrog step's needed bytes (U, V, ROC read once, U
written once) and flops take at the chip's peaks, over the kernel's
device time per call in the trace. Bound by bytes, by about 100x."""
from bench.roofline import kernel_share


def read(ctx):
    return kernel_share(ctx, "longrange3d")
