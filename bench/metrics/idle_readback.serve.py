"""Percent of the traced window in which the device is idle while the
serving program reads a batch's tokens back: the part of the window
inside an aligned ``serve.readback`` span and outside every device op,
over the window (``bench/program_spans.py``). At most
``device_idle.serve``."""
from bench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "serve.readback")
