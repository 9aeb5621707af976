"""Programs JAX made inside the measured window, compiled by the backend
or read from the persistent cache (the benchmark's own listener,
``bench/compiles.py``). Every shape is warmed in set-up, so it is 0
unless the server makes new shapes while it serves."""


def read(ctx):
    return ctx.counts.get("window_compiles")
