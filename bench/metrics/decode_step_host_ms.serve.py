"""Host milliseconds of one decode step: the median length of the
program's ``serve.decode_step`` span (key split, decode dispatch, sample)
over the window's steps, aligned to the trace by
``bench/program_spans.py``. While the host waits for each step's logits
before it dispatches the next, this is the device's step time; once the
host runs ahead it falls to the host's own cost of a step."""
import statistics

from bench import program_spans


def read(ctx):
    d = program_spans.durations_s(ctx, "serve.decode_step")
    return None if d is None else 1e3 * statistics.median(d)
