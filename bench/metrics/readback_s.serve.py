"""Host seconds a serving batch spends reading its tokens back: the mean
length of the program's ``serve.readback`` span over the window's
batches, aligned to the trace by ``bench/program_spans.py``. The span
starts once the batch's last token is ready, so it times the join and the
transfer to Python ints, not the wait for queued decode steps."""
from bench import program_spans


def read(ctx):
    d = program_spans.durations_s(ctx, "serve.readback")
    return None if d is None else sum(d) / len(d)
