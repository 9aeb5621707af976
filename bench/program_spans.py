"""The serving program's own spans (``repro.obs``) on a trace's clock.

The program keeps each span in memory, timed by ``perf_counter_ns``; the
benchmark's trace holds only its own ``bench.*`` host spans. Each of the
window's ``bench.batch`` spans wraps one ``serve`` call, which makes one
engine batch (``serve.batch``) when the mix sends ``clients`` =
``batch`` requests at a time. So the last K program batches pair, in
order, with the trace's K ``bench.batch`` spans, and each is shifted by
its own pair's start offset (the two start microseconds apart). A
program batch that then ends more than :data:`SLACK_NS` after its pair
was not made inside it, and nothing is aligned.

Where the program records no spans (a program without ``repro.obs``) or
the trace has no device, every reader here returns None.
"""
from __future__ import annotations

import bisect

from bench import trace

BATCH = "serve.batch"
BENCH_BATCH = "bench.batch"
#: how far a shifted program batch may end after its ``bench.batch``
SLACK_NS = 1e6


def records():
    """The program's span records, oldest first; None without them."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.spans()


def aligned(ctx):
    """The window's program batches on the trace's clock: one list per
    ``bench.batch`` span, in order, of the ``serve.batch`` record and every
    record inside it, each shifted and clipped to the ``bench.batch``
    span. None where nothing can be aligned."""
    if ctx.trace.n_devices == 0:
        return None
    recs = records()
    if not recs:
        return None
    outer = sorted((s for s in ctx.trace.spans if s.name == BENCH_BATCH),
                   key=lambda s: s.start_ns)
    prog = sorted((r for r in recs if r.name == BATCH),
                  key=lambda r: r.start_ns)
    if not outer or len(prog) < len(outer):
        return None
    children: dict = {}
    for r in recs:
        children.setdefault(r.parent, []).append(r)
    out = []
    for b, p in zip(outer, prog[-len(outer):]):
        shift = b.start_ns - p.start_ns
        if p.end_ns + shift > b.end_ns + SLACK_NS:
            return None
        batch, todo = [], [p]
        while todo:
            r = todo.pop()
            batch.append(r._replace(
                start_ns=max(b.start_ns, r.start_ns + shift),
                end_ns=max(b.start_ns, min(b.end_ns, r.end_ns + shift))))
            todo.extend(children.get(r.id, ()))
        out.append(batch)
    return out


def durations_s(ctx, name: str):
    """Seconds of each aligned span named ``name``; None where there is
    none or nothing aligns."""
    batches = aligned(ctx)
    if batches is None:
        return None
    out = [(r.end_ns - r.start_ns) * 1e-9 for b in batches for r in b
           if r.name == name]
    return out or None


class Idle:
    """One device plane's idle stretches of ``[lo, hi]`` (``trace._gaps``
    over its op intervals), looked up by start, since a reader asks for
    every span of the window."""

    def __init__(self, intervals, lo: float, hi: float):
        self.gaps = trace._gaps(intervals, lo, hi)
        self.starts = [s for s, _ in self.gaps]

    def ns(self, lo: float, hi: float) -> float:
        """Nanoseconds of ``[lo, hi]`` in which no op runs."""
        idle = 0.0
        i = max(0, bisect.bisect_right(self.starts, lo) - 1)
        while i < len(self.gaps) and self.gaps[i][0] < hi:
            s, e = self.gaps[i]
            idle += max(0.0, min(hi, e) - max(lo, s))
            i += 1
        return idle


def _op_intervals(tr) -> list[list[tuple[float, float]]]:
    """The op intervals of each device plane that ran an op."""
    names = sorted({e.plane for e in tr.ops})
    return [[(e.start_ns, e.end_ns) for e in tr.ops if e.plane == n]
            for n in names]


def _planes(tr, batches) -> list[Idle]:
    """One :class:`Idle` per device plane, over the aligned batches."""
    lo = min(r.start_ns for b in batches for r in b)
    hi = max(r.end_ns for b in batches for r in b)
    return [Idle(iv, lo, hi) for iv in _op_intervals(tr)]


def idle_share(ctx, name: str):
    """Percent of the traced window that lies inside an aligned span named
    ``name`` while no op runs on the device, averaged over the device
    planes as the trace's busy time is; at most ``device_idle``. None
    where nothing aligns."""
    batches = aligned(ctx)
    if batches is None or not ctx.trace.ops or ctx.trace.window_s <= 0:
        return None
    spans = [r for b in batches for r in b if r.name == name]
    if not spans:
        return None
    planes = _planes(ctx.trace, batches)
    idle = sum(p.ns(r.start_ns, r.end_ns) for p in planes for r in spans)
    return 100.0 * idle * 1e-9 / len(planes) / ctx.trace.window_s


def idle_split(ctx):
    """Seconds of the first device's idle time in the traced window by
    the innermost aligned program span around it (``serve.batch`` where
    it lies between the batch's inner spans), and ``outside`` any
    program span. None where nothing aligns."""
    batches = aligned(ctx)
    if batches is None or not ctx.trace.ops:
        return None
    p = _planes(ctx.trace, batches)[0]
    out: dict[str, float] = {}
    inside = 0.0
    for b in batches:
        idle = {r.id: p.ns(r.start_ns, r.end_ns) for r in b}
        own = dict(idle)
        for r in b:
            if r.parent in own:
                own[r.parent] -= idle[r.id]
        for r in b:
            out[r.name] = out.get(r.name, 0.0) + own[r.id] * 1e-9
        inside += idle[b[0].id]
    busy_s = trace.union_seconds(_op_intervals(ctx.trace)[0])
    out["outside"] = ctx.trace.window_s - busy_s - inside * 1e-9
    return out
