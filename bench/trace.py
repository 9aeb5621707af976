"""Profiler trace of a run's window, reduced to what the metrics read.

:func:`capture` wraps the timed window in ``jax.profiler`` tracing and
returns the ``.xplane.pb`` it wrote. :func:`load` reads that file with
``jax.profiler.ProfileData`` into plain :class:`Event` records, and
:func:`summarize` reduces them:

- the window: the host span named :data:`WINDOW_SPAN` that the harness
  records around the traced work;
- device events: those on the ``XLA Ops`` line of each ``/device:TPU:n``
  plane, clipped to the window (``XLA Modules`` gives one event per
  executable run and is kept apart, for per-program times);
- busy seconds: the union of the device-op intervals, averaged over the
  devices that ran anything;
- idle gaps: the stretches between busy intervals, each labelled by the
  innermost host span the benchmark recorded around it.

Nothing here knows a kernel or a model; the per-layer readers in
``bench/metrics`` pick events by name.
"""
from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import shutil
import tempfile

WINDOW_SPAN = "bench.window"
#: host spans the benchmark records start with this prefix
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    ops: list[Event]            # device ops inside the window
    modules: list[Event]        # device executable runs inside the window
    spans: list[Event]          # the benchmark's host spans
    gaps: list[tuple[str, float]]   # (host span, seconds), longest first

    def op_seconds(self, match) -> float:
        """Device seconds of the ops whose name satisfies ``match``."""
        return sum(e.dur_ns for e in self.ops if match(e.name)) * 1e-9

    def module_runs(self, match) -> list[Event]:
        return [e for e in self.modules if match(e.name)]

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` op names with the most device self time (an op's
        time less that of the ops nested in it, such as a loop's body)."""
        agg: dict[str, float] = {}
        for e, own in zip(self.ops, self_ns(self.ops)):
            agg[e.name] = agg.get(e.name, 0.0) + own * 1e-9
        return [[k, v] for k, v in
                sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def self_ns(events: list[Event]) -> list[float]:
    """Each event's duration less the events nested inside it on the same
    plane and line, in the order of ``events``."""
    own = [e.dur_ns for e in events]
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].plane, events[i].line,
                                  events[i].start_ns, -events[i].dur_ns))
    stack: list[int] = []
    for i in order:
        e = events[i]
        while stack and (events[stack[-1]].plane != e.plane
                         or events[stack[-1]].line != e.line
                         or events[stack[-1]].end_ns <= e.start_ns):
            stack.pop()
        if stack and e.end_ns <= events[stack[-1]].end_ns:
            own[stack[-1]] -= e.dur_ns
        stack.append(i)
    return own


@contextlib.contextmanager
def capture(keep: str | None = None):
    """Trace the body; yields a list that holds the ``.xplane.pb`` path
    once the body has ended. The trace directory is a fresh temporary
    one, removed by :func:`discard`; ``keep`` copies the file there."""
    import jax
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bench_trace_"))
    out: list[pathlib.Path] = []
    jax.profiler.start_trace(str(tmp))
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        pbs = sorted(tmp.rglob("*.xplane.pb"))
        if pbs:
            out.append(pbs[-1])
            if keep:
                pathlib.Path(keep).mkdir(parents=True, exist_ok=True)
                shutil.copy(pbs[-1], pathlib.Path(keep) / "trace.xplane.pb")
        out.append(tmp)


def discard(paths: list) -> None:
    for p in paths:
        if isinstance(p, pathlib.Path) and p.is_dir():
            shutil.rmtree(p, ignore_errors=True)


DEVICE_LINES = ("XLA Ops", "XLA Modules")


def load(path) -> list[Event]:
    """The events of an ``.xplane.pb`` that :func:`summarize` reads: the
    device planes' op and program lines, and the benchmark's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            for e in line.events:
                if not device and not e.name.startswith(SPAN_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, short_name(e.name),
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def short_name(name: str) -> str:
    """An op's name without its HLO text (``%fusion.3 = f32[...] ...`` ->
    ``fusion.3``), a program's without its fingerprint (``jit_f(123)`` ->
    ``jit_f``)."""
    name = name.split(" = ", 1)[0].lstrip("%")
    if name.endswith(")") and "(" in name:
        head, tail = name.rsplit("(", 1)
        if tail[:-1].isdigit():
            return head
    return name


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[12:].isdigit()


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of ``(start_ns, end_ns)`` intervals, in s."""
    total, end = 0.0, None
    start = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total * 1e-9


def _gaps(intervals, lo, hi):
    """Idle stretches of ``[lo, hi]`` between the merged intervals."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def _label(spans: list[Event], t: float) -> str:
    """The innermost benchmark span that covers time ``t``."""
    best = None
    for s in spans:
        if s.start_ns <= t <= s.end_ns and (best is None
                                            or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best is not None else "outside any span"


def summarize(events: list[Event], n_gaps: int = 10) -> TraceSummary:
    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)
             and not is_device_plane(e.plane)]
    windows = [e for e in spans if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no host span {WINDOW_SPAN!r} in the trace")
    win = max(windows, key=lambda e: e.dur_ns)
    lo, hi = win.start_ns, win.end_ns

    def clip(e: Event) -> Event | None:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t <= s:
            return None
        return Event(e.plane, e.line, e.name, s, t - s)

    ops, modules = [], []
    for e in events:
        if not is_device_plane(e.plane):
            continue
        c = clip(e)
        if c is None:
            continue
        if e.line == "XLA Ops":
            ops.append(c)
        elif e.line == "XLA Modules":
            modules.append(c)
    planes = sorted({e.plane for e in ops})
    busy = [union_seconds([(e.start_ns, e.end_ns) for e in ops
                           if e.plane == p]) for p in planes]
    busy_s = sum(busy) / len(busy) if busy else 0.0
    gaps = []
    inner = [s for s in spans if s is not win]
    for p in planes[:1]:
        for s, t in _gaps([(e.start_ns, e.end_ns) for e in ops
                           if e.plane == p], lo, hi):
            gaps.append((_label(inner, (s + t) / 2), (t - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=(hi - lo) * 1e-9, busy_s=busy_s,
                        n_devices=len(planes), ops=ops, modules=modules,
                        spans=inner, gaps=gaps[:n_gaps])
