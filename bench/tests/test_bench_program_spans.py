"""The serving program's spans aligned to a trace (``bench/program_spans``)
and the three readers built on it, on a hand-made trace and hand-made
program records."""
import sys
import types

import pytest

from bench_tiny_root import PEAKS, REPO
from bench import harness, program_spans, trace
from repro.obs import Span

DEV = "/device:TPU:0"
HOST = "/host:CPU"
T = 5e11                        # the program's clock, far from the trace's
MS = 1e6


def ev(plane, line, name, start, dur):
    return trace.Event(plane, line, name, float(start), float(dur))


def batch_records(base, first_id, length=2.9 * MS):
    """One engine batch on the program's clock: prefill, two decode steps
    and the read-back, in ``length`` ns from ``base``."""
    i = first_id
    kids = [("serve.prefill", 0.1, 0.5, {}),
            ("serve.decode_step", 0.5, 1.5, {"step": 1}),
            ("serve.decode_step", 1.5, 2.5, {"step": 2}),
            ("serve.readback", 2.5, 2.9, {"tokens": 6})]
    out = [Span(n, base + s * MS, base + e * MS, i, a, i + 1 + k)
           for k, (n, s, e, a) in enumerate(kids)]
    out.append(Span("serve.batch", base, base + length, None,
                    {"batch": first_id}, i))
    return out


def summary(device=True):
    events = [ev(HOST, "python", "bench.window", 0, 10 * MS),
              ev(HOST, "python", "bench.batch", 1 * MS, 3 * MS),
              ev(HOST, "python", "bench.batch", 5 * MS, 3 * MS)]
    if device:
        events += [ev(DEV, "XLA Ops", "fusion.1", 1.2 * MS, 2.2 * MS),
                   ev(DEV, "XLA Ops", "fusion.1", 5.2 * MS, 2.4 * MS)]
    return trace.summarize(events)


def records(overrun=False):
    warm = batch_records(T - 20 * MS, 0, length=2.9 * MS)
    b0 = batch_records(T, 10)
    b1 = batch_records(T + 10 * MS, 20,
                       length=(4.5 if overrun else 2.9) * MS)
    return warm + b0 + b1


def ctx_of(s):
    return types.SimpleNamespace(trace=s, counts={}, peaks=PEAKS)


READERS = ("readback_s.serve", "decode_step_host_ms.serve",
           "idle_readback.serve")


def reader(name):
    return harness.load_module(REPO / "bench" / "metrics" / f"{name}.py",
                               "bench_metric_" + name.replace(".", "_"))


@pytest.fixture
def program(monkeypatch):
    def use(recs):
        monkeypatch.setattr(program_spans, "records", lambda: recs)
    return use


def test_alignment_maps_each_program_batch_onto_its_bench_batch(program):
    program(records())
    batches = program_spans.aligned(ctx_of(summary()))
    assert len(batches) == 2
    for b, start in zip(batches, (1 * MS, 5 * MS)):
        top = [r for r in b if r.name == "serve.batch"]
        assert len(top) == 1 and top[0].start_ns == pytest.approx(start)
        assert top[0].end_ns == pytest.approx(start + 2.9 * MS)
        rb, = [r for r in b if r.name == "serve.readback"]
        assert (rb.start_ns, rb.end_ns) == pytest.approx(
            (start + 2.5 * MS, start + 2.9 * MS))
        assert sorted(r.name for r in b).count("serve.decode_step") == 2
    # the warm-up batch, recorded first, pairs with nothing
    assert {b[0].attrs.get("batch") for b in batches if
            b[0].name == "serve.batch"} <= {10, 20}


def test_readers_on_the_hand_made_trace(program):
    program(records())
    ctx = ctx_of(summary())
    got = {n: reader(n).read(ctx) for n in READERS}
    assert got["readback_s.serve"] == pytest.approx(0.4e-3)
    assert got["decode_step_host_ms.serve"] == pytest.approx(1.0)
    # idle inside the read-backs: 3.4-3.9 ms is outside the first batch's
    # ops (0.4 ms), 7.6-7.9 ms outside the second's (0.3 ms); of 10 ms
    assert got["idle_readback.serve"] == pytest.approx(7.0)
    device_idle = reader("device_idle.serve").read(ctx)
    assert device_idle == pytest.approx(54.0)
    assert 0 <= got["idle_readback.serve"] <= device_idle


def test_idle_split_accounts_for_all_idle_time(program):
    program(records())
    ctx = ctx_of(summary())
    split = program_spans.idle_split(ctx)
    want = {"serve.batch": 0.2e-3, "serve.prefill": 0.2e-3,
            "serve.decode_step": 0.1e-3, "serve.readback": 0.7e-3,
            "outside": 4.2e-3}
    assert split == pytest.approx(want)
    assert sum(split.values()) == pytest.approx(
        ctx.trace.window_s - ctx.trace.busy_s)


def test_idle_counts_only_time_inside_the_read_back(program):
    """Device idle time before the batch and between its steps is not the
    read-back's: with the ops covering the read-backs whole, the share
    is 0 while the device is still idle elsewhere."""
    program(records())
    events = [ev(HOST, "python", "bench.window", 0, 10 * MS),
              ev(HOST, "python", "bench.batch", 1 * MS, 3 * MS),
              ev(HOST, "python", "bench.batch", 5 * MS, 3 * MS),
              ev(DEV, "XLA Ops", "copy.1", 3.5 * MS, 0.4 * MS),
              ev(DEV, "XLA Ops", "copy.1", 7.5 * MS, 0.4 * MS)]
    ctx = ctx_of(trace.summarize(events))
    assert reader("idle_readback.serve").read(ctx) == 0.0
    assert reader("device_idle.serve").read(ctx) == pytest.approx(92.0)


def test_a_batch_that_overruns_its_pair_aligns_nothing(program):
    program(records(overrun=True))
    ctx = ctx_of(summary())
    assert program_spans.aligned(ctx) is None
    assert all(reader(n).read(ctx) is None for n in READERS)


@pytest.mark.parametrize("name", READERS)
def test_readers_need_a_device(program, name):
    program(records())
    assert reader(name).read(ctx_of(summary(device=False))) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_need_the_programs_spans(monkeypatch, name):
    """A program without ``repro.obs`` (an older checkout) reports none."""
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert program_spans.records() is None
    assert reader(name).read(ctx_of(summary())) is None


def test_fewer_program_batches_than_bench_batches_align_nothing(program):
    program(batch_records(T, 10))
    assert program_spans.aligned(ctx_of(summary())) is None
