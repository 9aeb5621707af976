"""The reduction from a profiler trace to the per-layer numbers: on
hand-made events, and on a small trace recorded on one TPU v5e (a traced
run of a 16 x 128 x 128 Jacobi cell, kept in fixtures/)."""
import types

import pytest

from bench_tiny_root import PEAKS, REPO
from bench import trace
from bench.counts import stencil as counts
from bench.roofline import kernel_share

FIXTURE = REPO / "bench/tests/fixtures/jacobi_16x128x128.xplane.pb"
DEV = "/device:TPU:0"


def ev(plane, line, name, start, dur):
    return trace.Event(plane, line, name, float(start), float(dur))


def test_short_names():
    assert trace.short_name("%fusion.3 = f32[2]{0} fusion(%p)") == "fusion.3"
    assert trace.short_name("jit_longrange3d(3453818376323076435)") == \
        "jit_longrange3d"
    assert trace.short_name("bench.step") == "bench.step"


def test_union_and_gaps_on_hand_made_events():
    events = [
        ev("/host:CPU", "python", "bench.window", 0, 1000),
        ev("/host:CPU", "python", "bench.step", 100, 300),
        ev("/host:CPU", "python", "bench.wait", 600, 350),
        ev(DEV, "XLA Ops", "k.1", 100, 200),       # 100-300
        ev(DEV, "XLA Ops", "k.1", 250, 150),       # 250-400, overlaps
        ev(DEV, "XLA Ops", "k.1", 700, 100),       # 700-800
        ev(DEV, "XLA Ops", "k.1", 950, 200),       # clipped to 950-1000
        ev(DEV, "XLA Modules", "jit_k", 100, 300),
        ev(DEV, "Async XLA Ops", "copy-start", 0, 1000),   # not an op
        ev(DEV, "XLA Ops", "k.1", 1200, 100),      # after the window
    ]
    s = trace.summarize(events)
    assert s.window_s == pytest.approx(1e-6)
    # busy: 100-400, 700-800, 950-1000 = 450 ns
    assert s.busy_s == pytest.approx(450e-9)
    assert s.n_devices == 1
    # gaps 0-100 and 400-700 lie in no inner span; 800-950 in bench.wait
    assert s.gaps[0] == ("outside any span", pytest.approx(300e-9))
    assert ("bench.wait", pytest.approx(150e-9)) in s.gaps
    assert s.top_ops(1) == [["k.1", pytest.approx(450e-9 + 50e-9)]]


def test_window_span_is_required():
    with pytest.raises(ValueError):
        trace.summarize([ev(DEV, "XLA Ops", "k", 0, 1)])


@pytest.fixture(scope="module")
def recorded():
    if not FIXTURE.is_file():
        pytest.fail(f"missing fixture {FIXTURE}")
    return trace.summarize(trace.load(FIXTURE))


def test_recorded_trace_reduces(recorded):
    s = recorded
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    kernels = [e for e in s.ops if e.name.startswith("stencil3d7pt")]
    assert kernels and all(e.dur_ns > 0 for e in kernels)
    assert [m.name for m in s.modules][0] == "jit_stencil3d7pt"
    assert any(e.name == "bench.step" for e in s.spans)
    assert s.top_ops(10)[0][0].startswith("stencil3d7pt")


def test_recorded_roofline_share_is_a_share(recorded):
    need = counts.sweep("jacobi7pt", 16, 128, 4)
    ctx = types.SimpleNamespace(
        trace=recorded, peaks=PEAKS,
        counts={"kernel": "stencil3d7pt", "needed_flops": need["flops"],
                "needed_bytes": need["bytes"]})
    share = kernel_share(ctx, "stencil3d7pt")
    assert 0 < share <= 100
    assert kernel_share(ctx, "longrange3d") is None


def test_self_time_subtracts_nested_ops():
    loop = ev(DEV, "XLA Ops", "while.1", 0, 100)
    body = [ev(DEV, "XLA Ops", "fusion.1", 10, 30),
            ev(DEV, "XLA Ops", "copy.1", 50, 40)]
    other = ev("/device:TPU:1", "XLA Ops", "k", 20, 10)
    assert trace.self_ns([loop, *body, other]) == [30.0, 30.0, 40.0, 10.0]
