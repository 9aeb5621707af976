"""Each driver end to end at a tiny size on the CPU (Pallas kernels in
interpret mode), through the harness with its look for a chip skipped;
and the control, which must read far above the program."""
import pytest

from bench_tiny_root import add_tiny_cells, copy_checkout, run_cell
from bench import control, harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = copy_checkout(tmp_path_factory.mktemp("co"))
    add_tiny_cells(r)
    return r


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)


@pytest.mark.parametrize("cell,e2e", [
    ("stencil-tiny.longrange25pt", {"sweep_glups", "setup_s"}),
    ("stencil-tiny.jacobi7pt", {"sweep_glups", "setup_s"}),
    ("lm-tiny.tiny-chat", {"tokens_per_s", "request_p95_s", "setup_s"}),
])
def test_driver_end_to_end(root, cell, e2e):
    out = run_cell(root, cell)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == e2e
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"


def test_traced_run_on_cpu_reports_no_device_metric(root, tmp_path):
    """The CPU has no device plane: the traced run still checks, and its
    per-layer readers find nothing to read, so it reports none; the trace
    itself is kept where ``--keep-trace`` says."""
    out = run_cell(root, "lm-tiny.tiny-chat", trace=1,
                   extra=("--keep-trace", str(tmp_path)))
    assert out["correct"] is True
    assert set(out["metrics"]) <= {"window_compiles.serve"}
    assert out["device"]["busy_s"] == 0.0 and out["device"]["window_s"] > 0
    assert (tmp_path / "trace.xplane.pb").stat().st_size > 0


@pytest.mark.parametrize("cell,name", [
    ("stencil-tiny.longrange25pt", "rel_err"),
    ("stencil-tiny.jacobi7pt", "rel_err"),
    ("lm-tiny.tiny-chat", "logit_gap"),
])
def test_control_fails_where_the_program_passes(root, cell, name):
    limit = harness.resolve(root, cell).limits[name]
    for rec in control.readings(root, cell, [11, 12, 13], 0.5,
                                require_tpu=False):
        assert rec["program"][name] <= limit
        assert rec["control"][name] > limit
