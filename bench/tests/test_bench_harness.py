"""The harness's refusals, the shape of BENCHMARK.json, and a cell that
exists only as files added to a copy of the checkout."""
import hashlib
import json
import os
import re
import subprocess
import sys

import pytest

from bench_tiny_root import (REPO, add_tiny_cells, copy_checkout,
                             run_cell)
from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _run(cwd, *args, env=None):
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env=env)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(REPO, "--workload", "stencil-paper.longrange25pt", "--seed", "1",
             "--seconds", "1", "--trace", "0", env=env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    root = copy_checkout(tmp_path / "co", with_src=False)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = _run(root, "--workload", "stencil-paper.longrange25pt", "--seed", "1",
             "--seconds", "1", "--trace", "0", env=env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_refused():
    with pytest.raises(harness.Refused):
        harness.peaks_for("TPU v0 imaginary")


def test_benchmark_json_contract():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (REPO / c["file"]).is_file()
        assert json.loads((REPO / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (REPO / "bench/traffic" / f"{w['traffic']}.json").is_file()
        assert (REPO / "bench/limits" / f"{w['name']}.json").is_file()
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names + cells + list(configs))
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert (REPO / "bench/metrics" / f"{m['name']}.py").is_file()
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved)
    for cell in cells:
        reported = [m for m in b["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in reported]
        assert len(reported) >= 2
        assert any(cell in m["workloads"] for m in b["per_layer"])


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_cell_from_added_files_only(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    root = copy_checkout(tmp_path / "co")
    before = _digests(root)
    add_tiny_cells(root)
    after = _digests(root)
    assert all(after[p] == d for p, d in before.items()), "a file was edited"
    assert set(after) - set(before)
    out = run_cell(root, "stencil-tiny.jacobi7pt")
    assert out["correct"] is True
    assert set(out["metrics"]) == {"sweep_glups", "setup_s"}
    assert list(out)[-1] == "checks"
