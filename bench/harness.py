"""Run one cell of ``BENCHMARK.json`` once and print its result line.

A cell names a configuration and a traffic mix; everything about it is
found by name, in files of their own:

- ``BENCHMARK.json``: the cell, its configuration's file, and which
  metrics it reports;
- ``bench/traffic/<traffic>.json``: the mix's parameters and the name of
  the driver that runs it, ``bench/drivers/<driver>.py``;
- ``bench/limits/<cell>.json``: the limit of each number the check
  compares;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A run: refuse anything but a TPU; make the inputs from ``--seed``; warm
every shape (set-up, ``setup_s``); measure ``--seconds`` (``--trace 1``:
a shorter traced window, and the per-layer metrics instead of the
end-to-end ones); read the peak memory; free the program's state; check
the window's results against the plain reference; print each compared
number beside its limit, last on standard error and last in the result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import pathlib
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = pathlib.Path(__file__).resolve().parent


class Refused(Exception):
    """The run cannot be made here: no result is printed."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise Refused(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(root: pathlib.Path, workload: str) -> types.SimpleNamespace:
    """Everything a cell is made of, found by name under ``root``."""
    bench = load_json(root / "BENCHMARK.json")
    cell = by_name(bench["workloads"], workload, "workload")
    conf = by_name(bench["configs"], cell["config"], "config")
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return types.SimpleNamespace(
        cell=cell, cfg=load_json(root / conf["file"]), traffic=traffic,
        driver=load_module(root / "bench" / "drivers"
                           / f"{traffic['driver']}.py",
                           f"bench_driver_{traffic['driver']}"),
        limits=load_json(root / "bench" / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"]
                    if applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if applies(m, workload)],
        readers={m["name"]: load_module(
            root / "bench" / "metrics" / f"{m['name']}.py",
            "bench_metric_" + m["name"].replace(".", "_"))
            for m in bench["per_layer"] if applies(m, workload)})


def seed_key(seed: int):
    """A JAX key from any whole number, through numpy's SeedSequence."""
    import jax
    import numpy as np
    a, b = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) & 0x7FFFFFFF),
                              int(b) & 0x7FFFFFFF)


def enable_compile_cache(root: pathlib.Path) -> None:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set,
    else at the fixed path ``<checkout>/.jax_cache``; every program is
    kept, however quickly it compiled."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(cell: dict, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {devs[0].platform} "
                      f"({devs[0].device_kind})")
    if len(devs) < cell["chips"]:
        raise Refused(f"{cell['chips']} chips asked for, {len(devs)} found")
    return devs


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the trace's .xplane.pb into this dir")
    return ap.parse_args(argv)


def run(argv, t0: float, *, root: pathlib.Path = ROOT,
        require_tpu: bool = True, peaks: dict | None = None) -> dict:
    """One run of one cell; returns the result line's object."""
    args = parse(argv)
    if not (root / "src" / "repro").is_dir():
        raise Refused(f"the program is not in this checkout ({root}/src)")
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    c = resolve(root, args.workload)
    import jax
    t_import = time.perf_counter() - t0
    from bench import compiles as compiles_mod
    from bench import trace as trace_mod
    devs = devices_for(c.cell, require_tpu)[:c.cell["chips"]]
    t_devices = time.perf_counter() - t0
    peaks = peaks or peaks_for(devs[0].device_kind)
    enable_compile_cache(root)
    compiles = compiles_mod.listen()
    seed = args.seed % 2 ** 63
    drv = c.driver.Driver(c.cfg, c.traffic, seed, seed_key(seed))
    drv.setup()
    traced = bool(args.trace)
    seconds = (min(args.seconds, c.traffic["trace_seconds"]) if traced
               else args.seconds)
    setup_s = time.perf_counter() - t0
    log("setup: " + json.dumps({
        "imports": t_import, "devices": t_devices - t_import,
        "driver": getattr(drv, "setup_phases", None), "total": setup_s}))
    before = compiles.programs
    captured: list = []
    if traced:
        with trace_mod.capture(args.keep_trace) as captured:
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                res = drv.window(seconds)
    else:
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            res = drv.window(seconds)
    counts = drv.counts()
    counts["window_compiles"] = compiles.programs - before
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak(devs)}
    drv.release()
    log(f"window: {json.dumps(res)} counts: {json.dumps(counts)}")
    out = {"correct": False, "attempted": res["attempted"],
           "failed": res["failed"]}
    if traced:
        pbs = [p for p in captured if p.suffix == ".pb"]
        if not pbs:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        summary = trace_mod.summarize(trace_mod.load(pbs[0]))
        trace_mod.discard(captured)
        ctx = types.SimpleNamespace(trace=summary, counts=counts,
                                    peaks=peaks)
        metrics = {}
        for m in c.per_layer:
            v = c.readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = {"device_ops": summary.top_ops(10),
                     "idle_gaps": [[k, v] for k, v in summary.gaps[:10]]}
    else:
        found = dict(res["metrics"], setup_s=setup_s)
        metrics = {}
        for m in c.end_to_end:
            if m["name"] not in found:
                raise RuntimeError(f"the driver gave no {m['name']}")
            metrics[m["name"]] = {"value": found[m["name"]],
                                  "unit": m["unit"]}
        breakdown = None
    if hasattr(drv, "predictions"):
        try:
            log(f"predictions: {json.dumps(drv.predictions())} "
                f"(the tool's own, for one step)")
        except Exception as e:          # the check does not depend on it
            log(f"predictions: failed: {e!r}")
    checks = {}
    for name, value in drv.check():
        checks[name] = {"value": value, "limit": c.limits[name]}
    out["correct"] = bool(res["attempted"] > 0 and res["failed"] == 0
                          and all(v["value"] <= v["limit"]
                                  for v in checks.values()))
    out.update(metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv, t0: float) -> int:
    try:
        out = run(argv, t0)
    except Refused as e:
        log(f"refused: {e}")
        return 2
    for name, v in out["checks"].items():
        log(f"check {name} = {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0
