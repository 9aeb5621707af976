"""Measurement harness: real timers around the shortlisted candidates.

The measure half of predict→measure→calibrate.  Each candidate runs as
``warmup`` untimed invocations followed by ``reps`` timed ones; the
reported wall time is the median after IQR outlier rejection (Tukey
fences), so a stray GC pause or container hiccup cannot crown the wrong
candidate.  Timing closes over ``jax.block_until_ready`` (built into the
space's runner), so async dispatch cannot fake a win either.

Off the TPU, every candidate runs by default in its own spawned
subprocess with a hard timeout: a candidate that crashes the Pallas
lowering, OOMs, or hangs is recorded as a failed :class:`TimedRun` and the
tune run continues — one bad point never kills the sweep.
``isolate=False`` times in-process (fast, used by tests and the
benchmark's smoke path) at the cost of timeout protection.  On a TPU
backend candidates are always timed in-process: a chip belongs to one
process, so a child could not reach it.

Fault injection for tests mirrors ``REPRO_WORKER_FAULT``
(:mod:`repro.service.workers`): ``REPRO_TUNE_FAULT`` ∈ {``raise``,
``exit``, ``hang``} fires inside the measurement child, optionally gated
by ``REPRO_TUNE_FAULT_MATCH`` (substring of the candidate's
``k=v,k=v`` parameter tag).
"""
from __future__ import annotations

import dataclasses
import math
import multiprocessing as mp
import os
import time

from repro.core.machine import Machine

_SENTINEL = "repro.tune"      # marker for error payloads


@dataclasses.dataclass(frozen=True)
class TimedRun:
    """One candidate's measurement outcome.

    ``wall_s`` is the IQR-robust median over ``samples`` (``inf`` when
    ``ok`` is False); ``rejected`` counts samples discarded as outliers;
    ``retries`` how many extra subprocess attempts the harness spent.
    """
    ok: bool
    wall_s: float
    samples: tuple[float, ...] = ()
    rejected: int = 0
    warmup: int = 1
    reps: int = 5
    error: str = ""
    timed_out: bool = False
    retries: int = 0

    def to_dict(self) -> dict:
        return {"ok": self.ok, "wall_s": self.wall_s,
                "samples": list(self.samples), "rejected": self.rejected,
                "warmup": self.warmup, "reps": self.reps,
                "error": self.error, "timed_out": self.timed_out,
                "retries": self.retries}

    @classmethod
    def from_dict(cls, d: dict) -> "TimedRun":
        return cls(ok=bool(d["ok"]), wall_s=float(d["wall_s"]),
                   samples=tuple(float(s) for s in d.get("samples", [])),
                   rejected=int(d.get("rejected", 0)),
                   warmup=int(d.get("warmup", 1)),
                   reps=int(d.get("reps", 5)),
                   error=str(d.get("error", "")),
                   timed_out=bool(d.get("timed_out", False)),
                   retries=int(d.get("retries", 0)))


def robust_median(samples) -> tuple[float, int]:
    """Median after Tukey-fence outlier rejection (1.5×IQR); returns
    ``(median, n_rejected)``.  With < 4 samples the plain median stands —
    quartiles of a triple are too noisy to reject on."""
    xs = sorted(float(s) for s in samples)
    n = len(xs)
    if n == 0:
        return math.inf, 0
    if n >= 4:
        def _q(p: float) -> float:
            k = p * (n - 1)
            lo = int(k)
            hi = min(lo + 1, n - 1)
            return xs[lo] + (k - lo) * (xs[hi] - xs[lo])
        q1, q3 = _q(0.25), _q(0.75)
        iqr = q3 - q1
        kept = [x for x in xs if q1 - 1.5 * iqr <= x <= q3 + 1.5 * iqr]
        if kept:
            rejected = n - len(kept)
            xs, n = kept, len(kept)
            mid = n // 2
            med = xs[mid] if n % 2 else 0.5 * (xs[mid - 1] + xs[mid])
            return med, rejected
    mid = n // 2
    return (xs[mid] if n % 2 else 0.5 * (xs[mid - 1] + xs[mid])), 0


def time_closure(fn, warmup: int = 1, reps: int = 5) -> TimedRun:
    """Time ``fn()``: ``warmup`` untimed calls, ``reps`` timed samples,
    IQR-robust median.  The closure must block until the result is ready
    (space runners call ``jax.block_until_ready`` internally)."""
    for _ in range(max(0, warmup)):
        fn()
    samples = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    med, rejected = robust_median(samples)
    return TimedRun(ok=True, wall_s=med, samples=tuple(samples),
                    rejected=rejected, warmup=warmup, reps=reps)


def _params_tag(params: dict) -> str:
    return ",".join(f"{k}={v}" for k, v in sorted(params.items()))


def _maybe_fault(params: dict) -> None:
    """Test hook: crash/raise/hang inside the measurement path on demand."""
    fault = os.environ.get("REPRO_TUNE_FAULT")
    if not fault:
        return
    match = os.environ.get("REPRO_TUNE_FAULT_MATCH", "")
    if match and match not in _params_tag(params):
        return
    if fault == "exit":
        os._exit(3)
    if fault == "hang":
        time.sleep(3600)
    raise RuntimeError(
        f"injected tune fault (REPRO_TUNE_FAULT={fault}) for "
        f"[{_params_tag(params)}]")


def _run_inproc(family: str, config: dict, params: dict,
                machine: Machine, warmup: int, reps: int,
                interpret: bool | None) -> TimedRun:
    from repro.tune.space import resolve_space
    _maybe_fault(params)
    space = resolve_space(family, machine, **config)
    fn = space.runner(params, interpret=interpret)
    return time_closure(fn, warmup=warmup, reps=reps)


def _child_entry(conn, family: str, config: dict, params: dict,
                 machine: Machine, warmup: int, reps: int,
                 interpret: bool | None) -> None:
    """Subprocess entry point (module-level for spawn picklability)."""
    try:
        tr = _run_inproc(family, config, params, machine, warmup, reps,
                         interpret)
        conn.send({_SENTINEL: "ok", "run": tr.to_dict()})
    except BaseException as exc:  # noqa: BLE001 — report, don't die silently
        try:
            conn.send({_SENTINEL: "error",
                       "error": f"{type(exc).__name__}: {exc}"})
        except Exception:
            pass
    finally:
        conn.close()


def _ensure_importable_env() -> tuple[str, str | None]:
    """Make sure spawned children can ``import repro`` (mirrors
    :mod:`repro.service.workers`); returns (key, previous) to restore."""
    import pathlib
    src = str(pathlib.Path(__file__).resolve().parents[2])
    old = os.environ.get("PYTHONPATH")
    parts = (old.split(os.pathsep) if old else [])
    if src not in parts:
        os.environ["PYTHONPATH"] = os.pathsep.join([src] + parts)
    return "PYTHONPATH", old


def measure_candidate(family: str, config: dict, params: dict,
                      machine: Machine, *, warmup: int = 1, reps: int = 3,
                      timeout_s: float = 120.0, isolate: bool = True,
                      retries: int = 1, interpret: bool | None = None,
                      start_method: str | None = None) -> TimedRun:
    """Measure one candidate; never raises for candidate-side failures.

    ``isolate=True`` (default) runs the measurement in a spawned
    subprocess with a ``timeout_s`` wall clock and up to ``retries``
    extra attempts after a crash — the failure mode of a bad Pallas
    config (lowering assert, OOM kill, interpreter hang) becomes a
    ``TimedRun(ok=False, ...)`` record.  Timeouts are not retried: a
    config that hangs once will hang again.  On a TPU backend ``isolate``
    is ignored and the candidate is timed in this process.
    """
    from repro.kernels.backend import on_tpu
    if not isolate or on_tpu():
        try:
            return _run_inproc(family, dict(config), dict(params), machine,
                               warmup, reps, interpret)
        except Exception as exc:  # noqa: BLE001
            return TimedRun(ok=False, wall_s=math.inf, warmup=warmup,
                            reps=reps, error=f"{type(exc).__name__}: {exc}")

    ctx = mp.get_context(start_method or "spawn")
    key, old = _ensure_importable_env()
    last_err, timed_out = "no attempt ran", False
    try:
        for attempt in range(max(0, retries) + 1):
            parent, child = ctx.Pipe(duplex=False)
            proc = ctx.Process(
                target=_child_entry,
                args=(child, family, dict(config), dict(params), machine,
                      warmup, reps, interpret))
            proc.start()
            child.close()
            payload = None
            try:
                if parent.poll(timeout_s):
                    payload = parent.recv()
                else:
                    timed_out = True
            except (EOFError, OSError):
                pass          # child died before sending
            finally:
                parent.close()
            proc.join(timeout=5.0)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=5.0)
            if payload is not None and payload.get(_SENTINEL) == "ok":
                tr = TimedRun.from_dict(payload["run"])
                return dataclasses.replace(tr, retries=attempt)
            if timed_out:
                last_err = (f"timed out after {timeout_s:g}s "
                            f"[{_params_tag(params)}]")
                break         # hangs are deterministic; don't retry
            if payload is not None:
                last_err = str(payload.get("error", "unknown child error"))
            else:
                last_err = (f"measurement child died (exit code "
                            f"{proc.exitcode}) [{_params_tag(params)}]")
    finally:
        if old is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = old
    return TimedRun(ok=False, wall_s=math.inf, warmup=warmup, reps=reps,
                    error=last_err, timed_out=timed_out,
                    retries=max(0, retries) if not timed_out else 0)
