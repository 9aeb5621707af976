"""Driver for the paper's stencils: time steps of one Pallas kernel
through ``repro.kernels.ops``, each step reading what the last one wrote.

Traffic parameters (``bench/traffic/<mix>.json``):

- ``stencil``: ``longrange25pt`` (leapfrog steps of ``ops.longrange3d``)
  or ``jacobi7pt`` (Jacobi sweeps of ``ops.stencil3d7pt``);
- ``in_flight``: steps dispatched ahead of the one the host waits for, so
  the device queue never drains and memory stays bounded;
- ``check_steps``: how many steps of the window a reservoir drawn from
  the seed keeps, with their inputs, for the check; the last step is
  always checked too;
- ``trace_seconds``: the length of the traced run's window.

The fields are made on the device from the seed in one jitted call. The
check runs the plain reference of ``bench/reference/stencil.py`` on each
kept step's inputs and compares the kernel's output with it.
"""
from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import stencil as counts
from bench.reference import stencil as reference

KERNELS = {"longrange25pt": "longrange3d", "jacobi7pt": "stencil3d7pt"}


def make_fields(key, kind: str, m: int, n: int, roc_range):
    """Random fields for one stencil, on the device: (state, roc)."""
    k1, k2 = jax.random.split(key)
    shape = (m, n, n)
    a = jax.random.normal(k1, shape, jnp.float32)
    if kind == "jacobi7pt":
        return (a,), None
    roc = jax.random.uniform(k2, shape, jnp.float32, *roc_range)
    return (a, a), roc


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, key):
        self.traffic = traffic
        self.kind = traffic["stencil"]
        self.spec = cfg["stencils"][self.kind]
        self.m, self.n = cfg["M"], cfg["N"]
        self.key = key
        self.rng = np.random.default_rng([seed, 1])
        self.coeffs = None
        self.samples: list = []
        self.steps = 0

    # -- the program's call ------------------------------------------------
    def step(self, state):
        if self.kind == "jacobi7pt":
            return (self.ops.stencil3d7pt(state[0], self.coeffs),)
        u, v = state
        return (v, self.ops.longrange3d(u, v, self.roc, self.coeffs))

    def setup(self) -> None:
        from repro.kernels import ops
        self.ops = ops
        fields = jax.jit(make_fields, static_argnums=(1, 2, 3, 4))
        self.state, self.roc = fields(self.key, self.kind, self.m, self.n,
                                      tuple(self.spec.get("roc_range",
                                                          (0.0, 0.0))))
        self.coeffs = jnp.asarray(self.spec["coefficients"], jnp.float32)
        # warm: the kernel's one shape, twice, so the steady call is cached
        for _ in range(2):
            self.state = jax.block_until_ready(self.step(self.state))

    def window(self, seconds: float) -> dict:
        """Dispatch steps until ``seconds`` have passed, keep at most
        ``in_flight`` of them queued, and end on the last one's result."""
        k = self.traffic["check_steps"]
        depth = self.traffic["in_flight"]
        pending = collections.deque()
        state, i = self.state, 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with jax.profiler.TraceAnnotation("bench.step"):
                new = self.step(state)
            if i < k:
                self.samples.append((state, new))
            else:
                j = int(self.rng.integers(0, i + 1))
                if j < k:
                    self.samples[j] = (state, new)
            prev, state, i = state, new, i + 1
            pending.append(new[-1])
            if len(pending) > depth:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    pending.popleft().block_until_ready()
            if time.perf_counter() >= deadline:
                break
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        self.samples.append((prev, state))
        self.state, self.steps = state, i
        self.seconds = dt
        sites = counts.sites(self.kind, self.m, self.n)
        return {"metrics": {"sweep_glups": i * sites / dt / 1e9},
                "attempted": i, "failed": 0}

    def counts(self) -> dict:
        """What the per-layer readers need: the kernel's name in the
        trace and the work each call of it needs."""
        need = counts.sweep(self.kind, self.m, self.n, 4)
        return {"kernel": KERNELS[self.kind], "calls": self.steps,
                "needed_flops": need["flops"], "needed_bytes": need["bytes"]}

    def release(self) -> None:
        """Drop everything but the kept steps."""
        self.state = None

    def _reference(self, state, dtype):
        if self.kind == "jacobi7pt":
            return reference.jacobi7pt_jit(state[0], self.coeffs,
                                           dtype=dtype)
        u, v = state
        return reference.longrange25pt_jit(u, v, self.roc, self.coeffs,
                                           dtype=dtype)

    def check(self, control: bool = False) -> list[tuple[str, float]]:
        """Worst relative error over the kept steps: max |out - ref| over
        max |ref|. ``control`` puts the reference in bfloat16 in the
        kernel's place."""
        worst = 0.0
        for state, new in self.samples:
            want = self._reference(state, jnp.float32)
            got = (self._reference(state, jnp.bfloat16) if control
                   else new[-1])
            err = jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
            err = float(err)
            worst = max(worst, err if np.isfinite(err) else np.inf)
        return [("rel_err", worst)]

    def predictions(self) -> dict:
        """The tool's own ECM and Roofline seconds for one sweep."""
        from repro.core import api, machine as machine_mod
        mach = machine_mod.load("V5E")
        trace = {"jacobi7pt": "trace:stencil3d7pt",
                 "longrange25pt": "trace:longrange3d"}[self.kind]
        r = self.spec["radius"]
        out = {}
        for model in ("ecm", "roofline"):
            res = api.analyze(trace, mach, model,
                              constants={"M": self.m, "N": self.n},
                              frontend_opts={"element_bytes": 4})
            cy = res.t_ecm if model == "ecm" else res.time_cy
            out[f"{model}_s"] = (cy / res.unit_iterations
                                 * (self.m - 2 * r) * (self.n - 2 * r) ** 2
                                 / mach.clock_hz)
        return out
