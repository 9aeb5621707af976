"""Candidate spaces: enumerate and analytically rank tuning configurations.

The predict half of the predict→measure→calibrate loop (DESIGN.md §12).
Each kernel family in :mod:`repro.kernels` registers a
:class:`CandidateSpace` that (1) enumerates every hardware-legal
configuration — flash-attention ``(block_q, block_kv)`` pairs, stencil
spatial block edges — and (2) scores all of them analytically in one
batched call, following the cutout-tuner shape (enumerate → search →
measure only a shortlist) with the search replaced by the paper's models:
the stencil families rank through :func:`repro.core.blocking.grid_search`
over the compiled sweep plan, flash attention through a closed-form
MXU/VPU/HBM time model seeded by :func:`repro.core.blocking
.attention_tiles`.  Thousands of candidates cost milliseconds; only the
top-k ever run (:mod:`repro.tune.measure`).

Each prediction carries its binding ``bound`` ('compute' or a memory
level), which :mod:`repro.tune.calibrate` uses to attribute the
measured/predicted error to per-level machine factors.
"""
from __future__ import annotations

import abc
import dataclasses
import importlib
import math

import numpy as np

from repro.core import blocking
from repro.core.machine import Machine

#: fraction of VMEM a candidate's working set may claim (leaves room for
#: double-buffered DMA slots, mirroring the advisor's default budgets)
VMEM_BUDGET = 0.8


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of a family's configuration space; ``params`` is a sorted
    tuple of pairs so candidates hash and compare by value."""
    family: str
    params: tuple[tuple[str, int], ...]

    @property
    def config(self) -> dict:
        return dict(self.params)

    @staticmethod
    def make(family: str, **params) -> "Candidate":
        return Candidate(family, tuple(sorted(params.items())))


@dataclasses.dataclass(frozen=True)
class Prediction:
    """Analytic score for one candidate: predicted wall seconds per kernel
    invocation, the binding term class ('compute' or a memory level name),
    and feasibility (with the reason when infeasible)."""
    seconds: float
    bound: str = ""
    feasible: bool = True
    reason: str = ""


class CandidateSpace(abc.ABC):
    """One kernel family's tunable configuration space.

    ``config`` holds the *problem shape* (sequence lengths, grid extents
    — what the caller is stuck with); candidates hold the *tunables*
    (block shapes — what the tuner may change).  Subclasses declare
    ``family`` and ``DEFAULTS`` and implement the four hooks below.
    """

    family: str = ""
    DEFAULTS: dict = {}

    def __init__(self, machine: Machine, **config):
        unknown = sorted(set(config) - set(self.DEFAULTS))
        if unknown:
            raise ValueError(
                f"unknown {self.family} config key(s) {unknown}; "
                f"accepted: {sorted(self.DEFAULTS)}")
        self.machine = machine
        self.config = {**self.DEFAULTS, **config}

    @abc.abstractmethod
    def candidates(self) -> list[Candidate]:
        """Every enumerated candidate (feasibility is judged by
        :meth:`predict`); must include :meth:`default`."""

    @abc.abstractmethod
    def default(self) -> Candidate:
        """The shipped configuration the tuner must beat; always
        feasible for the space's problem shape."""

    @abc.abstractmethod
    def predict(self, cands: list[Candidate],
                session=None) -> list[Prediction]:
        """Analytic predictions for ``cands``, order-aligned.  One batched
        call — pass a warm :class:`~repro.core.session.AnalysisSession`
        to share compiled plans across repeated rankings."""

    @abc.abstractmethod
    def runner(self, params: dict, interpret: bool | None = None):
        """A zero-argument closure executing one timed kernel invocation
        for ``params`` (inputs prebuilt, ``block_until_ready`` inside).
        ``interpret=None`` compiles on a TPU and interprets elsewhere.
        Imports jax lazily: prediction-only callers never pay for it."""


SPACE_REGISTRY: dict[str, type] = {}


def register_space(cls: type) -> type:
    SPACE_REGISTRY[cls.family] = cls
    return cls


def resolve_space(family: str, machine: Machine, **config) -> CandidateSpace:
    try:
        cls = SPACE_REGISTRY[family]
    except KeyError:
        raise ValueError(
            f"unknown tune family {family!r}; registered: "
            f"{sorted(SPACE_REGISTRY)}") from None
    return cls(machine, **config)


# ----------------------------------------------------------------------
# flash attention: (block_q, block_kv)
# ----------------------------------------------------------------------

@register_space
class FlashAttentionSpace(CandidateSpace):
    """``(block_q, block_kv)`` for the Pallas flash-attention kernel.

    The closed-form time model mirrors the kernel's schedule
    (:mod:`repro.kernels.flash_attention`): per (q, kv) block pair two
    MXU matmuls (QKᵀ, PV) plus ~8 VPU flops per score for the online
    softmax; causal masking skips fully-masked kv blocks, so the exact
    per-candidate grid-step count enters the model.  HBM traffic streams
    q/out once and re-fetches kv per visited step; the VMEM level
    overlaps compute (double-buffered DMA), so predicted seconds is
    ``max(compute, hbm)`` — the max's winner is the candidate's
    ``bound``.  Small q blocks under-fill the 128-row systolic array and
    pay more per-step overhead, which is what pushes the optimum away
    from the causal-waste minimum at tiny blocks.
    """

    family = "flash_attention"
    DEFAULTS = {"batch": 1, "heads": 4, "seq_q": 512, "seq_kv": 512,
                "head_dim": 128, "causal": True, "dtype": "bfloat16"}
    #: fixed cycles per grid step (pipeline fill, scratch init/finalize
    #: branches) — the overhead term the pure analytic models do not see
    STEP_OVERHEAD_CY = 1000.0

    def _elem_bytes(self) -> int:
        return 2 if str(self.config["dtype"]) == "bfloat16" else 4

    def _ws_bytes(self, bq: int, bkv: int) -> float:
        # the attention_tiles working-set formula: q tile + k/v tiles +
        # fp32 scores, accumulator, and (m, l) online-softmax state
        d, e = int(self.config["head_dim"]), self._elem_bytes()
        return (bq * d * e + 2 * bkv * d * e + bq * bkv * 4
                + bq * d * 4 + bq * 2 * 4)

    def candidates(self) -> list[Candidate]:
        sq, skv = int(self.config["seq_q"]), int(self.config["seq_kv"])
        bqs = range(blocking.SUBLANE, min(sq, 1024) + 1, blocking.SUBLANE)
        bkvs = range(blocking.LANE, min(skv, 2048) + 1, blocking.LANE)
        out = [Candidate.make(self.family, block_q=bq, block_kv=bkv)
               for bq in bqs for bkv in bkvs]
        seen = set(out)
        # seed the LC advisor's pick and the shipped default so both are
        # always scored even when the lattice above misses them
        for cand in (self._advisor(), self.default()):
            if cand not in seen:
                out.append(cand)
                seen.add(cand)
        return out

    def _advisor(self) -> Candidate:
        sq, skv = int(self.config["seq_q"]), int(self.config["seq_kv"])
        t = blocking.attention_tiles(sq, skv, int(self.config["head_dim"]),
                                     self._elem_bytes(),
                                     self.machine.vmem_bytes or 2 ** 27)
        bq, bkv = min(t.bq, sq), min(t.bkv, skv)
        while sq % bq:
            bq //= 2
        while skv % bkv:
            bkv //= 2
        return Candidate.make(self.family, block_q=bq, block_kv=bkv)

    def default(self) -> Candidate:
        from repro.kernels.flash_attention import default_config
        bq, bkv = default_config(int(self.config["seq_q"]),
                                 int(self.config["seq_kv"]),
                                 int(self.config["head_dim"]))
        return Candidate.make(self.family, block_q=bq, block_kv=bkv)

    def _steps(self, bq: int, bkv: int) -> int:
        """Grid steps per (batch·head) actually visited: causal schedules
        skip kv blocks entirely above the diagonal (``pl.when``)."""
        sq, skv = int(self.config["seq_q"]), int(self.config["seq_kv"])
        nq, nk = sq // bq, skv // bkv
        if not self.config["causal"]:
            return nq * nk
        off = skv - sq
        qi = np.arange(nq)
        last = qi * bq + off + bq - 1          # last kv position touched
        return int(np.minimum(nk, last // bkv + 1).clip(min=0).sum())

    def predict(self, cands: list[Candidate],
                session=None) -> list[Prediction]:
        m = self.machine
        c = self.config
        sq, skv = int(c["seq_q"]), int(c["seq_kv"])
        d, e = int(c["head_dim"]), self._elem_bytes()
        B = int(c["batch"]) * int(c["heads"])
        dtype_key = "BF16" if e == 2 else "FP32"
        peak_mxu = m.peak_flops.get(dtype_key) or m.peak_flops.get("FP32") \
            or 1e12
        peak_vpu = m.peak_flops.get("FP32") or peak_mxu
        hbm = m.hbm_bandwidth or m.main_memory_bandwidth or 1e11
        clock = m.clock_hz or 1e9
        vmem_limit = (m.vmem_bytes or 2 ** 27) * VMEM_BUDGET
        out: list[Prediction] = []
        for cand in cands:
            p = cand.config
            bq, bkv = int(p["block_q"]), int(p["block_kv"])
            if sq % bq or skv % bkv:
                out.append(Prediction(math.inf, feasible=False,
                                      reason=f"{bq}x{bkv} does not tile "
                                             f"{sq}x{skv}"))
                continue
            ws = self._ws_bytes(bq, bkv)
            if ws > vmem_limit:
                out.append(Prediction(
                    math.inf, feasible=False,
                    reason=f"working set {ws / 2**20:.1f} MiB exceeds "
                           f"{VMEM_BUDGET:.0%} of VMEM"))
                continue
            steps = self._steps(bq, bkv)
            # MXU: QK^T + PV, derated by systolic-row fill for small bq
            mxu_eff = min(bq, 128) / 128.0
            t_mxu = (4.0 * B * steps * bq * bkv * d) / (peak_mxu * mxu_eff)
            # VPU: exp/max/scale over the (bq, bkv) score tile
            t_vpu = (8.0 * B * steps * bq * bkv) / peak_vpu
            t_step = B * steps * self.STEP_OVERHEAD_CY / clock
            t_compute = t_mxu + t_vpu + t_step
            bytes_hbm = (2.0 * B * sq * d * e            # q in, out
                         + 2.0 * B * steps * bkv * d * e)  # k, v re-fetch
            t_hbm = bytes_hbm / hbm
            bound = "compute" if t_compute >= t_hbm else \
                (m.level_names[0] if m.level_names else "MEM")
            out.append(Prediction(max(t_compute, t_hbm), bound=bound))
        return out

    def runner(self, params: dict, interpret: bool | None = None):
        import jax
        import jax.numpy as jnp

        from repro.kernels.flash_attention import flash_attention
        c = self.config
        dtype = jnp.bfloat16 if str(c["dtype"]) == "bfloat16" \
            else jnp.float32
        shape_q = (int(c["batch"]), int(c["heads"]), int(c["seq_q"]),
                   int(c["head_dim"]))
        shape_kv = (int(c["batch"]), int(c["heads"]), int(c["seq_kv"]),
                    int(c["head_dim"]))
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], shape_q, dtype)
        k = jax.random.normal(ks[1], shape_kv, dtype)
        v = jax.random.normal(ks[2], shape_kv, dtype)
        bq, bkv = int(params["block_q"]), int(params["block_kv"])

        def run():
            out = flash_attention(q, k, v, causal=bool(c["causal"]),
                                  block_q=bq, block_kv=bkv,
                                  interpret=interpret)
            return jax.block_until_ready(out)
        return run


# ----------------------------------------------------------------------
# stencils: spatial block edge n, ranked through grid_search
# ----------------------------------------------------------------------

class _StencilSpace(CandidateSpace):
    """Spatial block-edge candidates for the 3D stencil kernels: the
    cutout shape is ``(m, n, n)`` and candidates vary ``n``.  Ranking
    runs the traced LoopKernel through :func:`repro.core.blocking
    .grid_search` (compiled analytic plan, metric='ecm') and converts
    cycles per unit of work into predicted seconds per cutout; the
    measured side times the Pallas kernel on the same cutout.  A
    ``cores`` config > 1 ranks over the batched (block x cores) grid
    instead, scoring each block by its chip-level saturated performance
    at the target core count."""

    #: subclasses: trace source, halo radius
    TRACE = ""
    RADIUS = 1
    DEFAULTS = {"m": 16, "n_min": 32, "n_max": 128, "n_step": 16,
                "cores": 1}

    def _values(self) -> list[int]:
        c = self.config
        lo = max(int(c["n_min"]), 2 * self.RADIUS + 2)
        return list(range(lo, int(c["n_max"]) + 1, int(c["n_step"])))

    def candidates(self) -> list[Candidate]:
        return [Candidate.make(self.family, n=v) for v in self._values()]

    def default(self) -> Candidate:
        vals = self._values()
        return Candidate.make(self.family, n=vals[len(vals) // 2])

    def _points(self, n: int) -> int:
        r = self.RADIUS
        return max(1, (int(self.config["m"]) - 2 * r) * (n - 2 * r) ** 2)

    def repeats(self, n: int) -> int:
        """Invocations per timed sample so every candidate processes the
        same reference volume (the largest candidate's point count) —
        raw per-cutout seconds would trivially crown the smallest block.
        Predictions scale by the same count, keeping predicted and
        measured walls directly comparable per candidate."""
        ref = self._points(max(self._values()))
        return max(1, round(ref / self._points(n)))

    def predict(self, cands: list[Candidate],
                session=None) -> list[Prediction]:
        from repro.core import api
        m = self.machine
        kernel = api.load_kernel(self.TRACE,
                                 constants={"M": int(self.config["m"])})
        vals = sorted({int(c.config["n"]) for c in cands})
        n_cores = int(self.config["cores"])
        if n_cores > 1:
            # rank over the batched (block x cores) grid: per-candidate
            # score = saturated min(single*n, sat) at the target core
            # count, converted back to effective cycles per unit below
            gs = blocking.grid_search(kernel, m, [("N", vals)],
                                      model="ecm", session=session,
                                      cores=list(range(1, n_cores + 1)))
            r0 = gs.best_result
            perf = {n: float(gs.scores[i, -1])
                    for i, n in enumerate(vals)}
            score = {n: (r0.flops_per_unit * m.clock_hz / p
                         if p > 0 else math.inf)
                     for n, p in perf.items()}
        else:
            gs = blocking.grid_search(kernel, m, [("N", vals)],
                                      model="ecm", session=session)
            score = {p["N"]: s for p, s in gs.ranking}
        unit = gs.best_result.unit_iterations
        clock = m.clock_hz or 1e9
        vmem = m.vmem_bytes or 2 ** 27
        kernel_mod = importlib.import_module(f"repro.kernels.{self.family}")
        # binding term at each point, for calibration attribution: the
        # exact result at the winner names whether compute or a transfer
        # term dominates (LC regimes are piecewise-constant, so the
        # winner's split is representative across the grid)
        r = gs.best_result
        bound = "compute" if r.t_ol >= r.t_data and all(
            r.t_ol >= c for _, c in r.overlapped) else \
            (m.level_names[0] if m.level_names else "MEM")
        out: list[Prediction] = []
        for cand in cands:
            n = int(cand.config["n"])
            ws = kernel_mod.vmem_bytes(n, 4)      # fp32 measurement planes
            if ws > vmem:
                out.append(Prediction(
                    math.inf, feasible=False,
                    reason=f"{n}x{n} planes need {ws / 2**20:.1f} MiB, "
                           f"more than VMEM"))
                continue
            cy_per_unit = score[n]
            secs = (cy_per_unit / unit / clock * self._points(n)
                    * self.repeats(n))
            out.append(Prediction(secs, bound=bound))
        return out


@register_space
class Stencil7ptSpace(_StencilSpace):
    family = "stencil3d7pt"
    TRACE = "trace:stencil3d7pt"
    RADIUS = 1

    def runner(self, params: dict, interpret: bool | None = None):
        import jax
        import jax.numpy as jnp

        from repro.kernels.stencil3d7pt import stencil3d7pt
        n = int(params["n"])
        a = jax.random.normal(jax.random.PRNGKey(0),
                              (int(self.config["m"]), n, n), jnp.float32)
        coeffs = jnp.linspace(0.1, 0.7, 7, dtype=jnp.float32)
        reps = self.repeats(n)

        def run():
            for _ in range(reps):
                out = stencil3d7pt(a, coeffs, interpret=interpret)
            return jax.block_until_ready(out)
        return run


@register_space
class LongRange3DSpace(_StencilSpace):
    family = "longrange3d"
    TRACE = "trace:longrange3d"
    RADIUS = 4

    def runner(self, params: dict, interpret: bool | None = None):
        import jax
        import jax.numpy as jnp

        from repro.kernels.longrange3d import longrange3d
        n = int(params["n"])
        shape = (int(self.config["m"]), n, n)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        u = jax.random.normal(ks[0], shape, jnp.float32)
        v = jax.random.normal(ks[1], shape, jnp.float32)
        roc = jax.random.uniform(ks[2], shape, jnp.float32, 0.5, 1.0)
        coeffs = jnp.linspace(0.05, 0.25, 5, dtype=jnp.float32)
        reps = self.repeats(n)

        def run():
            for _ in range(reps):
                out = longrange3d(u, v, roc, coeffs, interpret=interpret)
            return jax.block_until_ready(out)
        return run
