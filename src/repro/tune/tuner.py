"""The tune loop: rank analytically, measure a shortlist, derive factors.

``tune()`` is the one entry point (CLI ``repro tune`` and ``api.tune``
both land here): resolve the family's :class:`~repro.tune.space
.CandidateSpace`, predict every candidate in one batched call, measure
the top-k plus the shipped default with real timers, pick the fastest
measured candidate, and derive calibration factors from the
measured/predicted ratios.  A measured run whose shipped default fails
to measure raises :class:`TuneFailed` instead of falling back to the
prediction.  With a :class:`~repro.service
.AnalysisService` attached, whole reports persist in the result store
under kind ``"tune"`` — a warm replay decodes from disk without
recomputing or re-measuring.
"""
from __future__ import annotations

import math

from repro.core.machine import Machine

from . import calibrate as _calibrate
from .measure import measure_candidate
from .report import (STATUS_FAILED, STATUS_INFEASIBLE, STATUS_OK,
                     STATUS_PREDICTED, CandidateOutcome, TuneReport)
from .space import resolve_space

#: predicted (non-measured) candidates kept in the stored report
KEEP_PREDICTED = 32


class TuneFailed(RuntimeError):
    """Measuring was asked for and the shipped default did not measure, so
    there is no measured baseline to choose against."""


def _freeze(d: dict) -> tuple:
    return tuple(sorted(d.items()))


def tune(family: str, machine: Machine | str, *, config: dict | None = None,
         top_k: int = 4, measure: bool = True, warmup: int = 1,
         reps: int = 3, timeout_s: float = 120.0, isolate: bool = True,
         retries: int = 1, interpret: bool | None = None, session=None,
         service=None, keep_predicted: int = KEEP_PREDICTED) -> TuneReport:
    """Autotune ``family`` on ``machine``; returns a :class:`TuneReport`.

    ``config`` overrides the family's problem shape (see each space's
    ``DEFAULTS``).  ``top_k`` candidates (by analytic prediction) plus
    the shipped default are measured with ``warmup``+``reps`` timed
    invocations each, in isolated subprocesses with a ``timeout_s`` cap
    unless ``isolate=False``.  ``measure=False`` stops after the analytic
    ranking (the chosen candidate is then the predicted best).  A machine
    carrying ``calibration.time[family]`` (from a previous
    ``--apply-calibration``) has that factor folded into the predictions,
    so recalibrated predictions track measurements more closely.
    ``interpret=None`` times compiled kernels on a TPU and the Pallas
    interpreter elsewhere; the report records the resolved value.
    """
    from repro.core import api
    mach = api.resolve_machine(machine)
    if measure:
        from repro.kernels.backend import resolve_interpret
        interpret = resolve_interpret(interpret)
    config = dict(config or {})
    if service is not None:
        key = ("tune", family, mach.fingerprint, _freeze(config),
               int(top_k), bool(measure), int(warmup), int(reps),
               bool(interpret))
        meta = {"kind": "tune", "family": family, "machine": mach.name,
                "machine_fingerprint": mach.fingerprint,
                "measured": bool(measure)}

        def compute():
            rep = tune(family, mach, config=config, top_k=top_k,
                       measure=measure, warmup=warmup, reps=reps,
                       timeout_s=timeout_s, isolate=isolate,
                       retries=retries, interpret=interpret,
                       session=session, keep_predicted=keep_predicted)
            return rep, rep.to_dict()

        def decode(payload):
            try:
                return TuneReport.from_dict(payload)
            except (KeyError, TypeError, ValueError):
                return None
        return service.serve_custom(key, compute, decode, meta=meta)

    space = resolve_space(family, mach, **config)
    cands = space.candidates()
    preds = space.predict(cands, session=session)
    time_factor = mach.calibration_factor("time", family)

    default = space.default()
    by_cand = dict(zip(cands, preds))
    if default not in by_cand:      # defensive; spaces include default
        cands.append(default)
        p = space.predict([default], session=session)[0]
        preds.append(p)
        by_cand[default] = p

    feasible = [(c, p) for c, p in zip(cands, preds) if p.feasible]
    infeasible = [(c, p) for c, p in zip(cands, preds) if not p.feasible]
    feasible.sort(key=lambda cp: cp[1].seconds)

    def _pred_s(p) -> float:
        return p.seconds * time_factor

    shortlist = [c for c, _ in feasible[:max(1, top_k)]]
    if default in by_cand and by_cand[default].feasible \
            and default not in shortlist:
        shortlist.append(default)

    outcomes: dict = {}
    if measure:
        for cand in shortlist:
            tr = measure_candidate(family, space.config, cand.config, mach,
                                   warmup=warmup, reps=reps,
                                   timeout_s=timeout_s, isolate=isolate,
                                   retries=retries, interpret=interpret)
            p = by_cand[cand]
            outcomes[cand] = CandidateOutcome(
                params=cand.config,
                status=STATUS_OK if tr.ok else STATUS_FAILED,
                predicted_s=_pred_s(p), bound=p.bound, measured=tr)

    if measure and outcomes.get(default) is not None \
            and outcomes[default].status != STATUS_OK:
        raise TuneFailed(
            f"{family}: the default candidate {default.config} did not "
            f"measure: {outcomes[default].measured.error}")

    # chosen: fastest measured candidate, else the predicted best
    measured_ok = [(c, o) for c, o in outcomes.items()
                   if o.status == STATUS_OK]
    if measured_ok:
        chosen, chosen_out = min(measured_ok,
                                 key=lambda co: co[1].measured.wall_s)
    else:
        chosen = feasible[0][0] if feasible else default
        chosen_out = None

    def _meas_s(cand) -> float | None:
        o = outcomes.get(cand)
        return o.measured_s if o is not None else None

    meas_chosen = _meas_s(chosen)
    meas_default = _meas_s(default)
    speedup = None
    if meas_chosen and meas_default and meas_chosen > 0:
        speedup = meas_default / meas_chosen

    # calibration from every successful measurement (analytic predictions,
    # not time_factor-scaled: derived factors are absolute)
    samples = [(by_cand[c].seconds, o.measured.wall_s, o.bound)
               for c, o in measured_ok]
    calibration: dict = {}
    error: dict = {"n": 0}
    if samples:
        error = _calibrate.prediction_error(
            [(_pred_s(by_cand[c]), o.measured.wall_s)
             for c, o in measured_ok])
        calibration = _calibrate.derive_calibration(family, samples, mach)

    # stored candidate list: measured outcomes first (ranked by
    # prediction), then the best predicted tail, then infeasible count
    records: list[CandidateOutcome] = []
    listed = set()
    for c, p in feasible:
        if c in outcomes:
            records.append(outcomes[c])
            listed.add(c)
    n_pred = 0
    for c, p in feasible:
        if c in listed or n_pred >= max(0, keep_predicted):
            continue
        records.append(CandidateOutcome(
            params=c.config, status=STATUS_PREDICTED,
            predicted_s=_pred_s(p), bound=p.bound))
        n_pred += 1
    for c, p in infeasible[:8]:     # a few examples of why points died
        records.append(CandidateOutcome(
            params=c.config, status=STATUS_INFEASIBLE, reason=p.reason))

    dflt_p = by_cand.get(default)
    return TuneReport(
        family=family, machine=mach.name,
        machine_fingerprint=mach.fingerprint,
        config=dict(space.config),
        options={"top_k": top_k, "measure": measure, "warmup": warmup,
                 "reps": reps, "interpret": interpret, "isolate": isolate,
                 "time_factor": time_factor},
        candidates=tuple(records),
        n_enumerated=len(cands), n_feasible=len(feasible),
        default_params=default.config, chosen_params=chosen.config,
        predicted_chosen_s=_pred_s(by_cand[chosen]),
        predicted_default_s=(_pred_s(dflt_p)
                             if dflt_p and dflt_p.feasible else None),
        measured_chosen_s=meas_chosen, measured_default_s=meas_default,
        speedup_vs_default=speedup, error=error, calibration=calibration)
