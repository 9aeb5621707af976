"""The autotuner (repro.tune): predict -> measure -> calibrate.

Fast paths (spaces, timers, report round-trips, calibration math) run
pure; the handful of subprocess tests use the real flash-attention family
with tiny shapes so spawned children stay cheap.
"""
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from repro.core import api, blocking
from repro.core import machine as machine_mod
from repro.core.machine import Machine
from repro.service import AnalysisService
from repro.tune import (SPACE_REGISTRY, Candidate, CandidateOutcome,
                        TimedRun, TuneReport, apply_calibration,
                        derive_calibration, machine_yaml_path,
                        measure_candidate, prediction_error, register_space,
                        resolve_space, robust_median, time_closure, tune)
from repro.tune.space import CandidateSpace, Prediction

V5E = machine_mod.load("V5E")
TINY = {"seq_q": 256, "seq_kv": 256, "heads": 1}


# ----------------------------------------------------------------------
# candidate spaces
# ----------------------------------------------------------------------

class TestFlashSpace:
    def test_enumeration_counts(self):
        sp = resolve_space("flash_attention", V5E, seq_q=1024, seq_kv=2048)
        cands = sp.candidates()
        assert len(cands) >= 500           # the bench's ranking floor
        assert len(set(cands)) == len(cands)
        assert sp.default() in cands

    def test_predict_alignment_and_feasibility(self):
        sp = resolve_space("flash_attention", V5E, seq_q=512, seq_kv=512)
        cands = sp.candidates()
        preds = sp.predict(cands)
        assert len(preds) == len(cands)
        feas = [(c, p) for c, p in zip(cands, preds) if p.feasible]
        assert feas
        for c, p in feas:
            assert math.isfinite(p.seconds) and p.seconds > 0
            assert p.bound
            assert 512 % c.config["block_q"] == 0
            assert 512 % c.config["block_kv"] == 0
        bad = [p for p in preds if not p.feasible]
        assert bad and all(p.reason for p in bad)

    def test_default_always_feasible(self):
        for sq, skv in ((256, 256), (512, 1024), (1024, 4096)):
            sp = resolve_space("flash_attention", V5E, seq_q=sq, seq_kv=skv)
            d = sp.default()
            (p,) = sp.predict([d])
            assert p.feasible, (sq, skv, d.config, p.reason)

    def test_causal_skips_blocks(self):
        """Causal step counts: fewer visited kv blocks than the full
        rectangle, and exact for the square single-block case."""
        sp = resolve_space("flash_attention", V5E, seq_q=512, seq_kv=512)
        assert sp._steps(512, 512) == 1
        full = (512 // 64) * (512 // 128)
        assert sp._steps(64, 128) < full
        sp_nc = resolve_space("flash_attention", V5E, seq_q=512,
                              seq_kv=512, causal=0)
        assert sp_nc._steps(64, 128) == full

    def test_unknown_config_key_rejected(self):
        with pytest.raises(ValueError, match="unknown flash_attention"):
            resolve_space("flash_attention", V5E, seqq=512)

    def test_unknown_family_rejected(self):
        with pytest.raises(ValueError, match="unknown tune family"):
            resolve_space("nope", V5E)


class TestStencilSpaces:
    @pytest.mark.parametrize("family", ["stencil3d7pt", "longrange3d"])
    def test_predict_normalized_volume(self, family):
        """Predictions are per reference volume: small cutouts repeat, so
        the smallest n can't win just by doing less work."""
        sp = resolve_space(family, V5E)
        cands = sp.candidates()
        preds = sp.predict(cands)
        assert len(preds) == len(cands) >= 5
        secs = [p.seconds for p in preds if p.feasible]
        assert all(math.isfinite(s) and s > 0 for s in secs)
        # normalization: the work ratio between extremes is ~1, not ~n^2
        assert max(secs) / min(secs) < 10
        ns = sorted(c.config["n"] for c in cands)
        assert sp.repeats(ns[0]) > sp.repeats(ns[-1]) == 1

    def test_ranked_through_grid_search(self):
        """Stencil predictions come from the compiled plan's ECM ranking —
        cross-check one point against the exact analyze path."""
        sp = resolve_space("stencil3d7pt", V5E)
        c = Candidate.make("stencil3d7pt", n=64)
        (p,) = sp.predict([c])
        kernel = api.load_kernel(sp.TRACE, constants={"M": sp.config["m"]})
        res = api.analyze(kernel.bind(N=64), V5E, "ecm")
        want = (res.t_ecm / res.unit_iterations / V5E.clock_hz
                * sp._points(64) * sp.repeats(64))
        assert p.seconds == pytest.approx(want, rel=1e-9)


# ----------------------------------------------------------------------
# timers
# ----------------------------------------------------------------------

class TestTimers:
    def test_robust_median_rejects_outliers(self):
        med, rejected = robust_median([1.0, 1.1, 0.9, 1.05, 50.0])
        assert rejected == 1
        assert med == pytest.approx(1.025, abs=0.1)

    def test_robust_median_small_samples(self):
        assert robust_median([3.0]) == (3.0, 0)
        assert robust_median([1.0, 3.0]) == (2.0, 0)
        assert robust_median([]) == (math.inf, 0)

    def test_time_closure(self):
        calls = []
        tr = time_closure(lambda: calls.append(1), warmup=2, reps=5)
        assert tr.ok and len(calls) == 7 and len(tr.samples) == 5
        assert tr.wall_s >= 0

    def test_timed_run_roundtrip(self):
        tr = TimedRun(ok=False, wall_s=math.inf, error="boom",
                      timed_out=True, retries=2)
        back = TimedRun.from_dict(json.loads(json.dumps(tr.to_dict())))
        assert back == tr


# ----------------------------------------------------------------------
# measurement (in-process + subprocess isolation)
# ----------------------------------------------------------------------

class _ToySpace(CandidateSpace):
    family = "toy"
    DEFAULTS = {"n": 4}

    def candidates(self):
        return [Candidate.make("toy", k=k) for k in (1, 2)]

    def default(self):
        return Candidate.make("toy", k=1)

    def predict(self, cands, session=None):
        return [Prediction(1e-6 * c.config["k"], bound="compute")
                for c in cands]

    def runner(self, params, interpret=True):
        if params["k"] == 99:
            raise RuntimeError("toy candidate crash")
        return lambda: sum(range(100))


@pytest.fixture
def toy_space():
    register_space(_ToySpace)
    yield
    SPACE_REGISTRY.pop("toy", None)


class TestMeasureInProcess:
    def test_success(self, toy_space):
        tr = measure_candidate("toy", {}, {"k": 1}, V5E, isolate=False,
                               reps=3)
        assert tr.ok and len(tr.samples) == 3

    def test_crash_recorded_not_raised(self, toy_space):
        tr = measure_candidate("toy", {}, {"k": 99}, V5E, isolate=False)
        assert not tr.ok
        assert "toy candidate crash" in tr.error
        assert tr.wall_s == math.inf

    def test_injected_fault(self, toy_space, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE_FAULT", "raise")
        tr = measure_candidate("toy", {}, {"k": 1}, V5E, isolate=False)
        assert not tr.ok and "injected tune fault" in tr.error

    def test_fault_match_filters(self, toy_space, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE_FAULT", "raise")
        monkeypatch.setenv("REPRO_TUNE_FAULT_MATCH", "k=2")
        assert measure_candidate("toy", {}, {"k": 1}, V5E,
                                 isolate=False).ok
        assert not measure_candidate("toy", {}, {"k": 2}, V5E,
                                     isolate=False).ok


class TestMeasureSubprocess:
    """Spawned children must import repro from a clean interpreter, so
    these use the real (registered-at-import) flash family."""
    PARAMS = {"block_q": 128, "block_kv": 128}

    def test_success(self):
        tr = measure_candidate("flash_attention", TINY, self.PARAMS, V5E,
                               warmup=1, reps=2, timeout_s=300)
        assert tr.ok and tr.retries == 0
        assert 0 < tr.wall_s < math.inf

    def test_child_crash_recorded_with_retries(self, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE_FAULT", "exit")
        tr = measure_candidate("flash_attention", TINY, self.PARAMS, V5E,
                               reps=1, retries=1, timeout_s=300)
        assert not tr.ok and tr.retries == 1
        assert "exit code 3" in tr.error

    def test_timeout_kills_hung_child(self, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE_FAULT", "hang")
        tr = measure_candidate("flash_attention", TINY, self.PARAMS, V5E,
                               reps=1, retries=3, timeout_s=3)
        assert not tr.ok and tr.timed_out
        assert "timed out" in tr.error
        assert tr.retries == 0            # hangs are not retried


# ----------------------------------------------------------------------
# the tune loop
# ----------------------------------------------------------------------

class TestTune:
    def test_predict_only(self):
        rep = tune("flash_attention", V5E, config=TINY, measure=False)
        assert rep.chosen_params and rep.measured_chosen_s is None
        assert rep.n_feasible > 0
        assert rep.speedup_vs_default is None
        assert not rep.calibration
        # chosen is the predicted-best feasible candidate
        preds = [c for c in rep.candidates if c.status == "predicted"]
        assert preds[0].params == rep.chosen_params

    def test_measured_inprocess(self):
        rep = tune("flash_attention", V5E, config=TINY, top_k=2, reps=2,
                   isolate=False)
        assert rep.measured_chosen_s is not None
        assert rep.measured_default_s is not None
        assert rep.speedup_vs_default is not None
        assert rep.speedup_vs_default >= 1.0    # argmin includes default
        assert rep.error["n"] >= 2
        assert rep.calibration["time"]["flash_attention"] > 0
        assert rep.machine_fingerprint == V5E.fingerprint

    def test_failed_candidate_does_not_abort(self, monkeypatch):
        """A crashing candidate is recorded 'failed'; the run completes
        and chooses among the survivors."""
        monkeypatch.setenv("REPRO_TUNE_FAULT", "raise")
        monkeypatch.setenv("REPRO_TUNE_FAULT_MATCH", "block_q=256")
        rep = tune("flash_attention", V5E, config=TINY, top_k=3, reps=2,
                   isolate=False)
        assert rep.n_failed >= 1
        failed = [c for c in rep.candidates if c.status == "failed"]
        assert all(c.params["block_q"] == 256 for c in failed)
        assert all("injected tune fault" in c.measured.error
                   for c in failed)
        assert rep.chosen_params["block_q"] != 256
        assert rep.measured_chosen_s is not None

    def test_report_roundtrip(self):
        rep = tune("flash_attention", V5E, config=TINY, top_k=1, reps=2,
                   isolate=False)
        payload = json.loads(json.dumps(rep.to_dict()))
        back = TuneReport.from_dict(payload)
        assert back.to_dict() == rep.to_dict()
        assert back.chosen_params == rep.chosen_params
        text = rep.render()
        assert "chosen:" in text and "speedup" in text

    def test_stencil_family_inprocess(self):
        rep = tune("stencil3d7pt", V5E,
                   config={"m": 6, "n_min": 32, "n_max": 64, "n_step": 16},
                   top_k=1, reps=2, isolate=False)
        assert rep.measured_chosen_s is not None
        assert rep.speedup_vs_default >= 1.0
        assert rep.config["m"] == 6

    def test_service_cache_roundtrip(self, tmp_path):
        svc = AnalysisService(cache_dir=tmp_path)
        rep1 = tune("flash_attention", V5E, config=TINY, measure=False,
                    service=svc)
        assert svc.stats.computed == 1
        rep2 = tune("flash_attention", V5E, config=TINY, measure=False,
                    service=svc)
        assert svc.stats.computed == 1 and svc.stats.memory_hits == 1
        assert rep2.to_dict() == rep1.to_dict()
        svc2 = AnalysisService(cache_dir=tmp_path)
        rep3 = tune("flash_attention", V5E, config=TINY, measure=False,
                    service=svc2)
        assert svc2.stats.computed == 0 and svc2.stats.disk_hits == 1
        assert rep3.to_dict() == rep1.to_dict()


# ----------------------------------------------------------------------
# calibration
# ----------------------------------------------------------------------

class TestCalibration:
    def test_prediction_error(self):
        assert prediction_error([(1.0, 1.0), (2.0, 2.0)]) == {
            "n": 2, "rms_log": 0.0, "geomean_ratio": 1.0}
        e = prediction_error([(1.0, math.e)])
        assert e["rms_log"] == pytest.approx(1.0)
        assert e["geomean_ratio"] == pytest.approx(math.e)
        assert prediction_error([(0.0, 1.0)]) == {"n": 0}

    def test_derive_groups_by_bound(self):
        samples = [(1.0, 2.0, "compute"), (1.0, 8.0, "compute"),
                   (1.0, 3.0, "VMEM")]
        cal = derive_calibration("fam", samples, V5E)
        assert cal["compute"] == pytest.approx(4.0)    # geomean(2, 8)
        assert cal["levels"]["VMEM"] == pytest.approx(3.0)
        assert cal["time"]["fam"] == pytest.approx((2 * 8 * 3) ** (1 / 3))
        assert cal["meta"]["fam.n_samples"] == 3

    def test_derive_preserves_other_families(self):
        m = Machine.from_dict({**_v5e_dict(),
                               "calibration": {"levels": {"VMEM": 7.0},
                                               "time": {"other": 5.0}}})
        cal = derive_calibration("fam", [(1.0, 2.0, "compute")], m)
        assert cal["levels"]["VMEM"] == 7.0     # untouched level kept
        assert cal["time"]["other"] == 5.0      # other family kept
        assert cal["time"]["fam"] == pytest.approx(2.0)

    def test_apply_calibration_roundtrip(self, tmp_path):
        path = tmp_path / "v5e.yaml"
        shutil.copy(machine_yaml_path("tpu_v5e"), path)
        cal = {"compute": 2.0, "levels": {"VMEM": 3.0},
               "time": {"flash_attention": 480.0}}
        mach = apply_calibration(path, cal)
        assert mach.calibration_factor("compute") == 2.0
        assert mach.calibration_factor("level", "VMEM") == 3.0
        assert mach.calibration_factor(
            "time", "flash_attention") == 480.0
        # re-apply replaces the block (idempotent, comments preserved)
        apply_calibration(path, {"compute": 9.0})
        text = path.read_text()
        assert text.count("calibration:") == 1
        assert "#" in text
        m2 = Machine.from_yaml(path)
        assert m2.calibration_factor("compute") == 9.0
        assert m2.calibration_factor("time", "flash_attention") == 1.0

    def test_apply_rejects_invalid_mapping(self, tmp_path):
        path = tmp_path / "v5e.yaml"
        shutil.copy(machine_yaml_path("tpu_v5e"), path)
        before = path.read_text()
        with pytest.raises(ValueError):
            apply_calibration(path, {"levels": {"NOPE": 2.0}})
        assert path.read_text() == before      # file untouched on failure

    def test_machine_yaml_path(self, tmp_path):
        p = machine_yaml_path("tpu_v5e")
        assert p.name == "tpu_v5e.yaml" and p.is_file()
        assert machine_yaml_path("V5E") == p
        assert machine_yaml_path(str(p)) == p
        with pytest.raises(ValueError, match="cannot resolve"):
            machine_yaml_path("no_such_machine")

    def test_calibration_reduces_error(self, tmp_path):
        """The acceptance loop: tune, apply, re-tune — the re-predicted
        error is strictly lower (the time factor removes the bias)."""
        path = tmp_path / "v5e.yaml"
        shutil.copy(machine_yaml_path("tpu_v5e"), path)
        m0 = Machine.from_yaml(path)
        rep0 = tune("flash_attention", m0, config=TINY, top_k=2, reps=2,
                    isolate=False)
        assert rep0.options["time_factor"] == 1.0
        apply_calibration(path, rep0.calibration)
        m1 = Machine.from_yaml(path)
        rep1 = tune("flash_attention", m1, config=TINY, top_k=2, reps=2,
                    isolate=False)
        assert rep1.options["time_factor"] > 1.0
        assert rep1.error["rms_log"] < rep0.error["rms_log"]


# ----------------------------------------------------------------------
# calibrated model flag (opt-in; goldens stay bit-identical when off)
# ----------------------------------------------------------------------

def _v5e_dict():
    import yaml
    with open(machine_yaml_path("tpu_v5e")) as f:
        return yaml.safe_load(f)


class TestCalibratedModels:
    CAL = {"compute": 2.0, "levels": {"VMEM": 3.0}}

    def _machines(self):
        base = _v5e_dict()
        return (Machine.from_dict(base),
                Machine.from_dict({**base, "calibration": self.CAL}))

    def test_ecm_calibrated_scales_terms(self):
        # same-named machine variants: pass explicit sessions, the pooled
        # per-name session would serve whichever Machine arrived first
        from repro.core.session import AnalysisSession
        plain, cal = self._machines()
        s0, s1 = AnalysisSession(plain), AnalysisSession(cal)
        kernel = api.load_kernel("trace:stencil3d7pt",
                                 constants={"M": 16, "N": 128})
        r0 = api.analyze(kernel, plain, "ecm", session=s0)
        r_off = api.analyze(kernel, cal, "ecm", session=s1)
        r_on = api.analyze(kernel, cal, "ecm", session=s1,
                           calibrated=True)
        # off on a calibrated machine: bit-identical payload, no flag key
        assert r_off.to_dict() == r0.to_dict()
        assert "calibrated" not in r_off.to_dict()
        assert r_on.to_dict()["calibrated"] is True
        assert r_on.t_ol == pytest.approx(r0.t_ol * 2.0)
        terms0 = dict(r0.overlapped + r0.contributions)
        terms1 = dict(r_on.overlapped + r_on.contributions)
        for label in terms0:
            f = 3.0 if label.startswith("VMEM") else 1.0
            assert terms1[label] == pytest.approx(terms0[label] * f)

    def test_roofline_calibrated_derates(self):
        from repro.core.session import AnalysisSession
        plain, cal = self._machines()
        s0, s1 = AnalysisSession(plain), AnalysisSession(cal)
        kernel = api.load_kernel("trace:stencil3d7pt",
                                 constants={"M": 16, "N": 128})
        r0 = api.analyze(kernel, plain, "roofline", session=s0)
        r_off = api.analyze(kernel, cal, "roofline", session=s1)
        r_on = api.analyze(kernel, cal, "roofline", session=s1,
                           calibrated=True)
        assert r_off.to_dict() == r0.to_dict()
        assert r_on.to_dict()["calibrated"] is True
        assert r_on.performance <= r0.performance

    def test_grid_search_rejects_calibrated(self):
        _, cal = self._machines()
        kernel = api.load_kernel("trace:stencil3d7pt",
                                 constants={"M": 16})
        with pytest.raises(ValueError, match="uncalibrated compiled"):
            blocking.grid_search(kernel, cal, [("N", [64, 128])],
                                 calibrated=True)


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _no_checkout_compile_cache(monkeypatch):
    """A measuring ``repro tune`` turns on the compile cache; keep the
    suite's compiles out of the checkout's cache directory."""
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)


class TestTuneCLI:
    def _run(self, *argv):
        from repro.cli import main
        return main(list(argv))

    def test_predict_only_json(self, capsys):
        rc = self._run("tune", "flash_attention", "-m", "tpu_v5e",
                       "--no-measure", "--shape", "seq_q", "256",
                       "--shape", "seq_kv", "256", "--json")
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["kind"] == "tune"
        assert payload["config"]["seq_q"] == 256
        assert payload["chosen_params"]
        assert payload["measured_chosen_s"] is None

    def test_measured_with_apply(self, capsys, tmp_path):
        path = tmp_path / "v5e.yaml"
        shutil.copy(machine_yaml_path("tpu_v5e"), path)
        rc = self._run("tune", "flash_attention", "-m", str(path),
                       "--shape", "seq_q", "256", "--shape", "seq_kv",
                       "256", "--shape", "heads", "1", "--top-k", "1",
                       "--reps", "2", "--no-isolate",
                       "--apply-calibration", "--json")
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["speedup_vs_default"] >= 1.0
        assert payload["calibration_written_to"] == str(path)
        assert Machine.from_yaml(path).calibration_factor(
            "time", "flash_attention") > 1.0

    def test_only_measuring_turns_on_compile_cache(self, monkeypatch,
                                                   capsys):
        from repro.launch import compile_cache
        calls = []
        monkeypatch.setattr(compile_cache, "enable_compile_cache",
                            lambda: calls.append(1))
        argv = ("tune", "flash_attention", "-m", "tpu_v5e", "--shape",
                "seq_q", "256", "--shape", "seq_kv", "256", "--shape",
                "heads", "1", "--top-k", "1", "--reps", "1", "--no-isolate")
        assert self._run(*argv, "--no-measure") == 0 and calls == []
        monkeypatch.setenv("REPRO_TUNE_FAULT", "raise")
        assert self._run(*argv) == 1 and calls == [1]

    def test_unknown_family_exit_code(self, capsys):
        assert self._run("tune", "nope", "-m", "tpu_v5e",
                         "--no-measure") == 2


# ----------------------------------------------------------------------
# platform: the interpreter off the TPU, one process on it
# ----------------------------------------------------------------------

class TestPlatform:
    def test_report_records_resolved_interpret(self):
        rep = tune("flash_attention", V5E, config=TINY, top_k=1, reps=1,
                   isolate=False)
        assert rep.options["interpret"] is True      # CPU backend

    def test_tpu_backend_measures_in_process(self, toy_space, monkeypatch):
        """With a TPU backend ``isolate`` is ignored: the toy family is
        registered only in this process, so a spawned child would fail."""
        import repro.kernels.backend as backend
        monkeypatch.setattr(backend, "on_tpu", lambda: True)
        tr = measure_candidate("toy", {}, {"k": 1}, V5E, isolate=True,
                               reps=2)
        assert tr.ok and len(tr.samples) == 2 and tr.retries == 0

    def test_unmeasured_default_fails_the_run(self, monkeypatch):
        from repro.tune import TuneFailed
        monkeypatch.setenv("REPRO_TUNE_FAULT", "raise")
        monkeypatch.setenv("REPRO_TUNE_FAULT_MATCH", "block_q=128")
        with pytest.raises(TuneFailed, match="default candidate"):
            tune("flash_attention", V5E, config=TINY, top_k=1, reps=1,
                 isolate=False)

    def test_cli_exits_nonzero_when_default_fails(self, monkeypatch,
                                                  capsys):
        from repro.cli import main
        monkeypatch.setenv("REPRO_TUNE_FAULT", "raise")
        rc = main(["tune", "flash_attention", "-m", "tpu_v5e",
                   "--shape", "seq_q", "256", "--shape", "seq_kv", "256",
                   "--shape", "heads", "1", "--top-k", "1", "--reps", "1",
                   "--no-isolate"])
        assert rc == 1
        assert "did not measure" in capsys.readouterr().err
