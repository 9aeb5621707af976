"""Model-driven autotuner: predict → measure → calibrate (DESIGN.md §12).

The analytic models (ECM/Roofline over the compiled sweep plans) rank a
kernel family's whole configuration space in milliseconds; real timers
measure only the top-k; the measured/predicted ratios become per-machine
calibration factors written back into the machine YAML.  See
``docs/autotune.md``.
"""
from .calibrate import (apply_calibration, derive_calibration,
                        machine_yaml_path, prediction_error)
from .measure import TimedRun, measure_candidate, robust_median, time_closure
from .report import CandidateOutcome, TuneReport
from .space import (SPACE_REGISTRY, Candidate, CandidateSpace, Prediction,
                    register_space, resolve_space)
from .tuner import TuneFailed, tune

__all__ = [
    "Candidate", "CandidateOutcome", "CandidateSpace", "Prediction",
    "SPACE_REGISTRY", "TimedRun", "TuneFailed", "TuneReport", "apply_calibration",
    "derive_calibration", "machine_yaml_path", "measure_candidate",
    "prediction_error", "register_space", "resolve_space",
    "robust_median", "time_closure", "tune",
]
