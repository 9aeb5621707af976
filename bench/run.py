"""One run of one benchmark cell; see ``bench/harness.py``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Prints one JSON object as the last line of standard output, or nothing
and a non-zero exit code when the run cannot be made here (no TPU, too
few chips, the program missing from the checkout).
"""
import time

T0 = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# this directory's modules are reached as ``bench.*``, never top-level
sys.path[:] = [p for p in sys.path
               if pathlib.Path(p or ".").resolve() != ROOT / "bench"]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
