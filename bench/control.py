"""The readings a cell's limits are set from, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed: make the cell's inputs, warm up, run a short window at
the cell's own load, free the program's state, and print one JSON line
with the compared numbers twice: as the program's results give them
(the lower reading), and with the control in the program's place (the
upper reading): the plain reference computed in the precision below
the configuration's (bfloat16 for float32, float8 for bfloat16). The
benchmark's own runs never run the control.
"""
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:] = [p for p in sys.path
               if pathlib.Path(p or ".").resolve() != ROOT / "bench"]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402


def readings(root, workload: str, seeds, seconds: float,
             require_tpu: bool = True):
    """Yield ``{"seed", "program": {...}, "control": {...}}`` per seed."""
    c = harness.resolve(root, workload)
    devs = harness.devices_for(c.cell, require_tpu)
    harness.enable_compile_cache(root)
    for seed in seeds:
        seed = seed % 2 ** 63
        drv = c.driver.Driver(c.cfg, c.traffic, seed,
                              harness.seed_key(seed))
        drv.setup()
        res = drv.window(seconds)
        drv.release()
        t = time.perf_counter()
        prog = dict(drv.check())
        t_ref = time.perf_counter() - t
        prog_stats = getattr(drv, "gap_stats", None)
        ctrl = dict(drv.check(control=True))
        yield {"seed": seed, "attempted": res["attempted"],
               "program": prog, "control": ctrl,
               "stats": {"program": prog_stats,
                         "control": getattr(drv, "gap_stats", None)},
               "reference_s": t_ref,
               "device": devs[0].device_kind}
        del drv
        gc.collect()


def main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        for rec in readings(ROOT, args.workload, seeds, args.seconds):
            print(json.dumps(rec), flush=True)
    except harness.Refused as e:
        harness.log(f"refused: {e}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
