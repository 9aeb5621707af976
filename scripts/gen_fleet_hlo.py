#!/usr/bin/env python
"""Generate the checked-in fleet HLO dumps (src/repro/configs/hlo/).

For every config in ``repro.configs.ARCH_IDS`` this lowers + compiles the
*reduced* (family-preserving, DESIGN.md §4) model on a local 2x2
forced-host-device mesh — tensor parallelism tp=2 so the modules carry
real collectives — and gzips the per-device HLO text of a small prefill
step.  The dumps make ``python -m repro fleet --all`` and the CI fleet
gate fully deterministic and jax-free at analysis time; regenerate only
when the model code or the reduced configs change (then refresh the
goldens too, see docs/fleet.md):

    PYTHONPATH=src python scripts/gen_fleet_hlo.py [CONFIG ...]

Requires jax (any backend; the CPU wheel is enough).
"""
from __future__ import annotations

import dataclasses
import gzip
import os
import pathlib
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch.cell import build_cell, shard  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402

OUT_DIR = ROOT / "src" / "repro" / "configs" / "hlo"
# small but structurally faithful: enough tokens that dots/collectives
# dominate parameters, small enough that every config compiles in seconds
SHAPE = configs.ShapeSpec("fleet_prefill", "prefill", seq=128, batch=4)


def generate(arch: str) -> pathlib.Path:
    cfg = dataclasses.replace(configs.reduced(configs.get_config(arch)),
                              tp=2)
    cell = build_cell(arch, SHAPE, cfg=cfg)
    mesh = make_local_mesh(data=2, model=2)
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            cell.fn,
            in_shardings=shard(mesh, cell.in_specs),
            out_shardings=shard(mesh, cell.out_specs),
        ).lower(*cell.abstract_args).compile()
    text = compiled.as_text()
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    path = OUT_DIR / f"{arch}.hlo.gz"
    # mtime=0 -> byte-identical archives for identical HLO across runs
    path.write_bytes(gzip.compress(text.encode(), mtime=0))
    print(f"  {arch}: {len(text)} chars -> {path.stat().st_size} bytes "
          f"({path.relative_to(ROOT)})")
    return path


def main(argv=None) -> int:
    archs = (argv or sys.argv[1:]) or list(configs.ARCH_IDS)
    print(f"generating fleet HLO dumps for {len(archs)} configs "
          f"(devices: {jax.device_count()})")
    failed = []
    for arch in archs:
        try:
            generate(arch)
        except Exception as e:  # noqa: BLE001 - report, then fail the run
            failed.append(arch)
            print(f"  {arch}: FAILED ({type(e).__name__}: {e})")
    if failed:
        print(f"failed: {', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
