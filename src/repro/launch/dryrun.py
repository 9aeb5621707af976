import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import anywhere: jax locks the
# device count on first init. Do not move them.

import argparse            # noqa: E402
import json                # noqa: E402
import pathlib             # noqa: E402
import time                # noqa: E402
import traceback           # noqa: E402

import jax                 # noqa: E402

from repro import configs                       # noqa: E402
from repro.core import hlo_analysis             # noqa: E402
from repro.launch.cell import build_cell, shard  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def run_cell(arch: str, shape: str, multi_pod: bool,
             out_dir: pathlib.Path = ARTIFACTS, verbose: bool = True,
             donate: bool = True) -> dict:
    """Lower + compile one (arch × shape × mesh) cell; record the §Dry-run /
    §Roofline evidence (memory fit, FLOPs/bytes, collective schedule).

    ``donate`` aliases the streaming state (train: params+opt; serve: the
    KV caches) into the outputs — the production in-place-update pattern;
    without it every decode step double-buffers the whole cache
    (EXPERIMENTS.md §Perf round 1).
    """
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.perf_counter()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cell = build_cell(arch, shape, multi_pod=multi_pod)
    donate_args = ()
    if donate:
        donate_args = {"train": (0, 1), "prefill": (2,)}.get(
            cell.shape.kind, (1,))

    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn,
                         in_shardings=shard(mesh, cell.in_specs),
                         out_shardings=shard(mesh, cell.out_specs),
                         donate_argnums=donate_args)
        lowered = jitted.lower(*cell.abstract_args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    hlo_txt = compiled.as_text()
    ana = hlo_analysis.analyze_hlo_text(hlo_txt)
    report = hlo_analysis.roofline_from_compiled(
        compiled, arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        model_flops_global=cell.model_flops_global, hlo_text=hlo_txt)
    rec = report.to_dict()
    rec.update(
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=int(mem.argument_size_in_bytes),
            output_bytes=int(mem.output_size_in_bytes),
            temp_bytes=int(mem.temp_size_in_bytes),
            alias_bytes=int(mem.alias_size_in_bytes),
            # donated outputs alias their inputs: count them once
            total_per_device=int(mem.argument_size_in_bytes
                                 + mem.output_size_in_bytes
                                 + mem.temp_size_in_bytes
                                 - mem.alias_size_in_bytes)),
        schedule_head=[{
            "kind": r.kind, "bytes": r.result_bytes, "x": r.multiplier,
            "group": r.group_size} for r in ana.schedule[:24]],
        top_traffic=[{"op": n, "bytes": int(b)}
                     for n, b in ana.top_traffic(12)],
    )
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"{arch}__{shape}__{mesh_name}.json", "w") as f:
        json.dump(rec, f, indent=1)
    if verbose:
        gb = rec["memory"]["total_per_device"] / 2**30
        print(f"[dryrun] {arch} x {shape} x {mesh_name}: "
              f"compile {t_compile:.1f}s, {gb:.2f} GiB/device, "
              f"T=(c {report.t_compute*1e3:.2f} | m {report.t_memory*1e3:.2f}"
              f" | x {report.t_collective*1e3:.2f}) ms, "
              f"dominant={report.dominant}, "
              f"useful={report.useful_flop_ratio:.2f}")
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=configs.ARCH_IDS)
    ap.add_argument("--shape", choices=list(configs.SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every supported (arch x shape) cell")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for a, s in configs.cells():
            print(f"{a:30s} {s}")
        return

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if args.all:
        todo = configs.cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        todo = [(args.arch, args.shape)]

    failures = []
    for arch, shape in todo:
        for mp in meshes:
            try:
                run_cell(arch, shape, mp)
            except Exception as e:             # noqa: BLE001
                failures.append((arch, shape, mp, repr(e)))
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall dry-run cells compiled OK")


if __name__ == "__main__":
    main()
