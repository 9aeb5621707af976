"""Per-cell dry-run profiler: compiles one (arch x shape) cell on the
single-pod mesh and prints the three roofline terms + the top HBM-traffic
and MXU-FLOP contributors — the "profile" the §Perf hillclimbing reads.

    PYTHONPATH=src python benchmarks/profile_cell.py <arch> <shape>
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import sys, time
import jax
from repro.launch.cell import build_cell, shard
from repro.launch.mesh import make_production_mesh
from repro.core import hlo_analysis as H

arch, shape = sys.argv[1], sys.argv[2]
donate = {"train": (0, 1), "prefill": (2,)}
mesh = make_production_mesh(multi_pod=False)
cell = build_cell(arch, shape, multi_pod=False)
dn = donate.get(cell.shape.kind, (1,))
t0 = time.perf_counter()
with jax.set_mesh(mesh):
    compiled = jax.jit(cell.fn, in_shardings=shard(mesh, cell.in_specs),
                       out_shardings=shard(mesh, cell.out_specs),
                       donate_argnums=dn).lower(*cell.abstract_args).compile()
ana = H.analyze_hlo_text(compiled.as_text())
mem = compiled.memory_analysis()
tot = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes - mem.alias_size_in_bytes
print(f"== {arch} x {shape} (compile {time.perf_counter()-t0:.0f}s) ==")
print(f"mem/device: {tot/2**30:.1f} GiB (arg {mem.argument_size_in_bytes/2**30:.1f} "
      f"temp {mem.temp_size_in_bytes/2**30:.1f} alias {mem.alias_size_in_bytes/2**30:.1f})")
print(f"T_comp {ana.mxu_flops/H.PEAK_FLOPS_BF16*1e3:9.1f} ms | "
      f"T_mem {ana.hbm_bytes/H.HBM_BW*1e3:9.1f} ms | "
      f"T_coll {ana.collective_wire_bytes/H.ICI_LINK_BW*1e3:9.1f} ms | "
      f"useful {cell.model_flops_global/256/ana.mxu_flops:.3f}")
print("collectives:", {k: f"{v/2**30:.1f}GiB" for k, v in ana.collective_by_kind.items()})
print("-- top traffic --")
for name, b in ana.top_traffic(12):
    print(f"  {b/2**30:9.2f} GiB  {name[:110]}")
print("-- top flops --")
for name, f in ana.top_flops(8):
    print(f"  {f/1e12:9.1f} TF   {name[:110]}")
