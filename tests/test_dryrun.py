"""Multi-pod dry-run plumbing: representative cells must LOWER against
both production meshes (full compile runs via launch/dryrun.py --all; the
artifacts in artifacts/dryrun/ are the evidence)."""
import pytest

CELLS = [("granite-8b", "train_4k"),
         ("deepseek-v3-671b", "decode_32k"),
         ("mamba2-2.7b", "long_500k"),
         ("whisper-small", "prefill_32k")]


@pytest.mark.parametrize("multi_pod", [False, True],
                         ids=["pod16x16", "pod2x16x16"])
def test_cells_lower(devices8, multi_pod):
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax
from repro.launch.cell import build_cell, shard
from repro.launch.mesh import make_production_mesh
mesh = make_production_mesh(multi_pod={multi_pod})
assert mesh.devices.size == {512 if multi_pod else 256}
for arch, shape in {CELLS!r}:
    cell = build_cell(arch, shape, multi_pod={multi_pod})
    with jax.set_mesh(mesh):
        jax.jit(cell.fn, in_shardings=shard(mesh, cell.in_specs),
                out_shardings=shard(mesh, cell.out_specs)).lower(
            *cell.abstract_args)
    print("lowered", arch, shape)
print("ALL LOWERED")
"""
    assert "ALL LOWERED" in devices8(code, timeout=500)


def test_unsupported_cell_raises():
    from repro.launch.cell import build_cell
    with pytest.raises(ValueError, match="skips"):
        build_cell("granite-8b", "long_500k")


def test_artifacts_exist_and_complete():
    """After the sweep, every supported cell has both mesh artifacts."""
    import json
    import pathlib

    from repro import configs
    art = pathlib.Path(__file__).resolve().parent.parent / "artifacts" / \
        "dryrun"
    if not art.exists() or len(list(art.glob("*.json"))) < 66:
        pytest.skip("full dry-run sweep not yet complete")
    for arch, shape in configs.cells():
        for mesh in ("pod16x16", "pod2x16x16"):
            p = art / f"{arch}__{shape}__{mesh}.json"
            assert p.exists(), p.name
            r = json.loads(p.read_text())
            assert r["t_compute"] >= 0 and r["memory"]["total_per_device"] > 0
            assert r["dominant"] in ("compute", "memory", "collective")
