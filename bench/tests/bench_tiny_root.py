"""A temporary checkout with tiny cells added, for the benchmark's CPU tests.

The copy holds ``BENCHMARK.json`` and ``bench/`` as they are in the
repository, with ``src`` linked in. ``add_tiny_cells`` then adds only new
files (a configuration, a traffic mix, the limits) and new entries, as a
later PR would; no file that was there is edited."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

PEAKS = json.loads((REPO / "bench" / "peaks.json").read_text())["TPU v5 lite"]

TINY_LM = {"name": "lm-tiny", "hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "num_hidden_layers": 2, "vocab_size": 200}
TINY_CHAT = {"driver": "serve", "prompt_len": 8, "max_new": 8, "clients": 2,
             "batch": 2, "max_len": 16, "check_requests": 8, "ref_batch": 4,
             "trace_seconds": 0.3}
#: the tiny cells' limits, set from CPU readings at these sizes: the
#: program's logit gap 0 to 0.031 and the float8 control's 0.127 to 0.652
#: over 12 seeds; the stencils' relative error 0 and the bfloat16
#: control's 1.3e-3 to 6.8e-3 over 3 seeds
TINY_LIMITS = {"logit_gap": 0.06, "rel_err": 1e-4}


def copy_checkout(dst: pathlib.Path, with_src: bool = True) -> pathlib.Path:
    dst.mkdir(parents=True, exist_ok=True)
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    if with_src:
        (dst / "src").symlink_to(REPO / "src")
    return dst


def add_tiny_cells(root: pathlib.Path) -> dict:
    """Add tiny stencil and LM cells by new files and new entries only;
    returns ``{cell name: traffic}``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    b = root / "bench"
    st = json.loads((b / "configs" / "stencil-paper.json").read_text())
    st.update(name="stencil-tiny", M=12, N=16)
    (b / "configs" / "stencil-tiny.json").write_text(json.dumps(st))
    lm = json.loads((b / "configs" / "phi3-mini-3.8b.json").read_text())
    lm.update(TINY_LM)
    (b / "configs" / "lm-tiny.json").write_text(json.dumps(lm))
    (b / "traffic" / "tiny-chat.json").write_text(json.dumps(TINY_CHAT))
    for name, cfg in (("stencil-tiny", st), ("lm-tiny", lm)):
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "CPU test"})
    cells = {"stencil-tiny.longrange25pt": "longrange25pt",
             "stencil-tiny.jacobi7pt": "jacobi7pt",
             "lm-tiny.tiny-chat": "tiny-chat"}
    for cell, traffic in cells.items():
        bench["workloads"].append({"name": cell, "config": cell.split(".")[0],
                                   "traffic": traffic, "chips": 1,
                                   "why": "CPU test"})
        key = "logit_gap" if traffic == "tiny-chat" else "rel_err"
        (b / "limits" / f"{cell}.json").write_text(
            json.dumps({key: TINY_LIMITS[key]}))
    twin = {"stencil-tiny.longrange25pt": "stencil-paper.longrange25pt",
            "stencil-tiny.jacobi7pt": "stencil-paper.longrange25pt",
            "lm-tiny.tiny-chat": "phi3-mini-3.8b.chat-decode"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        for cell, like in twin.items():
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return cells


def run_cell(root: pathlib.Path, cell: str, trace: int = 0,
             seed: int = 3000000001, seconds: float = 0.5,
             extra: tuple = ()) -> dict:
    """One run through the harness, with its look for a chip skipped."""
    from bench import harness
    return harness.run(["--workload", cell, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", str(trace),
                        *extra],
                       time.perf_counter(), root=root, require_tpu=False,
                       peaks=PEAKS)
