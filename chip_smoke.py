#!/usr/bin/env python3
"""Chip smoke test: run the main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # four chips: the sharded serving path

One chip, one JSON line per phase:

(a) device: the platform must be ``tpu``; ``device_kind`` picks the
    machine description (an unknown kind is an error).
(b) kernels: the three Pallas kernels, compiled, against ``kernels/ref.py``
    in float32 — stencil3d7pt and longrange3d at the paper's M=130, N=1015,
    flash attention at phi3-mini's head shape for one prefill and one
    decode shape — each with its host-clock time around
    ``block_until_ready`` and the tool's own ECM or Roofline prediction.
(c) tuner: ``tune()`` measures the default and the top-2 predicted
    candidates of flash attention and stencil3d7pt in this process.
(d) server: phi3-mini-3.8b at its published widths and all 32 layers,
    random weights from a fixed seed, serves 8 requests of 256-1024 prompt
    tokens (32 new tokens each, batch 4, ``max_len`` 2048) through
    ``repro.launch.serve``. Replaying the served tokens through prefill
    and cached decode gives the logits they were picked from (their argmax
    must be the served tokens); those of one unpadded request are checked
    against one full ``model.forward`` over the same tokens.

``--chips 4`` runs only the sharded path: the same requests served with
params and KV caches sharded over a 2x2 (data, model) mesh by the serving
rule, and on one device of the four. Each run's tokens are replayed
through both placements, and the logits compared at every served
position.

The last line of standard output is ``{"ok": true, "device": {...}}``. Any
failure raises before it, so the exit code is non-zero; nothing falls back
to the CPU. The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
import numpy as np                                       # noqa: E402

from repro import configs                                # noqa: E402
from repro.core import api, machine as machine_mod      # noqa: E402
from repro.kernels import ops, ref                       # noqa: E402
from repro.launch import serve as serve_mod              # noqa: E402
from repro.launch.compile_cache import (count_compiles,  # noqa: E402
                                        enable_compile_cache)
from repro.serve import Engine                           # noqa: E402

ARCH = "phi3-mini-3.8b"
M, N = 130, 1015                       # the paper's stencil size
#: flash attention (name, batch, seq_q, seq_kv) at phi3-mini's heads
FLASH_SHAPES = (("flash_prefill", 1, 4096, 4096),
                ("flash_decode", 8, 1, 8192))
#: tuner runs: (family, problem shape); phi3-mini's heads for flash
TUNE_RUNS = (("flash_attention", {"batch": 1, "seq_q": 2048, "seq_kv": 2048,
                                  "causal": True, "dtype": "bfloat16"}),
             ("stencil3d7pt", {"m": M, "n_min": 256, "n_max": 1024,
                               "n_step": 256}))
#: the served traffic: 8 requests of 256-1024 prompt tokens, 32 new each
REQUESTS, PROMPT_LEN, MAX_NEW = 8, (256, 1024), 32
MAX_LEN, BATCH = 2048, 4
COEFFS = dict(W=0.1, E=0.2, N=0.3, S=0.15, F=0.25, B=0.05, s=-1.0)
LR_COEFFS = [0.5, 0.1, 0.05, 0.02, 0.01]
#: logits vs their reference (model.forward, or one device for the 2x2
#: mesh): both sides run bf16 activations and round differently, which on
#: CPU at 4-8 layers of phi3-mini width moves logits by up to 1.3% of the
#: largest |logit|; 2**-4 leaves room for 32 layers, while a wrong cache
#: slot or sharding moves them by the whole range
LOGIT_TOL = 2.0 ** -4


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def timed(fn, *args):
    """(result, seconds) of one call, on the host clock, ending in
    ``block_until_ready``."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def check_close(name: str, got, want, rtol: float, atol: float) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite output")
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                               err_msg=name)
    return float(np.max(np.abs(got - want)))


def ecm_seconds(trace: str, radius: int, mach) -> float:
    """The ECM model's seconds for one sweep of the (M, N, N) float32
    stencil: cycles per unit of work times units, over the clock."""
    res = api.analyze(trace, mach, "ecm", constants={"M": M, "N": N},
                      frontend_opts={"element_bytes": 4})
    points = (M - 2 * radius) * (N - 2 * radius) ** 2
    return res.t_ecm / res.unit_iterations * points / mach.clock_hz


def flash_roofline(mach, b, h, sq, skv, d, bq, bkv) -> float:
    """The flash-attention space's closed-form MXU/VPU/HBM bound for these
    blocks, in seconds."""
    from repro.tune import Candidate, resolve_space
    space = resolve_space("flash_attention", mach, batch=b, heads=h,
                          seq_q=sq, seq_kv=skv, head_dim=d, causal=True,
                          dtype="bfloat16")
    cand = Candidate.make("flash_attention", block_q=bq, block_kv=bkv)
    return space.predict([cand])[0].seconds


def phase_device(want_count: int):
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform} "
                         f"({dev.device_kind})")
    if len(devs) < want_count:
        raise SystemExit(f"{want_count} chips asked for, {len(devs)} found")
    mach = machine_mod.for_device_kind(dev.device_kind)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    return device, mach


def phase_kernels(mach) -> dict:
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    hp = jax.default_matmul_precision("highest")
    rows = []

    # stencil3d7pt at the paper's size
    a = jax.random.normal(k1, (M, N, N), jnp.float32)
    cvec = [COEFFS[c] for c in "WENSFB"] + [COEFFS["s"]]
    _, first = timed(ops.stencil3d7pt, a, cvec)
    out, wall = timed(ops.stencil3d7pt, a, cvec)
    with hp:
        want = jax.jit(ref.stencil3d7pt)(a, COEFFS)
    err = check_close("stencil3d7pt", out, want, 2e-5, 1e-6)
    rows.append({"kernel": "stencil3d7pt", "shape": [M, N, N],
                 "max_abs_err": err, "first_call_s": first, "wall_s": wall,
                 "ecm_s": ecm_seconds("trace:stencil3d7pt", 1, mach)})
    del a, out, want

    # longrange3d at the paper's size
    shape = (M, N, N)
    u = jax.random.normal(k1, shape, jnp.float32)
    v = jax.random.normal(k2, shape, jnp.float32)
    roc = jax.random.normal(k3, shape, jnp.float32) * 0.1
    c = jnp.asarray(LR_COEFFS, jnp.float32)
    _, first = timed(ops.longrange3d, u, v, roc, c)
    out, wall = timed(ops.longrange3d, u, v, roc, c)
    with hp:
        want = jax.jit(ref.longrange3d)(u, v, roc, c)
    err = check_close("longrange3d", out, want, 2e-4, 1e-5)
    rows.append({"kernel": "longrange3d", "shape": list(shape),
                 "max_abs_err": err, "first_call_s": first, "wall_s": wall,
                 "ecm_s": ecm_seconds("trace:longrange3d", 4, mach)})
    del u, v, roc, out, want

    # flash attention at phi3-mini's head shape: prefill and decode
    cfg = configs.get_config(ARCH)
    h, d = cfg.n_heads, cfg.head_dim
    for name, b, sq, skv in FLASH_SHAPES:
        q = jax.random.normal(k1, (b, h, sq, d), jnp.bfloat16)
        k = jax.random.normal(k2, (b, h, skv, d), jnp.bfloat16)
        vv = jax.random.normal(k3, (b, h, skv, d), jnp.bfloat16)
        _, first = timed(ops.flash_attention, q, k, vv)
        out, wall = timed(ops.flash_attention, q, k, vv)
        with hp:
            want = jax.jit(ref.attention)(q, k, vv)
        err = check_close(name, out, want, 2e-2, 2e-2)
        bq, bkv = ops.flash_blocks(sq, skv, d, 2)
        rows.append({"kernel": name, "shape": [b, h, sq, skv, d],
                     "blocks": [bq, bkv], "max_abs_err": err,
                     "first_call_s": first, "wall_s": wall,
                     "roofline_s": flash_roofline(mach, b, h, sq, skv, d,
                                                  bq, bkv)})
        del q, k, vv, out, want
    return {"kernels": rows}


def phase_tuner(mach) -> dict:
    from repro.tune import tune
    cfg = configs.get_config(ARCH)
    rows = []
    for family, config in TUNE_RUNS:
        if family == "flash_attention":
            config = {**config, "heads": cfg.n_heads,
                      "head_dim": cfg.head_dim}
        rep = tune(family, mach, config=config, top_k=2)
        if rep.options["interpret"]:
            raise AssertionError(f"{family}: tuner ran the interpreter")
        if rep.measured_default_s is None or rep.measured_chosen_s is None:
            raise AssertionError(f"{family}: default or chosen not measured")
        rows.append({"family": family, "interpret": rep.options["interpret"],
                     "default": rep.default_params,
                     "chosen": rep.chosen_params,
                     "measured_default_s": rep.measured_default_s,
                     "measured_chosen_s": rep.measured_chosen_s,
                     "predicted_chosen_s": rep.predicted_chosen_s,
                     "n_measured": len(rep.measured_outcomes),
                     "failed": [(c.params, c.measured.error[:200])
                                for c in rep.measured_outcomes
                                if c.status == "failed"]})
    return {"runs": rows}


def requests(vocab: int):
    return serve_mod.make_requests(vocab, REQUESTS, PROMPT_LEN, MAX_NEW)


def argmax_agree(done, logits) -> float:
    """Share of served tokens that are the argmax of their replayed
    logits."""
    hits = sum(int(t == p) for r in done
               for t, p in zip(r.result, np.argmax(logits[r.uid], -1)))
    return hits / sum(len(r.result) for r in done)


def phase_server() -> dict:
    cfg, model, params = serve_mod.build(ARCH)
    engine = Engine(model, params, max_len=MAX_LEN)
    res = serve_mod.serve(engine, requests(cfg.vocab), batch_size=BATCH)
    logits = serve_mod.replay_logits(engine, res.done, BATCH)
    picked = argmax_agree(res.done, logits)
    if picked != 1.0:
        raise AssertionError(f"replayed logits pick {picked} of the served "
                             f"tokens, not all")
    req = serve_mod.unpadded(res, BATCH)
    chk = serve_mod.forward_logit_error(model, params, req, logits[req.uid])
    tol = LOGIT_TOL * chk["max_abs_logit"]
    if not chk["max_abs_err"] <= tol:
        raise AssertionError(f"served logits differ from model.forward by "
                             f"{chk['max_abs_err']} > {tol}")
    return {"arch": ARCH, "layers": cfg.n_layers, "d_model": cfg.d_model,
            "requests": len(res.done), "tokens": res.tokens,
            "serve_s_incl_compile": res.seconds, "batches": res.batches,
            "served_argmax_agree": picked, "checked_uid": req.uid,
            "prompt_len": len(req.tokens), **chk, "tol": tol}


def phase_sharded() -> dict:
    """Serve the requests on one device and on the 2x2 mesh, then replay
    each run's tokens through both placements and compare the logits at
    every served position."""
    from repro.launch.cell import rule_for
    from repro.launch.mesh import make_local_mesh
    cfg, model, params = serve_mod.build(ARCH)
    one = Engine(model, params, max_len=MAX_LEN)
    del params
    res1 = serve_mod.serve(one, requests(cfg.vocab), batch_size=BATCH)
    one_on_1 = serve_mod.replay_logits(one, res1.done, BATCH)

    mesh = make_local_mesh(data=2, model=2)
    rule = rule_for(cfg, configs.SHAPES["decode_32k"], multi_pod=False)
    shd = Engine(model, one.params, max_len=MAX_LEN, mesh=mesh, rule=rule)
    # the first device holds either the whole weights or its shard, never
    # both next to a KV cache
    one.params = None
    gc.collect()
    res4 = serve_mod.serve(shd, requests(cfg.vocab), batch_size=BATCH)
    shd_on_1 = serve_mod.replay_logits(shd, res1.done, BATCH)
    shd_on_4 = serve_mod.replay_logits(shd, res4.done, BATCH)
    one.params = jax.device_put(shd.params, jax.devices()[0])
    del shd
    gc.collect()
    one_on_4 = serve_mod.replay_logits(one, res4.done, BATCH)

    worst, top, positions = 0.0, 0.0, 0
    for ref_run, mesh_run in ((one_on_1, shd_on_1), (one_on_4, shd_on_4)):
        for uid, want in ref_run.items():
            worst = max(worst, float(np.max(np.abs(mesh_run[uid] - want))))
            top = max(top, float(np.max(np.abs(want))))
            positions += len(want)
    tol = LOGIT_TOL * top
    if not worst <= tol:
        raise AssertionError(f"2x2 logits differ from one device by "
                             f"{worst} > {tol}")
    picked = argmax_agree(res1.done, one_on_1)
    if picked != 1.0:
        raise AssertionError(f"replayed logits pick {picked} of the "
                             f"one-device tokens, not all")
    agree = sum(x == y for r1, r4 in zip(res1.done, res4.done)
                for x, y in zip(r1.result, r4.result))
    return {"arch": ARCH, "mesh": dict(mesh.shape), "requests": len(res1.done),
            "positions_compared": positions, "max_abs_err": worst,
            "max_abs_logit": top, "tol": tol,
            "served_argmax_agree_one_device": picked,
            "served_argmax_agree_2x2": argmax_agree(res4.done, shd_on_4),
            "tokens_agree": agree, "tokens": res1.tokens,
            "serve_s_one_device": res1.seconds, "serve_s_2x2": res4.seconds}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: serve on a 2x2 mesh vs one device, nothing "
                         "else")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    compiles = count_compiles()
    device, mach = phase_device(args.chips)
    emit({"phase": "a_device", **device, "machine": mach.name,
          "compile_cache": cache_dir})
    if args.chips == 4:
        phases = [("sharded_2x2", phase_sharded)]
    else:
        phases = [("b_kernels", lambda: phase_kernels(mach)),
                  ("c_tuner", lambda: phase_tuner(mach)),
                  ("d_server", phase_server)]
    for name, phase in phases:
        before = compiles.to_dict()
        t0 = time.perf_counter()
        rec = phase()
        gc.collect()
        after = compiles.to_dict()
        emit({"phase": name, **rec, "phase_s": time.perf_counter() - t0,
              "compile": {k: after[k] - before[k] for k in after}})
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
