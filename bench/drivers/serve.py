"""Driver for LM serving: closed-loop batches of requests through
``repro.launch.serve.serve`` (``BatchedServer`` -> ``Engine.generate`` ->
``LM.prefill`` and cached ``LM.decode_step``), greedy.

Traffic parameters (``bench/traffic/<mix>.json``):

- ``prompt_len``, ``max_new``: every request's prompt length and greedy
  tokens; the prompt's ids are uniform over the vocabulary, from the seed;
- ``clients``: requests outstanding; the next batch of ``clients``
  requests is sent when the last one has been answered (closed loop);
- ``batch``: the server's batch size; ``max_len``: its KV-cache length;
- ``check_requests``: how many completed requests, drawn from the seed,
  the reference re-computes; ``ref_batch`` of them at a time;
- ``trace_seconds``: the length of the traced run's window.

The weights are made on the device from the seed in one jitted call, in
the program's layout and in bfloat16, the type they are served in.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import lm as counts
from bench.reference import lm as reference


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a Hugging Face style config:
    dense, rotary GQA, SwiGLU, RMSNorm, served on one chip (no head
    padding for tensor parallelism)."""
    from repro.configs import ArchConfig
    k = counts.dims(cfg)
    return ArchConfig(name=cfg["name"], family="dense", n_layers=k["L"],
                      d_model=k["d"], n_heads=k["h"], n_kv_heads=k["kvh"],
                      head_dim=k["hd"], d_ff=k["ff"], vocab=k["V"],
                      rope_theta=float(cfg["rope_theta"]), act="swiglu",
                      norm="rmsnorm", tp=1, act_dtype=cfg["torch_dtype"])


def init_scales(cfg: dict) -> dict:
    """Standard deviation of each weight by its name: 1/sqrt(fan-in),
    with the head's fan-in taken as d (tied embedding); norm gains are
    stored as offsets from 1 and start at 0."""
    k = counts.dims(cfg)
    d = k["d"]
    return {"embed": d ** -0.5, "wq": d ** -0.5, "wk": d ** -0.5,
            "wv": d ** -0.5, "wo": (k["h"] * k["hd"]) ** -0.5,
            "w_gate": d ** -0.5, "w_up": d ** -0.5,
            "w_out": k["ff"] ** -0.5, "ln": 0.0, "final_ln": 0.0}


def make_weights(model, cfg: dict, key):
    """Random weights in the program's parameter tree, made on the device
    in one jitted call, in the served dtype."""
    from repro.models.common import is_rec
    recs = model.param_recs()
    leaves, tree = jax.tree_util.tree_flatten_with_path(recs,
                                                        is_leaf=is_rec)
    scales = init_scales(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    plan = []
    for path, rec in leaves:
        name = str(getattr(path[-1], "key", path[-1]))
        if name not in scales:
            raise KeyError(f"no initial scale for weight {name!r}")
        plan.append((tuple(rec.shape), scales[name]))

    @jax.jit
    def make(key):
        out = []
        for i, (shape, scale) in enumerate(plan):
            if scale == 0.0:
                out.append(jnp.zeros(shape, dtype))
                continue
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out.append((x * scale).astype(dtype))
        return out

    return jax.tree_util.tree_unflatten(tree, make(key))


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, key):
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.key = seed, key
        self.done: list = []          # (prompt, result) of each request
        self.rounds = 0               # batches answered in the window

    def _requests(self, round_: int, max_new: int | None = None):
        from repro.serve.engine import Request
        t = self.traffic
        rng = np.random.default_rng([self.seed, 2, round_ + 1])
        vocab = self.cfg["vocab_size"]
        return [Request(uid=round_ * t["clients"] + i,
                        tokens=rng.integers(0, vocab,
                                            t["prompt_len"]).tolist(),
                        max_new=max_new or t["max_new"])
                for i in range(t["clients"])]

    def setup(self) -> None:
        from repro.launch.serve import serve
        from repro.models.lm import LM
        from repro.serve import Engine
        t0 = time.perf_counter()
        self.model = LM(arch_config(self.cfg))
        self.params = jax.block_until_ready(
            make_weights(self.model, self.cfg, self.key))
        t1 = time.perf_counter()
        self.engine = Engine(self.model, self.params,
                             max_len=self.traffic["max_len"])
        self.serve = functools.partial(serve, self.engine,
                                       batch_size=self.traffic["batch"])
        self.fwd = jax.jit(functools.partial(reference.logits, self.cfg),
                           static_argnums=(2, 3, 4))
        # warm: one batch of the mix's prefill shape and the decode shape,
        # answering 2 tokens; then the eager ops that join a batch's
        # tokens and read them back by row, at the mix's answer length
        self.serve(self._requests(-1, max_new=2))
        b, n = self.traffic["batch"], self.traffic["max_new"]
        gen = jnp.concatenate([jnp.zeros((b, 1), jnp.int32)] * n, axis=1)
        for i in range(b):
            _ = [int(t) for t in gen[i][:n]]
        #: seconds of each part of set-up, which the harness logs
        self.setup_phases = {"weights": t1 - t0,
                             "warm": time.perf_counter() - t1}

    def window(self, seconds: float) -> dict:
        """Closed loop: send ``clients`` requests, wait for all of them,
        send the next, until ``seconds`` have passed; every batch started
        in the window is finished and counted."""
        lat, tokens, r = [], 0, 0
        batch_s = []                  # (wall-clock start, seconds), logged
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            reqs = self._requests(r)
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.batch"):
                res = self.serve(reqs)
            te = time.perf_counter()
            batch_s.append((time.time() - (te - ts), te - ts))
            for q in res.done:
                lat.append(te - ts)
                tokens += len(q.result)
                self.done.append((q.tokens, q.result))
            r += 1
            if te >= deadline:
                break
        dt = time.perf_counter() - t0
        self.rounds = r
        attempted = r * self.traffic["clients"]
        return {"metrics": {"tokens_per_s": tokens / dt,
                            "request_p95_s": float(np.percentile(lat, 95))},
                "attempted": attempted,
                "failed": attempted - len(self.done), "batch_s": batch_s}

    def counts(self) -> dict:
        """What the per-layer readers need: the model flops the window's
        requests needed, and the needed work of each decode call."""
        t = self.traffic
        n_req = len(self.done)
        s0, n_new = t["prompt_len"], t["max_new"]
        elem = jnp.dtype(self.cfg["torch_dtype"]).itemsize
        calls = [counts.decode_step(self.cfg, t["batch"], s0 + i - 1, elem)
                 for i in range(1, n_new)]
        rounds = self.rounds
        return {"model_flops": n_req * counts.request_flops(
                    self.cfg, s0, n_new),
                "decode_calls": rounds * len(calls),
                "decode_needed_flops": rounds * sum(c["flops"]
                                                    for c in calls),
                "decode_needed_bytes": rounds * sum(c["bytes"]
                                                    for c in calls),
                "decode_program": "_decode", "prefill_program": "_prefill"}

    def release(self) -> None:
        """Free the server; the weights stay for the reference."""
        self.engine = self.serve = None

    def _sample(self) -> list:
        rng = np.random.default_rng([self.seed, 3])
        k = min(self.traffic["check_requests"], len(self.done))
        longest = max(range(len(self.done)),
                      key=lambda i: len(self.done[i][0]) + len(
                          self.done[i][1]))
        rest = [i for i in range(len(self.done)) if i != longest]
        pick = [longest] + list(rng.choice(rest, size=k - 1,
                                           replace=False)) if k > 1 \
            else [longest]
        return [self.done[i] for i in sorted(pick)]

    def check(self, control: bool = False) -> list[tuple[str, float]]:
        """Widest gap by which a served token's float32 reference logit
        lies below the reference's best, over a sample of the completed
        requests. ``control`` replaces each served token by the one the
        float8 pass puts first."""
        t = self.traffic
        s0, n_new = t["prompt_len"], t["max_new"]
        w = reference.weights_of(self.params)
        gaps = []
        for block in self._blocks(self._sample()):
            seq = jnp.asarray([p + r[:-1] for p, r in block], jnp.int32)
            want = self.fwd(w, seq, s0 - 1, n_new, "f32")
            if control:
                served = jnp.argmax(self.fwd(w, seq, s0 - 1, n_new, "fp8"),
                                    -1)
            else:
                served = jnp.asarray([r for _, r in block], jnp.int32)
            gaps.append(np.asarray(reference.served_gap(want, served)))
        gaps = np.concatenate([g.ravel() for g in gaps])
        gaps = np.where(np.isfinite(gaps), gaps, np.inf)
        #: what ``bench/control.py`` prints beside the compared number
        self.gap_stats = {"positions": int(gaps.size),
                          "mean_gap": float(gaps.mean()),
                          "flipped": float(np.mean(gaps > 0))}
        return [("logit_gap", float(gaps.max()))]

    def _blocks(self, sample: list):
        step = self.traffic["ref_batch"]
        return [sample[i:i + step] for i in range(0, len(sample), step)]
