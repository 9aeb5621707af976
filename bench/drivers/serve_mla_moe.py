"""Driver for serving one decode rank of an MLA + routed-expert LM
(DeepSeek-V3 family): the ``serve`` driver's closed loop, requests, token
read-back and sampling of checked requests (``bench/drivers/serve.py``,
imported), with this family's configuration, weights, work counts and
reference.

The configuration's ``ep_size`` makes every MoE layer hold rank 0's share
of its routed experts (``repro.models.moe.held_apply``); the server counts
the pairs those experts computed and how many of them were active, per
call kind, and the counts here read them (``bench/counts/mla_moe.py``).
Traffic parameters as for ``serve``.
"""
from __future__ import annotations

import functools
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import mla_moe as counts
from bench.drivers import serve as base
from bench.harness import Refused
from bench.reference import mla_moe as reference

if not (pathlib.Path(__file__).resolve().parents[2] / "src" / "repro"
        / "kernels" / "moe_gmm.py").is_file():
    raise Refused("this program has no held-expert MoE layer "
                  "(src/repro/kernels/moe_gmm.py)")


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a DeepSeek-V3 style Hugging Face
    config, served on one chip: MLA on every layer, the leading dense
    layers, then MoE layers holding rank 0's share of ``ep_size``."""
    from repro.configs import ArchConfig, MLAConfig
    from repro.models.common import YarnConfig
    from repro.models.moe import MoEConfig
    y = cfg.get("rope_scaling")
    if y and y["mscale"] != y["mscale_all_dim"]:
        raise ValueError("YaRN with mscale != mscale_all_dim scales cos "
                         "and sin, which the program does not")
    yarn = YarnConfig(
        factor=float(y["factor"]),
        original_max_position=y["original_max_position_embeddings"],
        beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
        mscale_all_dim=float(y["mscale_all_dim"])) if y else None
    return ArchConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), act="swiglu", norm="rmsnorm",
        moe=MoEConfig(n_experts=cfg["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      n_shared=cfg["n_shared_experts"], router="noaux_tc",
                      n_group=cfg["n_group"], topk_group=cfg["topk_group"],
                      routed_scale=float(cfg["routed_scaling_factor"]),
                      ep_size=cfg["ep_size"]),
        mla=MLAConfig(q_lora=cfg["q_lora_rank"], kv_lora=cfg["kv_lora_rank"],
                      qk_nope_dim=cfg["qk_nope_head_dim"],
                      qk_rope_dim=cfg["qk_rope_head_dim"],
                      v_dim=cfg["v_head_dim"]),
        n_dense_layers=cfg["first_k_dense_replace"],
        mtp=bool(cfg["num_nextn_predict_layers"]), tp=1,
        tie_embed=cfg["tie_word_embeddings"], rope_scaling=yarn,
        act_dtype=cfg["torch_dtype"])


#: norm gains (offsets from 1) and the router's bias start at 0
ZERO = {"ln", "final_ln", "q_ln", "kv_ln", "router_bias"}


def init_scale(name: str, shape: tuple) -> float:
    """Standard deviation of a weight by its name and shape: 1/sqrt(fan-in),
    the fan-in being the contracted dims (the embedding's and head's d)."""
    if name in ZERO:
        return 0.0
    if name in ("embed", "head"):
        fan_in = shape[-1]
    elif name == "wo":                              # (h, v, d)
        fan_in = shape[-3] * shape[-2]
    elif name in ("wq_b", "wk_b", "wv_b"):          # (rank, h, dim)
        fan_in = shape[-3]
    else:                                           # (..., fan_in, out)
        fan_in = shape[-2]
    return fan_in ** -0.5


def make_weights(model, cfg: dict, key):
    """Random weights in the program's parameter tree, made on the device
    in one jitted call, in the served dtype (the router in its own)."""
    from repro.models.common import is_rec
    leaves, tree = jax.tree_util.tree_flatten_with_path(model.param_recs(),
                                                        is_leaf=is_rec)
    dtype = jnp.dtype(cfg["torch_dtype"])
    plan = [(tuple(rec.shape), rec.dtype or dtype,
             init_scale(str(getattr(path[-1], "key", path[-1])), rec.shape))
            for path, rec in leaves]

    @jax.jit
    def make(key):
        return [jnp.zeros(shape, dt) if scale == 0.0 else
                (jax.random.normal(jax.random.fold_in(key, i), shape,
                                   jnp.float32) * scale).astype(dt)
                for i, (shape, dt, scale) in enumerate(plan)]

    return jax.tree_util.tree_unflatten(tree, make(key))


class Driver(base.Driver):
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
        self._serve = functools.partial(serve, self.engine,
                                        batch_size=self.traffic["batch"])
        self.fwd = jax.jit(functools.partial(reference.logits, self.cfg),
                           static_argnums=(2, 3, 4))
        # warm as the serve driver does: the mix's prefill and decode
        # shapes, then the eager join and read-back at its answer length
        self.moe = {"pairs": {}, "active": {}}
        self.serve(self._requests(-1, max_new=2))
        b, n = self.traffic["batch"], self.traffic["max_new"]
        gen = jnp.concatenate([jnp.zeros((b, 1), jnp.int32)] * n, axis=1)
        for i in range(b):
            _ = [int(t) for t in gen[i][:n]]
        self.moe = {"pairs": {}, "active": {}}      # the window's counts
        self.setup_phases = {"weights": t1 - t0,
                             "warm": time.perf_counter() - t1}

    def serve(self, reqs):
        """Serve one batch through ``launch/serve.serve``, adding its
        held-expert counters to the window's."""
        res = self._serve(reqs)
        for name, got in (("pairs", res.counts.moe_pairs),
                          ("active", res.counts.moe_active)):
            for kind, n in got.items():
                self.moe[name][kind] = self.moe[name].get(kind, 0) + n
        return res

    def counts(self) -> dict:
        """What the per-layer readers need: the model flops the window's
        requests needed, the needed work of each decode call and of the
        held experts' grouped matmuls in decode, and the counters."""
        t = self.traffic
        s0, n_new = t["prompt_len"], t["max_new"]
        elem = jnp.dtype(self.cfg["torch_dtype"]).itemsize
        rounds = self.rounds
        calls = [counts.decode_step(self.cfg, t["batch"], s0 + i - 1, elem)
                 for i in range(1, n_new)]
        pairs, active = self.moe["pairs"], self.moe["active"]
        moe = counts.routed(self.cfg, pairs.get("decode", 0),
                            active.get("decode", 0), elem)
        return {"model_flops": (
                    len(self.done) * counts.request_flops(self.cfg, s0, n_new)
                    + counts.pair_flops(self.cfg) * sum(pairs.values())),
                "decode_calls": rounds * len(calls),
                "decode_needed_flops": (rounds * sum(c["flops"]
                                                     for c in calls)
                                        + moe["flops"]),
                "decode_needed_bytes": (rounds * sum(c["bytes"]
                                                     for c in calls)
                                        + moe["bytes"]),
                "moe_gmm_needed_flops": moe["flops"],
                "moe_gmm_needed_bytes": moe["bytes"],
                "moe_pairs": dict(pairs), "moe_active": dict(active),
                "decode_program": "_decode", "prefill_program": "_prefill"}

    def release(self) -> None:
        """Free the server; the weights stay for the reference."""
        self.engine = self._serve = None

    def check(self, control: bool = False) -> list[tuple[str, float]]:
        """Gaps by which each served token's float32 reference logit lies
        below the reference's best, over a sample of the completed
        requests, ``ref_batch`` at a time: the widest (``logit_gap``) and
        the mean over every position (``mean_gap``). The mean is the one
        that tells the float8 control from the program: with bfloat16
        activations a router near-tie that flips a held expert moves a
        few positions' logits far (section 6 of PERF.md), while a fault
        moves every position. ``control`` replaces each served token by
        the one the float8 pass puts first."""
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
        return [("logit_gap", float(gaps.max())),
                ("mean_gap", float(gaps.mean()))]

