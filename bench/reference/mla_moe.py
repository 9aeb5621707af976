"""Plain float32 forward pass of one decode rank of DeepSeek-V3
(arXiv:2412.19437): RMSNorm, multi-head latent attention in its published
expanded form with YaRN rope, a dense SiLU-gated FFN on the leading
layers, and on the others the group-limited sigmoid router over all routed
experts, the held experts' share of the routed sum and the shared expert;
an untied head. In ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``, with no cache, no kernel and no weight absorption.

It reads the weights the benchmark made, in the layout the program
serves them in (``weights_of``), upcast a layer at a time inside a scan,
so that only one layer is ever held in float32. It is computed for the
requests it is given at once (``ref_batch``), one request in the cell.

Departures from the published model, each the program's too:

- only the routed experts this rank holds (``ep_size``, rank 0) add to
  the routed sum; what the others would add is left out, and that partial
  result is what goes on to the next layer;
- rope rotates the two halves of the rope dims, where the published code
  interleaves pairs: a fixed permutation of random weights' columns;
- norm gains are stored as offsets from 1, and the shared expert has a
  norm gain of its own, equal to the routed experts' (both 1 here);
- the vocabulary is the configuration's slice, and the MTP module is not
  run.

``mode="fp8"`` is the control: the same pass with both operands of every
matrix product, the router's included, rounded to float8 e4m3 (each
tensor scaled so that its largest magnitude maps to 448), the precision
below the served bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.lm import _mm, _rms, served_gap  # noqa: F401


def weights_of(params) -> dict:
    """The served tensors: embedding, head, final norm, and per stage the
    layer stacks (leading axis = layer) of attention and FFN."""
    return {"embed": params["embed"],
            "head": params.get("head", params["embed"]),
            "final_ln": params["final_ln"],
            "stages": [tuple(st["blocks"]) for st in params["stages"]]}


def yarn(cfg: dict):
    """(inv_freq (rope / 2,), softmax scale) from the config's
    ``rope_scaling`` (YaRN), or plain rope without one."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * i / dim)
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    y = cfg.get("rope_scaling")
    if not y:
        return extra, scale

    def at(rot):                      # the dim where `rot` turns fit
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(at(y["beta_fast"])), 0)
    high = min(math.ceil(at(y["beta_slow"])), dim - 1)
    keep = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = extra * keep + extra / y["factor"] * (1.0 - keep)

    def mscale(m):
        return 0.1 * m * math.log(y["factor"]) + 1.0

    assert y["mscale"] == y["mscale_all_dim"], "cos/sin would be scaled"
    return inv_freq, scale * mscale(y["mscale_all_dim"]) ** 2


def _rope(x, inv_freq):
    """Rotate the two halves of the last dim by position (x: b, s, h, hd)."""
    s, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(y, w_gate, w_up, w_out, mode):
    g = _mm("bsd,df->bsf", y, w_gate, mode)
    u = _mm("bsd,df->bsf", y, w_up, mode)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, w_out, mode)


def route(cfg: dict, y, router, bias, mode: str = "f32"):
    """DeepSeek-V3's ``noaux_tc`` router over all routed experts: (b, s, E)
    combine weights, nonzero at each token's top_k experts."""
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    g, kg = cfg["n_group"], cfg["topk_group"]
    s = jax.nn.sigmoid(_mm("bsd,de->bse", y, router, mode))
    sel = s + bias
    groups = jnp.sort(sel.reshape(*sel.shape[:-1], g, e // g), -1)
    g_score = groups[..., -2:].sum(-1)                          # (b, s, g)
    g_rank = jnp.argsort(jnp.argsort(-g_score, -1), -1)
    keep = jnp.repeat(g_rank < kg, e // g, axis=-1)
    sel = jnp.where(keep, sel, -jnp.inf)
    rank = jnp.argsort(jnp.argsort(-sel, -1), -1)
    w = jnp.where(rank < k, s, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / w.sum(-1, keepdims=True)
    return w * cfg.get("routed_scaling_factor", 1.0)


def moe_ffn(cfg: dict, f: dict, x, mode: str = "f32"):
    """A MoE layer's FFN output for the residual ``x`` (b, s, d), float32
    weights ``f``: the shared expert, and the routed sum over the experts
    held here, ``f``'s first ``n_routed_experts / ep_size``."""
    eps = cfg["rms_norm_eps"]
    y = _rms(x, f["ln"], eps)
    cw = route(cfg, y, f["router"], f["router_bias"], mode)
    sh = f["shared"]
    out = _swiglu(_rms(x, sh["ln"], eps), sh["w_gate"], sh["w_up"],
                  sh["w_out"], mode)
    for e in range(cfg["n_routed_experts"] // max(cfg.get("ep_size", 1), 1)):
        out = out + cw[..., e:e + 1] * _swiglu(
            y, f["w_gate"][e], f["w_up"][e], f["w_out"][e], mode)
    return out


def _attention(cfg: dict, a, x, inv_freq, scale, causal, mode):
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope = cfg["qk_nope_head_dim"]
    eps = cfg["rms_norm_eps"]
    y = _rms(x, a["ln"], eps)
    q = _mm("bsr,rnh->bsnh", _rms(_mm("bsd,dr->bsr", y, a["wq_a"], mode),
                                  a["q_ln"], eps), a["wq_b"], mode)
    kv = _mm("bsd,dr->bsr", y, a["wkv_a"], mode)
    lat = _rms(kv[..., :r], a["kv_ln"], eps)
    q_rope = _rope(q[..., nope:], inv_freq)
    k_rope = _rope(kv[..., None, r:], inv_freq)
    k_nope = _mm("bsr,rnh->bsnh", lat, a["wk_b"], mode)
    v = _mm("bsr,rnh->bsnh", lat, a["wv_b"], mode)
    k_rope = jnp.broadcast_to(k_rope, (*k_rope.shape[:2], h,
                                       k_rope.shape[-1]))
    sc = (_mm("bqnh,bknh->bnqk", q[..., :nope], k_nope, mode)
          + _mm("bqnh,bknh->bnqk", q_rope, k_rope, mode)) * scale
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = _mm("bnqk,bknh->bqnh", p, v, mode)
    return x + _mm("bqnh,nhd->bqd", o, a["wo"], mode)


def logits(cfg: dict, w: dict, tokens, start: int, count: int,
           mode: str = "f32"):
    """Float32 logits over the real vocabulary at positions ``start ..
    start + count - 1`` of ``tokens`` (b, s) int32: (b, count, vocab)."""
    with jax.default_matmul_precision("highest"):
        return _logits(cfg, w, tokens, start, count, mode)


def _logits(cfg, w, tokens, start, count, mode):
    eps = cfg["rms_norm_eps"]
    vocab = cfg["vocab_size"]
    inv_freq, scale = yarn(cfg)
    s = tokens.shape[1]
    x = w["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        a, f = jax.tree.map(lambda t: t.astype(jnp.float32), lw)
        x = _attention(cfg, a, x, inv_freq, scale, causal, mode)
        if "router" not in f:                                 # dense FFN
            y = _rms(x, f["ln"], eps)
            return x + _swiglu(y, f["w_gate"], f["w_up"], f["w_out"],
                               mode), None
        return x + moe_ffn(cfg, f, x, mode), None

    for stack in w["stages"]:
        x, _ = jax.lax.scan(layer, x, stack)
    x = _rms(x[:, start:start + count],
             w["final_ln"].astype(jnp.float32), eps)
    head = w["head"][:vocab].astype(jnp.float32)
    return _mm("bsd,vd->bsv", x, head, mode)
