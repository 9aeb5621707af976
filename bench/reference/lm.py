"""Plain float32 forward pass of a dense decoder-only LM: RMSNorm, rotary
GQA attention, SiLU-gated MLP, tied embedding head (the Phi-3 family's
equations, arXiv:2404.14219), in ``jax.numpy`` at ``HIGHEST`` matmul
precision, with no cache, no batching tricks and no kernels.

It reads the weights the benchmark made, in the layout the program
serves them in (``weights_of``), upcast layer by layer inside one scan,
so that only one layer is ever held in float32. Norm gains are stored as
offsets from 1, as the program stores them.

``mode="fp8"`` is the control: the same pass with both operands of every
matrix product rounded to float8 e4m3 (each tensor scaled so that its
largest magnitude maps to 448), the precision below the served bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def weights_of(params) -> dict:
    """The tensors of the served layout: embedding, final norm and the
    layer stacks (leading axis = layer) of attention and MLP."""
    attn, mlp = params["stages"][0]["blocks"]
    return {"embed": params["embed"], "final_ln": params["final_ln"],
            "attn": attn, "mlp": mlp}


def _f8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, mode):
    if mode == "fp8":
        a, b = _f8(a), _f8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain)


def _rope(x, theta):
    """Rotate the two halves of the head dim by position (x: b, s, h, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(cfg: dict, w: dict, tokens, start: int, count: int,
           mode: str = "f32"):
    """Float32 logits over the real vocabulary at positions ``start ..
    start + count - 1`` of ``tokens`` (b, s) int32: (b, count, vocab)."""
    eps = cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    vocab = cfg["vocab_size"]
    b, s = tokens.shape
    x = w["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        a, m = jax.tree.map(lambda t: t.astype(jnp.float32), lw)
        y = _rms(x, a["ln"], eps)
        q = _rope(_mm("bsd,dnh->bsnh", y, a["wq"], mode), theta)
        k = _rope(_mm("bsd,dnh->bsnh", y, a["wk"], mode), theta)
        v = _mm("bsd,dnh->bsnh", y, a["wv"], mode)
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
        sc = _mm("bqnh,bknh->bnqk", q, k, mode) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = _mm("bnqk,bknh->bqnh", p, v, mode)
        x = x + _mm("bqnh,nhd->bqd", o, a["wo"], mode)
        y = _rms(x, m["ln"], eps)
        g = _mm("bsd,df->bsf", y, m["w_gate"], mode)
        u = _mm("bsd,df->bsf", y, m["w_up"], mode)
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_out"], mode)
        return x, None

    x, _ = jax.lax.scan(layer, x, (w["attn"], w["mlp"]))
    x = _rms(x[:, start:start + count],
             w["final_ln"].astype(jnp.float32), eps)
    head = w["embed"][:vocab].astype(jnp.float32)
    return _mm("bsd,vd->bsv", x, head, mode)



def served_gap(ref_logits, served):
    """Per position, how far the served token's reference logit lies
    below the reference's best: (b, count) float32, 0 where they agree."""
    got = jnp.take_along_axis(ref_logits, served[..., None], axis=-1)[..., 0]
    return jnp.max(ref_logits, axis=-1) - got
