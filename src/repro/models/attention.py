"""Attention blocks: GQA (full/causal/local-chunked/NoPE), MLA (DeepSeek),
cross-attention, with KV caches for prefill/decode and TP sharding via
logical-axis constraints. Pure-jnp reference path; the Pallas flash kernel
(repro.kernels.flash_attention) mirrors the chunked online-softmax exactly
and is enabled on real TPUs via ``use_pallas``.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from .common import PRec, constrain, layer_norm, pad_heads, rms_norm, rope

NEG_INF = -2.0 ** 30  # large-but-finite: keeps fully-masked rows NaN-free


# ----------------------------------------------------------------------
# Parameter records
# ----------------------------------------------------------------------
def gqa_recs(cfg, bias: bool = False) -> dict[str, PRec]:
    h = pad_heads(cfg.n_heads, cfg.tp)
    kv = pad_heads(cfg.n_kv_heads, cfg.tp)
    d, hd = cfg.d_model, cfg.head_dim
    # fan-in is d: the default (shape[-2]) would read the head count and
    # give q/k entries variance d/h, scores of std ~hd, and a near-one-hot
    # softmax whose choices flip on any rounding difference
    recs = {
        "wq": PRec((d, h, hd), ("embed", "heads", "hd"), scale=d ** -0.5),
        "wk": PRec((d, kv, hd), ("embed", "kv", "hd"), scale=d ** -0.5),
        "wv": PRec((d, kv, hd), ("embed", "kv", "hd"), scale=d ** -0.5),
        "wo": PRec((h, hd, d), ("heads", "hd", "embed"),
                   scale=(h * hd) ** -0.5),
        "ln": PRec((d,), ("embed",), init="zeros"),
    }
    if cfg.norm == "layernorm":
        recs["ln"] = PRec((d,), ("embed",), init="ones")
        recs["ln_b"] = PRec((d,), ("embed",), init="zeros")
    if bias:
        recs["bq"] = PRec((h, hd), ("heads", "hd"), init="zeros")
        recs["bk"] = PRec((kv, hd), ("kv", "hd"), init="zeros")
        recs["bv"] = PRec((kv, hd), ("kv", "hd"), init="zeros")
    return recs


def mla_recs(cfg) -> dict[str, PRec]:
    """DeepSeek-V3 multi-head latent attention: KV compressed to a shared
    latent (kv_lora) + a decoupled RoPE key; Q via its own low-rank path."""
    m = cfg.mla
    d, h = cfg.d_model, pad_heads(cfg.n_heads, cfg.tp)
    nope, rope_d = m.qk_nope_dim, m.qk_rope_dim
    return {
        "wq_a": PRec((d, m.q_lora), ("embed", "latent")),
        "q_ln": PRec((m.q_lora,), ("latent",), init="zeros"),
        "wq_b": PRec((m.q_lora, h, nope + rope_d), ("latent", "heads", "hd"),
                     scale=m.q_lora ** -0.5),
        "wkv_a": PRec((d, m.kv_lora + rope_d), ("embed", "latent")),
        "kv_ln": PRec((m.kv_lora,), ("latent",), init="zeros"),
        "wk_b": PRec((m.kv_lora, h, nope), ("latent", "heads", "hd"),
                     scale=m.kv_lora ** -0.5),
        "wv_b": PRec((m.kv_lora, h, m.v_dim), ("latent", "heads", "hd"),
                     scale=m.kv_lora ** -0.5),
        "wo": PRec((h, m.v_dim, d), ("heads", "hd", "embed"),
                   scale=(h * m.v_dim) ** -0.5),
        "ln": PRec((d,), ("embed",), init="zeros"),
    }


def cross_recs(cfg) -> dict[str, PRec]:
    recs = gqa_recs(cfg)
    return recs


# ----------------------------------------------------------------------
# Core attention math (grouped heads, online-softmax chunking for long S)
# ----------------------------------------------------------------------
def _grouped_scores(q, k):
    """q: (b, sq, h, hd), k: (b, skv, kv, hd) -> (b, kv, g, sq, skv)."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    return jnp.einsum("bqkgh,bskh->bkgqs", qg, k) / math.sqrt(hd)


def _grouped_out(p, v):
    """p: (b, kv, g, sq, skv), v: (b, skv, kv, hd) -> (b, sq, h, hd)."""
    b, kvh, g, sq, skv = p.shape
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v)
    return o.reshape(b, sq, kvh * g, v.shape[-1])


def _softmax(scores, mask):
    scores = jnp.where(mask, scores.astype(jnp.float32), NEG_INF)
    return jax.nn.softmax(scores, axis=-1)


def _causal_mask(sq: int, skv: int, q_start) -> jnp.ndarray:
    """(sq, skv) lower-triangular mask with the query block starting at
    absolute position ``q_start`` into the kv sequence."""
    qp = jnp.arange(sq)[:, None] + q_start
    kp = jnp.arange(skv)[None, :]
    return qp >= kp


def _mask(kind: str, q_pos, kv_pos, window: int, kv_len=None):
    """q_pos: (sq,), kv_pos: (skv,) absolute positions; kv_pos = -1 marks
    empty ring-buffer slots. kinds: causal | local | bidir."""
    qp, kp = q_pos[:, None], kv_pos[None, :]
    if kind == "bidir":
        m = jnp.ones_like(qp >= kp)
    else:
        m = qp >= kp
    if kind == "local" and window:
        m = m & ((qp // window) == (kp // window))
    m = m & (kp >= 0)
    if kv_len is not None:
        m = m & (kp < kv_len)
    return m


def attend(q, k, v, kind: str, q_pos=None, kv_pos=None, window: int = 0,
           kv_len=None, chunk_q: int = 512, rule=None):
    """Dense or q-chunked attention with positional masking."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    if q_pos is None:
        q_pos = jnp.arange(sq)
    if kv_pos is None:
        kv_pos = jnp.arange(skv)

    def blockless(qq, qp):
        scores = _grouped_scores(qq, k)
        p = _softmax(scores, _mask(kind, qp, kv_pos, window, kv_len))
        return _grouped_out(p.astype(v.dtype), v)

    if sq <= max(chunk_q, 1024) or sq % chunk_q != 0:
        return blockless(q, q_pos)

    # q-chunked streaming (keeps the score tile VMEM/HBM footprint bounded;
    # block sizes on real TPUs come from core.blocking.attention_tiles).
    # The chunk body is rematerialized: without it the scan stores every
    # chunk's fp32 probability tile for backward — a (nchunks, b, h, cq,
    # skv) stack that dominated the train-cell memory term (§Perf).
    nchunks = sq // chunk_q
    qc = q.reshape(b, nchunks, chunk_q, h, hd).swapaxes(0, 1)
    qpc = q_pos.reshape(nchunks, chunk_q)

    @jax.checkpoint
    def body(carry, args):
        qq, qp = args
        return carry, blockless(qq, qp)

    _, outs = jax.lax.scan(body, (), (qc, qpc))
    return outs.swapaxes(0, 1).reshape(b, sq, h, hd)


# ----------------------------------------------------------------------
# GQA block
# ----------------------------------------------------------------------
def gqa_apply(p, x, cfg, kind: str = "causal", positions=None, cache=None,
              pos=None, rule=None, window: int = 0, use_rope: bool = True):
    """Returns (delta_x, new_cache). cache: dict(k, v, len) or None."""
    b, s, d = x.shape
    xn = (rms_norm(x, p["ln"]) if cfg.norm == "rmsnorm"
          else layer_norm(x, p["ln"], p["ln_b"]))
    q = jnp.einsum("bsd,dnh->bsnh", xn, p["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", xn, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", xn, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if positions is None:
        positions = jnp.arange(s)[None, :] + (0 if pos is None else pos)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    if rule is not None:
        q = constrain(q, rule, ("batch", None, "act_heads", None))
        k = constrain(k, rule, ("batch", None, "act_kv", None))
        v = constrain(v, rule, ("batch", None, "act_kv", None))

    kv_len = None
    kv_pos = None
    q_pos = positions[0] if positions.ndim == 2 else positions
    if cache is not None:
        ck, cv = cache["k"], cache["v"]
        W = ck.shape[1]
        if "pos" in cache:
            # ring buffer (local-window layers): slot = position mod W
            cp = cache["pos"]
            if s >= W:       # prefill longer than the window: keep the tail
                ck = k[:, -W:].astype(ck.dtype)
                cv = v[:, -W:].astype(cv.dtype)
                cp = q_pos[-W:]
                cache = {"k": ck, "v": cv, "pos": cp}
                # attention itself sees the FULL in-call k/v (early queries
                # need their own chunk, which the ring has already evicted)
                kv_pos = q_pos
            else:            # decode / short prefill (no intra-call wrap)
                slot = (pos if s == 1 else pos) % W
                ck = jax.lax.dynamic_update_slice(
                    ck, k.astype(ck.dtype), (0, slot, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cv, v.astype(cv.dtype), (0, slot, 0, 0))
                cp = jax.lax.dynamic_update_slice(cp, q_pos, (slot,))
                cache = {"k": ck, "v": cv, "pos": cp}
                k, v, kv_pos = ck, cv, cp
        else:
            ck = jax.lax.dynamic_update_slice(
                ck, k.astype(ck.dtype), (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cv, v.astype(cv.dtype), (0, pos, 0, 0))
            k, v = ck, cv
            kv_len = pos + s
            cache = {"k": ck, "v": cv}
    o = attend(q, k, v, kind, q_pos=q_pos, kv_pos=kv_pos, window=window,
               kv_len=kv_len, rule=rule)
    out = jnp.einsum("bsnh,nhd->bsd", o, p["wo"])
    if rule is not None:
        out = constrain(out, rule, ("batch", "seq", "act_embed"))
    return out, cache


# ----------------------------------------------------------------------
# MLA block (DeepSeek-V3). Cache stores the compressed latent + rope key:
# the paper's KV-cache reduction; K/V are re-expanded from the latent.
# ----------------------------------------------------------------------
def mla_apply(p, x, cfg, positions=None, cache=None, pos=None, rule=None):
    m = cfg.mla
    b, s, d = x.shape
    xn = rms_norm(x, p["ln"])
    # queries
    ql = rms_norm(jnp.einsum("bsd,dr->bsr", xn, p["wq_a"]), p["q_ln"])
    q = jnp.einsum("bsr,rnh->bsnh", ql, p["wq_b"])
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    # compressed kv latent + decoupled rope key
    kv_a = jnp.einsum("bsd,dr->bsr", xn, p["wkv_a"])
    latent, k_rope = kv_a[..., :m.kv_lora], kv_a[..., m.kv_lora:]
    latent = rms_norm(latent, p["kv_ln"])
    if positions is None:
        positions = jnp.arange(s)[None, :] + (0 if pos is None else pos)
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    k_rope = rope(k_rope[..., None, :], positions, cfg.rope_theta)

    kv_len, q_start = None, 0
    if cache is not None:
        cl = jax.lax.dynamic_update_slice(
            cache["latent"], latent.astype(cache["latent"].dtype), (0, pos, 0))
        cr = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
            (0, pos, 0, 0))
        latent, k_rope = cl, cr
        cache = {"latent": cl, "k_rope": cr}
        kv_len, q_start = pos + s, pos

    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    skv = latent.shape[1]
    mask = _causal_mask(s, skv, q_start)
    if kv_len is not None:
        mask = mask & (jnp.arange(skv)[None, :] < kv_len)

    if cache is not None and s == 1:
        # DECODE: weight absorption (DeepSeek-V3 inference form). Folding
        # wk_b into q and wv_b into the output scores the small q block
        # directly against the (b, skv, r) latent — O(h·r·(hd + skv)) per
        # step instead of re-expanding K/V for every cached position
        # (§Perf: 260x less decode MXU work at skv=32k).
        # fp32 through the (tiny) absorbed q/o tensors: the extra rounding
        # of the two-hop latent contraction otherwise drifts logits
        q_lat = jnp.einsum("bqnh,rnh->bqnr", q_nope, p["wk_b"],
                           preferred_element_type=jnp.float32)
        scores = (jnp.einsum("bqnr,bkr->bnqk", q_lat,
                             latent.astype(jnp.float32))
                  + jnp.einsum("bqnh,bkoh->bnqk", q_rope,
                               jnp.broadcast_to(k_rope, k_rope.shape))) \
            * scale
        pr = _softmax(scores, mask)
        o_lat = jnp.einsum("bnqk,bkr->bqnr", pr,
                           latent.astype(jnp.float32))
        o = jnp.einsum("bqnr,rnh->bqnh", o_lat,
                       p["wv_b"].astype(jnp.float32)).astype(x.dtype)
    else:
        # TRAIN/PREFILL: expand keys/values from the latent (per-head)
        k_nope = jnp.einsum("bsr,rnh->bsnh", latent, p["wk_b"])
        vv = jnp.einsum("bsr,rnh->bsnh", latent, p["wv_b"])
        if rule is not None:
            q_nope = constrain(q_nope, rule,
                               ("batch", None, "act_heads", None))
            k_nope = constrain(k_nope, rule,
                               ("batch", None, "act_heads", None))
            vv = constrain(vv, rule, ("batch", None, "act_heads", None))
        # NB: q-chunking this path was tried and REFUTED (§Perf r5): with
        # seq-sharded q the per-chunk reshard triggers involuntary full
        # rematerialization in the SPMD partitioner (23.5 TiB of extra
        # all-gathers). The fp32 score-tile traffic is instead addressed by
        # the Pallas flash kernel on real TPUs (kernel-aware §Roofline).
        # Exact prefill/decode logit parity (same-argmax tests) comes from
        # cfg.act_dtype=float32, not from forcing fp32 here — bf16 configs
        # keep bf16 score/value tiles.
        scores = (jnp.einsum("bqnh,bknh->bnqk", q_nope, k_nope)
                  + jnp.einsum("bqnh,bkoh->bnqk", q_rope,
                               jnp.broadcast_to(k_rope, k_rope.shape))) \
            * scale
        pr = _softmax(scores, mask)
        o = jnp.einsum("bnqk,bknh->bqnh", pr.astype(vv.dtype), vv)
    out = jnp.einsum("bsnh,nhd->bsd", o, p["wo"])
    if rule is not None:
        out = constrain(out, rule, ("batch", "seq", "act_embed"))
    return out, cache


# ----------------------------------------------------------------------
# Cross attention (whisper decoder). Encoder K/V cached once at prefill.
# ----------------------------------------------------------------------
def cross_apply(p, x, enc_kv, cfg, rule=None):
    xn = (rms_norm(x, p["ln"]) if cfg.norm == "rmsnorm"
          else layer_norm(x, p["ln"], p["ln_b"]))
    q = jnp.einsum("bsd,dnh->bsnh", xn, p["wq"])
    k, v = enc_kv
    o = attend(q, k, v, "bidir", rule=rule)
    return jnp.einsum("bsnh,nhd->bsd", o, p["wo"])


def encode_kv(p, enc_out):
    k = jnp.einsum("bsd,dnh->bsnh", enc_out, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", enc_out, p["wv"])
    return k, v
