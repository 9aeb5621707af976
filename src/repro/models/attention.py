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
from jax.experimental.layout import Layout, with_layout_constraint

from .common import (PRec, constrain, layer_norm, pad_heads, rms_norm, rope,
                     yarn_mscale)

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
# KV caches: every layer's rows live in one stacked (L, b, ..., S) buffer
# ----------------------------------------------------------------------
def write_rows(buf, layer, rows, start):
    """Write ``rows`` (b, s, ...) into layer ``layer`` of the stacked cache
    ``buf`` from row ``start``; returns the buffer and that layer's rows as
    (b, S, ...), the new ones included.

    ``buf`` keeps the sequence axis last, (L, b, ..., S), and is held in
    row-major layout, the one a TPU gives such an array when S is a
    multiple of 128: the layer scan that carries it then writes the ``s``
    rows in place. Left to choose, XLA gives the loop a row-contiguous
    layout of its own and copies the whole buffer into and out of the loop
    on every step."""
    rows = jnp.moveaxis(rows, 1, -1)[None].astype(buf.dtype)
    idx = (layer,) + (0,) * (buf.ndim - 2) + (start,)
    buf = jax.lax.dynamic_update_slice(buf, rows, idx)
    buf = with_layout_constraint(buf, Layout(tuple(range(buf.ndim))))
    mine = jax.lax.dynamic_index_in_dim(buf, layer, keepdims=False)
    return buf, jnp.moveaxis(mine, -1, 1)


# ----------------------------------------------------------------------
# GQA block
# ----------------------------------------------------------------------
def gqa_apply(p, x, cfg, kind: str = "causal", positions=None, cache=None,
              pos=None, layer=None, rule=None, window: int = 0,
              use_rope: bool = True):
    """Returns (delta_x, cache). ``cache``: the stacked ``k``, ``v``
    (L, b, kv, hd, S) of every layer, and for a local-window ring buffer
    this layer's ``pos`` (S,); the block writes this ``layer``'s new rows
    and returns the same entries, written."""
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
        with jax.named_scope("kv_update"):
            W = cache["k"].shape[-1]
            if "pos" in cache:
                # ring buffer (local-window layers): slot = position mod W
                if s >= W:   # prefill longer than the window: keep the tail
                    shift = q_pos[-W] % W       # the tail, in ring order
                    ck, _ = write_rows(cache["k"], layer,
                                       jnp.roll(k[:, -W:], shift, 1), 0)
                    cv, _ = write_rows(cache["v"], layer,
                                       jnp.roll(v[:, -W:], shift, 1), 0)
                    cp = jnp.roll(q_pos[-W:], shift)
                    # attention itself sees the FULL in-call k/v (early queries
                    # need their own chunk, which the ring has already evicted)
                    kv_pos = q_pos
                else:        # decode / short prefill (no intra-call wrap)
                    slot = pos % W
                    ck, k = write_rows(cache["k"], layer, k, slot)
                    cv, v = write_rows(cache["v"], layer, v, slot)
                    cp = jax.lax.dynamic_update_slice(cache["pos"], q_pos,
                                                      (slot,))
                    kv_pos = cp
                cache = {"k": ck, "v": cv, "pos": cp}
            else:
                ck, k = write_rows(cache["k"], layer, k, pos)
                cv, v = write_rows(cache["v"], layer, v, pos)
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
def mla_scale(cfg) -> float:
    """MLA's softmax scale: (nope + rope dims)^-1/2, times YaRN's
    ``mscale_all_dim`` factor squared when the config scales rope."""
    m, y = cfg.mla, cfg.rope_scaling
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


#: MLA prefill scores this many bytes of float32 scores at a time at most
MLA_SCORE_BYTES = 1 << 29


def _mla_expanded(q_nope, q_rope, k_nope, k_rope, vv, mask, scale):
    scores = (jnp.einsum("bqnh,bknh->bnqk", q_nope, k_nope)
              + jnp.einsum("bqnh,bkoh->bnqk", q_rope,
                           jnp.broadcast_to(k_rope, k_rope.shape))) \
        * scale
    pr = _softmax(scores, mask)
    return jnp.einsum("bnqk,bknh->bqnh", pr.astype(vv.dtype), vv)


def _mla_query_chunks(q_nope, q_rope, k_nope, k_rope, vv, mask, scale):
    """The expanded form a chunk of queries at a time, so that no more
    than :data:`MLA_SCORE_BYTES` of scores is live: (b, s, h, v_dim)."""
    b, s, h, _ = q_nope.shape
    skv = k_nope.shape[1]
    cq = s
    while cq > 8 and (b * h * cq * skv * 4 > MLA_SCORE_BYTES or s % cq):
        cq //= 2
    if s % cq or cq == s:
        return _mla_expanded(q_nope, q_rope, k_nope, k_rope, vv, mask,
                             scale)
    n = s // cq

    def chunks(a):
        return a.reshape(a.shape[0], n, cq, *a.shape[2:]).swapaxes(0, 1)

    def body(_, args):
        qn, qr, mk = args
        return None, _mla_expanded(qn, qr, k_nope, k_rope, vv, mk, scale)

    _, o = jax.lax.scan(body, None, (chunks(q_nope), chunks(q_rope),
                                     mask.reshape(n, cq, skv)))
    return o.swapaxes(0, 1).reshape(b, s, h, vv.shape[-1])


def mla_apply(p, x, cfg, positions=None, cache=None, pos=None, layer=None,
              rule=None):
    """Returns (delta_x, cache). ``cache``: the stacked ``latent`` and
    ``k_rope`` of every layer; the block writes this ``layer``'s new rows
    and returns both, written. A prefill (cache, several tokens) scores a
    chunk of queries at a time; a decode step scores the latent rows
    directly (weight absorption)."""
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
    q_rope = rope(q_rope, positions, cfg.rope_theta, yarn=cfg.rope_scaling)
    k_rope = rope(k_rope[..., None, :], positions, cfg.rope_theta,
                  yarn=cfg.rope_scaling)

    kv_len, q_start = None, 0
    if cache is not None:
        with jax.named_scope("kv_update"):
            cl, latent = write_rows(cache["latent"], layer, latent, pos)
            cr, k_rope = write_rows(cache["k_rope"], layer, k_rope, pos)
        cache = {"latent": cl, "k_rope": cr}
        kv_len, q_start = pos + s, pos
        if s > 1 and isinstance(pos, int):   # prefill: the rows so far
            latent, k_rope = latent[:, :kv_len], k_rope[:, :kv_len]

    scale = mla_scale(cfg)
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
        with jax.named_scope("mla_decode"):
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
        if cache is not None and rule is None:
            o = _mla_query_chunks(q_nope, q_rope, k_nope, k_rope, vv, mask,
                                  scale)
        else:
            # NB: q-chunking the sharded path was tried and REFUTED (§Perf
            # r5): with seq-sharded q the per-chunk reshard triggers
            # involuntary full rematerialization in the SPMD partitioner
            # (23.5 TiB of extra all-gathers). The fp32 score-tile traffic
            # is instead addressed by the Pallas flash kernel on real TPUs
            # (kernel-aware §Roofline). Exact prefill/decode logit parity
            # (same-argmax tests) comes from cfg.act_dtype=float32, not
            # from forcing fp32 here — bf16 configs keep bf16 score/value
            # tiles.
            o = _mla_expanded(q_nope, q_rope, k_nope, k_rope, vv, mask,
                              scale)
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
