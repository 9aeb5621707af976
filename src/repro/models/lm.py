"""Generic LM assembly covering all ten assigned architectures.

A model is a sequence of *stages*; each stage is a repeating *group* of
blocks scanned with ``lax.scan`` (stacked parameters, low compile time, one
HLO while-loop whose trip count the HLO analyzer multiplies back in — the
same loop-aware accounting Kerncraft does for C loops). Heterogeneous
patterns (llama4's 3-local+1-global iRoPE, DeepSeek's dense-then-MoE,
Zamba2's shared attention block) are expressed as group structure.

Block kinds: attn (causal|local|nope|bidir), mla, mlp, moe, mamba,
shared_attn (weight-tied across applications, per-application KV cache),
cross (encoder-decoder).

Serving caches mirror the stages. The sequence caches (K/V, MLA latent and
rope key) are stacked over a stage's layers and ride in the scan's carry:
each layer writes only its new rows at its own layer index, so a donated
cache is updated in place. Small per-layer state (ring positions, SSM
state, encoder K/V) goes through the scan's xs/ys and is replaced whole.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from . import attention, mamba2, mlp, moe
from .common import (PRec, constrain, layer_norm, pad_heads, rms_norm, tmap)


# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Block:
    kind: str
    opts: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Stage:
    blocks: tuple[Block, ...]
    repeat: int


def build_stages(cfg) -> list[Stage]:
    if cfg.family == "ssm":
        return [Stage((Block("mamba"),), cfg.n_layers)]
    if cfg.family == "hybrid":
        # Zamba2: all n_layers are Mamba2 blocks; one weight-tied attn+MLP
        # block is applied after every `hybrid_attn_every` of them.
        per = cfg.hybrid_attn_every
        groups = cfg.n_layers // per
        blocks = tuple([Block("mamba") for _ in range(per)]
                       + [Block("shared_attn")])
        stages = [Stage(blocks, groups)]
        tail = cfg.n_layers - groups * per
        if tail:
            stages.append(Stage((Block("mamba"),), tail))
        return stages
    if cfg.encdec:
        return [Stage((Block("attn", {"kind": "causal"}), Block("cross"),
                       Block("mlp")), cfg.n_layers)]
    def ffn(i: int) -> Block:
        """FFN for layer index i within the repeating group: MoE layers are
        interleaved every ``moe_every`` (llama4: dense/MoE alternation)."""
        if cfg.moe and (cfg.moe_every <= 1 or i % cfg.moe_every == cfg.moe_every - 1):
            return Block("moe")
        return Block("mlp")

    if cfg.local_window:  # llama4 iRoPE: (period-1) local-RoPE + 1 global-NoPE
        per = cfg.local_period
        blocks = []
        for i in range(per - 1):
            blocks += [Block("attn", {"kind": "local"}), ffn(i)]
        blocks += [Block("attn", {"kind": "nope"}), ffn(per - 1)]
        assert cfg.n_layers % per == 0
        return [Stage(tuple(blocks), cfg.n_layers // per)]
    stages = []
    if cfg.n_dense_layers:  # deepseek: first k layers use a dense FFN
        stages.append(Stage((Block("mla" if cfg.mla else "attn"),
                             Block("mlp")), cfg.n_dense_layers))
    if cfg.moe and cfg.moe_every > 1 and not cfg.local_window:
        blocks = []
        for i in range(cfg.moe_every):
            blocks += [Block("mla" if cfg.mla else "attn"), ffn(i)]
        assert cfg.n_layers % cfg.moe_every == 0
        stages.append(Stage(tuple(blocks), cfg.n_layers // cfg.moe_every))
        return stages
    stages.append(Stage((Block("mla" if cfg.mla else "attn"), ffn(0) if not cfg.moe
                         else Block("moe")), cfg.n_layers - cfg.n_dense_layers))
    return stages


# ----------------------------------------------------------------------
# Sequence caches: one row per position, per layer
# ----------------------------------------------------------------------
# Logical axes of one layer's sequence cache (one row per position), by
# entry name, sequence axis last (attention.write_rows); cache_recs stacks
# them over the layers. Every other cache entry is small per-layer state.
_SEQ_AXES = {"k": ("batch", "act_kv", None, "kv_seq"),
             "v": ("batch", "act_kv", None, "kv_seq"),
             "latent": ("batch", None, "kv_seq"),
             "k_rope": ("batch", None, None, "kv_seq")}


def _seq_axes(name: str, ring: bool = False) -> tuple:
    """Axes of sequence cache ``name``; a ring buffer (local-window layer)
    keeps kv_seq local to the window."""
    axes = _SEQ_AXES[name]
    return tuple(None if ring and a == "kv_seq" else a for a in axes)


#: the named scope of a block kind in the trace, where it is not the kind
_SCOPES = {"mla": "attn"}


def _split_cache(cache: dict) -> tuple[dict, dict]:
    """(sequence entries, small per-layer state) of one block's cache."""
    return ({k: v for k, v in cache.items() if k in _SEQ_AXES},
            {k: v for k, v in cache.items() if k not in _SEQ_AXES})


# ----------------------------------------------------------------------
# Parameter records
# ----------------------------------------------------------------------
def _block_recs(blk: Block, cfg) -> dict:
    if blk.kind in ("attn", "shared_attn", "cross"):
        return attention.gqa_recs(cfg, bias=cfg.qkv_bias)
    if blk.kind == "mla":
        return attention.mla_recs(cfg)
    if blk.kind == "mlp":
        return mlp.mlp_recs(cfg)
    if blk.kind == "moe":
        return moe.moe_recs(cfg)
    if blk.kind == "mamba":
        return mamba2.mamba2_recs(cfg)
    raise ValueError(blk.kind)


def _stack(recs, n: int):
    return tmap(lambda r: PRec((n,) + r.shape, ("layers",) + r.axes,
                               scale=r.scale, dtype=r.dtype, init=r.init), recs)


class LM:
    def __init__(self, cfg):
        self.cfg = cfg
        self.stages = build_stages(cfg)
        # Megatron-style vocab padding: lane-aligned (128) so the vocab dim
        # shards evenly over any TP degree; padded logits are masked in _head.
        self.padded_vocab = -(-cfg.vocab // 128) * 128
        #: caches -> the held experts' counts, summed (moe.held_counts);
        #: None when no layer holds a share of the experts
        self.held_counts = moe.held_counts(cfg)

    # -- parameters ------------------------------------------------------
    def param_recs(self):
        cfg = self.cfg
        d = cfg.d_model
        recs: dict[str, Any] = {
            # in (and, when tied, out) embedding: d^-1/2 init keeps head
            # logits O(1) (rmsnorm renormalizes the input side)
            "embed": PRec((self.padded_vocab, d), ("vocab", "embed"),
                          scale=d ** -0.5),
            "final_ln": PRec((d,), ("embed",),
                             init="zeros" if cfg.norm == "rmsnorm" else "ones"),
        }
        if cfg.norm == "layernorm":
            recs["final_ln_b"] = PRec((d,), ("embed",), init="zeros")
        if not cfg.tie_embed:       # an output head of its own
            recs["head"] = PRec((self.padded_vocab, d), ("vocab", "embed"),
                                scale=d ** -0.5)
        stage_recs = []
        for st in self.stages:
            blocks = []
            for blk in st.blocks:
                if blk.kind == "shared_attn":
                    blocks.append({})      # weights live in recs['shared']
                else:
                    blocks.append(_block_recs(blk, cfg))
            stage_recs.append(_stack({"blocks": blocks}, st.repeat))
        recs["stages"] = stage_recs
        if any(b.kind == "shared_attn" for st in self.stages for b in st.blocks):
            shared = attention.gqa_recs(cfg)
            # Zamba2: the shared block sees concat(hidden, embedding) and is
            # a full transformer block (attn + MLP), weight-tied across uses.
            shared["w_concat"] = PRec((2 * d, d), ("embed", None),
                                      scale=(2 * d) ** -0.5)
            shared["mlp"] = mlp.mlp_recs(cfg)
            recs["shared"] = shared
        if cfg.encdec:
            enc_block = {"attn": attention.gqa_recs(cfg),
                         "mlp": mlp.mlp_recs(cfg)}
            recs["encoder"] = {
                "blocks": _stack(enc_block, cfg.n_enc_layers),
                "ln": PRec((d,), ("embed",), init="ones"),
                "ln_b": PRec((d,), ("embed",), init="zeros"),
            }
        if cfg.mtp:  # DeepSeek multi-token-prediction head: 1 extra block
            recs["mtp"] = {
                "proj": PRec((2 * d, d), ("embed", None), scale=(2 * d) ** -0.5),
                "ln_h": PRec((d,), ("embed",), init="zeros"),
                "ln_e": PRec((d,), ("embed",), init="zeros"),
                "attn": attention.mla_recs(cfg) if cfg.mla
                else attention.gqa_recs(cfg),
                "mlp": mlp.mlp_recs(cfg),
            }
        return recs

    # -- caches -----------------------------------------------------------
    def cache_recs(self, batch: int, max_len: int):
        """Zero-init cache records mirroring the stage structure."""
        cfg = self.cfg
        kvh = pad_heads(cfg.n_kv_heads, cfg.tp)
        hd = cfg.head_dim

        def blk_cache(blk: Block):
            if blk.kind in ("attn", "shared_attn"):
                local = (blk.opts.get("kind") == "local"
                         and cfg.local_window < max_len)
                s = cfg.local_window if local else max_len
                out = {}
                if local:
                    out["pos"] = PRec((s,), (None,), dtype=jnp.int32,
                                      init="fill", scale=-1)
                for name in ("k", "v"):
                    out[name] = PRec((batch, kvh, hd, s),
                                     _seq_axes(name, ring=local),
                                     init="zeros")
                return out
            if blk.kind == "mla":
                m = cfg.mla
                return {"latent": PRec((batch, m.kv_lora, max_len),
                                       _seq_axes("latent"), init="zeros"),
                        "k_rope": PRec((batch, 1, m.qk_rope_dim, max_len),
                                       _seq_axes("k_rope"), init="zeros")}
            if blk.kind == "moe":
                return moe.moe_cache_recs(cfg)
            if blk.kind == "mamba":
                shapes = mamba2.mamba2_cache_shape(cfg, batch)
                return {"ssm": PRec(shapes["ssm"][0],
                                    ("batch", "act_heads", None, None),
                                    dtype=shapes["ssm"][1], init="zeros"),
                        "conv": PRec(shapes["conv"][0],
                                     ("batch", None, "act_inner"),
                                     dtype=shapes["conv"][1], init="zeros")}
            if blk.kind == "cross":
                return {"ck": PRec((batch, cfg.enc_len, kvh, hd),
                                   ("batch", None, "act_kv", None), init="zeros"),
                        "cv": PRec((batch, cfg.enc_len, kvh, hd),
                                   ("batch", None, "act_kv", None), init="zeros")}
            return {}

        out = []
        for st in self.stages:
            out.append(_stack({"blocks": [blk_cache(b) for b in st.blocks]},
                              st.repeat))
        return out

    # -- forward ----------------------------------------------------------
    def _apply_block(self, blk: Block, p, x, rule, cache=None, pos=None,
                     layer=None, shared=None, enc_out=None, x_emb=None):
        cfg = self.cfg
        if blk.kind == "attn":
            kind = blk.opts.get("kind", "causal")
            window = cfg.local_window if kind == "local" else 0
            use_rope = kind != "nope"
            dx, c = attention.gqa_apply(
                p, x, cfg, kind="local" if kind == "local" else
                ("causal" if kind != "bidir" else "bidir"),
                cache=cache, pos=pos, layer=layer, rule=rule, window=window,
                use_rope=use_rope)
            return x + dx, c
        if blk.kind == "shared_attn":
            xin = jnp.einsum("bse,ed->bsd",
                             jnp.concatenate([x, x_emb], -1), shared["w_concat"])
            dx, c = attention.gqa_apply(shared, xin, cfg, kind="causal",
                                        cache=cache, pos=pos, layer=layer,
                                        rule=rule)
            x = x + dx
            return x + mlp.mlp_apply(shared["mlp"], x, cfg, rule=rule), c
        if blk.kind == "mla":
            dx, c = attention.mla_apply(p, x, cfg, cache=cache, pos=pos,
                                        layer=layer, rule=rule)
            return x + dx, c
        if blk.kind == "mlp":
            return x + mlp.mlp_apply(p, x, cfg, rule=rule), cache
        if blk.kind == "moe":
            dx, c = moe.block_apply(p, x, cfg, rule=rule, cache=cache,
                                    layer=layer)
            return x + dx, c
        if blk.kind == "mamba":
            dx, c = mamba2.mamba2_apply(p, x, cfg, rule=rule, cache=cache,
                                        pos=pos)
            return x + dx, c
        if blk.kind == "cross":
            if enc_out is not None:     # training fwd / prefill: encode now
                enc_kv = attention.encode_kv(p, enc_out)
                if cache is not None:   # prefill: persist for decode steps
                    cache = {"ck": enc_kv[0].astype(cache["ck"].dtype),
                             "cv": enc_kv[1].astype(cache["cv"].dtype)}
            else:                       # decode: reuse cached encoder K/V
                enc_kv = (cache["ck"], cache["cv"])
            dx = attention.cross_apply(p, x, enc_kv, cfg, rule=rule)
            return x + dx, cache
        raise ValueError(blk.kind)

    def _run_stages(self, params, x, rule, caches=None, pos=None,
                    enc_out=None, x_emb=None, remat=False):
        """Scan each stage's layers. With ``caches``, the sequence caches
        ride in the scan's carry as stacked ``(L, b, ..., S)`` buffers:
        layer ``i`` writes its new rows at index ``i``, row ``pos``, and
        reads its rows back from the same buffer, so a donated cache is
        updated in place. The small per-layer state goes through the
        scan's ``xs``/``ys``, replaced whole."""
        new_caches = []
        for si, st in enumerate(self.stages):
            pstack = params["stages"][si]["blocks"]
            # weights a block keeps stacked, outside the scan's xs
            stacks = [{k: p[k] for k in moe.scan_stacked(self.cfg)}
                      if blk.kind == "moe" else {}
                      for blk, p in zip(st.blocks, pstack)]
            pstack = [{k: v for k, v in p.items() if k not in w}
                      for p, w in zip(pstack, stacks)]
            if caches is None:
                seq, small = [{} for _ in st.blocks], None
            else:
                seq, small = map(list, zip(*map(_split_cache,
                                                caches[si]["blocks"])))
            axes = [{k: ("layers",) + _seq_axes(k, ring="pos" in sm)
                     for k in sq}
                    for sq, sm in zip(seq, small or [{}] * len(seq))]

            def pin(seq, _axes=axes):
                """Keep each carried buffer on its cache's sharding."""
                if rule is None:
                    return seq
                return [{k: constrain(v, rule, ax[k]) for k, v in c.items()}
                        for c, ax in zip(seq, _axes)]

            def body(carry, layer_in, _st=st, _stacks=stacks):
                xc, seq = carry
                lp, lsmall, li = layer_in
                seq, new_small = list(seq), []
                for bi, blk in enumerate(_st.blocks):
                    bc = None if lsmall is None else {**seq[bi],
                                                      **lsmall[bi]}
                    with jax.named_scope(_SCOPES.get(blk.kind, blk.kind)):
                        xc, bc = self._apply_block(
                            blk, {**lp[bi], **_stacks[bi]}, xc, rule,
                            cache=bc, pos=pos,
                            layer=li, shared=params.get("shared"),
                            enc_out=enc_out, x_emb=x_emb)
                    seq[bi], sm = _split_cache(bc or {})
                    new_small.append(sm)
                return (xc, pin(seq)), new_small

            body_fn = jax.checkpoint(body) if remat else body
            (x, seq), small = jax.lax.scan(
                body_fn, (x, pin(seq)),
                (pstack, small, jnp.arange(st.repeat, dtype=jnp.int32)))
            new_caches.append({"blocks": [{**a, **b}
                                          for a, b in zip(seq, small)]})
        return x, (new_caches if caches is not None else None)

    def _embed(self, params, tokens, batch_extra, rule):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0).astype(
            jnp.dtype(cfg.act_dtype))
        if cfg.emb_scale:
            x = x * math.sqrt(cfg.d_model)
        if cfg.n_img_tokens and "patch_embeds" in (batch_extra or {}):
            pe = batch_extra["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([pe, x[:, pe.shape[1]:]], axis=1)
        if rule is not None:
            x = constrain(x, rule, ("batch", "seq", "act_embed"))
        return x

    def _encoder(self, params, frames, rule):
        cfg = self.cfg
        enc = params["encoder"]
        x = frames.astype(jnp.dtype(cfg.act_dtype))
        pos = _sinusoid(x.shape[1], cfg.d_model, x.dtype)
        x = x + pos[None]

        def body(xc, lp):
            dx, _ = attention.gqa_apply(lp["attn"], xc, cfg, kind="bidir",
                                        rule=rule, use_rope=False)
            xc = xc + dx
            xc = xc + mlp.mlp_apply(lp["mlp"], xc, cfg, rule=rule)
            return xc, None

        x, _ = jax.lax.scan(body, x, enc["blocks"])
        return layer_norm(x, enc["ln"], enc["ln_b"])

    def forward(self, params, batch, rule=None, remat=False,
                return_hidden=False):
        """Full forward (training / prefill-without-cache): returns logits,
        optionally also the final hidden states (for the MTP head)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens, batch, rule)
        enc_out = (self._encoder(params, batch["frames"], rule)
                   if cfg.encdec else None)
        x_emb = x if cfg.hybrid_attn_every else None
        x, _ = self._run_stages(params, x, rule, enc_out=enc_out,
                                x_emb=x_emb, remat=remat)
        logits = self._head(params, x, rule)
        return (logits, x) if return_hidden else logits

    def mtp_forward(self, params, hidden, next_tokens, rule=None):
        """DeepSeek-V3 multi-token-prediction module (depth 1): combine the
        main model's final hidden state with the embedding of the *next*
        token, run one extra block, reuse the shared head — predicting
        token t+2 at position t."""
        cfg = self.cfg
        mtp = params["mtp"]
        emb = jnp.take(params["embed"], next_tokens, axis=0).astype(
            hidden.dtype)
        comb = jnp.concatenate([rms_norm(hidden, mtp["ln_h"]),
                                rms_norm(emb, mtp["ln_e"])], axis=-1)
        x = jnp.einsum("bse,ed->bsd", comb, mtp["proj"])
        if cfg.mla:
            dx, _ = attention.mla_apply(mtp["attn"], x, cfg, rule=rule)
        else:
            dx, _ = attention.gqa_apply(mtp["attn"], x, cfg, rule=rule)
        x = x + dx
        x = x + mlp.mlp_apply(mtp["mlp"], x, cfg, rule=rule)
        return self._head(params, x, rule)

    def _head(self, params, x, rule):
        cfg = self.cfg
        with jax.named_scope("head"):
            x = (rms_norm(x, params["final_ln"]) if cfg.norm == "rmsnorm"
                 else layer_norm(x, params["final_ln"],
                                 params["final_ln_b"]))
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["head" if "head" in params
                                       else "embed"])
            if self.padded_vocab != cfg.vocab:   # mask vocab-padding
                pad_mask = jnp.arange(self.padded_vocab) >= cfg.vocab
                logits = jnp.where(pad_mask, jnp.float32(
                    -2.0 ** 30).astype(logits.dtype), logits)
            if rule is not None:
                logits = constrain(logits, rule,
                                   ("batch", None, "act_vocab"))
        return logits

    # -- serving ----------------------------------------------------------
    def prefill(self, params, batch, caches, rule=None):
        cfg = self.cfg
        x = self._embed(params, batch["tokens"], batch, rule)
        enc_out = (self._encoder(params, batch["frames"], rule)
                   if cfg.encdec else None)
        x_emb = x if cfg.hybrid_attn_every else None
        x, caches = self._run_stages(params, x, rule, caches=caches, pos=0,
                                     enc_out=enc_out, x_emb=x_emb)
        return self._head(params, x[:, -1:], rule), caches

    def decode_step(self, params, caches, tokens, pos, rule=None):
        """tokens: (b, 1); pos: scalar int32 — one decoding step."""
        cfg = self.cfg
        x = self._embed(params, tokens, None, rule)
        x_emb = x if cfg.hybrid_attn_every else None
        x, caches = self._run_stages(params, x, rule, caches=caches, pos=pos,
                                     x_emb=x_emb)
        return self._head(params, x, rule), caches


def _sinusoid(length: int, channels: int, dtype):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(channels // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / channels)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)
