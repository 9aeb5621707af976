"""Pallas TPU flash attention (blocked online softmax).

Grid = (batch·heads, q_blocks, kv_blocks); the kv axis iterates fastest, so
the fp32 (m, l, acc) online-softmax state lives in VMEM scratch persisted
across kv steps — the classic TPU flash schedule. Block sizes come from the
layer-condition advisor (core.blocking.attention_tiles): the q tile is the
"layer" kept resident, the KV stream carries the ∞ reuse distance
(DESIGN.md §4).

Causal masking skips fully-masked kv blocks via ``pl.when`` (no MXU work
issued), and masks the diagonal block elementwise — this is the compute-
side win the §Perf log quantifies against the XLA-default attention, whose
materialized (sq × skv) score tensors dominate the memory roofline term.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -2.0 ** 30

#: Starting configurations for the autotuner (:mod:`repro.tune`), keyed by
#: the smallest kv sequence length the row applies to: ``(block_q,
#: block_kv)``.  These are the shipped defaults the tuner must beat — the
#: LC advisor (:func:`repro.core.blocking.attention_tiles`) picks larger
#: VMEM-filling tiles, this table holds the conservative fallbacks.
DEFAULT_CONFIGS: tuple[tuple[int, tuple[int, int]], ...] = (
    (4096, (256, 512)),
    (1024, (128, 256)),
    (256, (128, 128)),
    (0, (8, 128)),
)


def default_config(seq_q: int, seq_kv: int, head_dim: int = 128
                   ) -> tuple[int, int]:
    """The default ``(block_q, block_kv)`` for a problem shape: the
    :data:`DEFAULT_CONFIGS` row for ``seq_kv``, clamped (by halving) to
    divisors of the actual sequence lengths so the returned pair always
    passes :func:`validate_blocks`."""
    for floor, (bq, bkv) in DEFAULT_CONFIGS:
        if seq_kv >= floor:
            break
    bq = max(1, min(bq, seq_q))
    bkv = max(1, min(bkv, seq_kv))
    while seq_q % bq:
        bq //= 2
    while seq_kv % bkv:
        bkv //= 2
    return bq, bkv


def validate_blocks(seq_q: int, seq_kv: int, block_q: int,
                    block_kv: int) -> None:
    """Reject block sizes that don't tile the sequence lengths.

    The Pallas grid is ``(bh, seq_q // block_q, seq_kv // block_kv)``; a
    non-dividing block silently drops the remainder rows/columns, so this
    is a hard error, not a truncation.
    """
    if block_q <= 0 or block_kv <= 0:
        raise ValueError(
            f"flash_attention block sizes must be positive, got "
            f"block_q={block_q}, block_kv={block_kv}")
    if seq_q % block_q:
        raise ValueError(
            f"flash_attention: block_q={block_q} does not divide "
            f"seq_q={seq_q}; the q grid would drop {seq_q % block_q} "
            f"trailing rows (pick block_q from divisors of {seq_q}, "
            f"e.g. default_config({seq_q}, {seq_kv}))")
    if seq_kv % block_kv:
        raise ValueError(
            f"flash_attention: block_kv={block_kv} does not divide "
            f"seq_kv={seq_kv}; the kv grid would drop "
            f"{seq_kv % block_kv} trailing keys (pick block_kv from "
            f"divisors of {seq_kv}, e.g. default_config({seq_q}, "
            f"{seq_kv}))")


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, causal: bool, q_offset: int,
            block_q: int, block_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions of this tile
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q) + q_offset
    k_pos = ki * block_kv + jax.lax.iota(jnp.int32, block_kv)

    def compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bkv, d)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T) * scale                 # (bq, bkv) on the MXU
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_prev * alpha + p.sum(axis=-1)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(p, v)

    if causal:
        # skip kv blocks entirely above the diagonal (no work issued)
        first_q = qi * block_q + q_offset
        pl.when(ki * block_kv <= first_q + block_q - 1)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_kv", "interpret", "q_offset"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_kv: int = 128, q_offset: int | None = None,
                    interpret: bool | None = None):
    """q: (b, h, sq, d); k, v: (b, h, skv, d). Grouped-head (GQA) callers
    broadcast/reshape kv before the call. ``q_offset`` is the absolute
    position of q[0] in the kv sequence (decode: skv - sq).
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    if q_offset is None:
        q_offset = skv - sq
    validate_blocks(sq, skv, block_q, block_kv)
    bh = b * h
    qf = q.reshape(bh, sq, d)
    kf = k.reshape(bh, skv, d)
    vf = v.reshape(bh, skv, d)
    grid = (bh, sq // block_q, skv // block_kv)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(d), causal=causal,
                          q_offset=q_offset, block_q=block_q,
                          block_kv=block_kv),
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda g, qi, ki: (g, qi, 0)),
            pl.BlockSpec((1, block_kv, d), lambda g, qi, ki: (g, ki, 0)),
            pl.BlockSpec((1, block_kv, d), lambda g, qi, ki: (g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda g, qi, ki: (g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # m
            pltpu.VMEM((block_q,), jnp.float32),       # l
            pltpu.VMEM((block_q, d), jnp.float32),     # acc
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
