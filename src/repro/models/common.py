"""Model substrate: parameter records, sharding-rule engine, norms, RoPE.

Parameters are declared once as a pytree of :class:`PRec` (shape + logical
axis names + init scale). Three interpreters map the record tree to
(a) ``PartitionSpec`` trees via a logical→mesh rule table,
(b) ``ShapeDtypeStruct`` trees (dry-run: no allocation), and
(c) materialized random arrays (jit-compatible).

The rule tables implement DP/FSDP/TP/EP/SP as *roles* of the two mesh axes
(`data`, `model`) plus the replicated/pipelined `pod` axis — see DESIGN.md §5.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class PRec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]       # logical axis name per dim
    scale: float | None = None         # None -> fan-in 1/sqrt(shape[fan_in_dim])
    dtype: Any = None                  # None -> builder default
    init: str = "normal"               # normal | zeros | ones

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_rec(x) -> bool:
    return isinstance(x, PRec)


def tmap(f, tree):
    return jax.tree.map(f, tree, is_leaf=is_rec)


# ----------------------------------------------------------------------
# Logical -> mesh rule tables. `fsdp` additionally shards one weight dim
# over 'data' (ZeRO-3); serving modes keep weights TP-only.
# ----------------------------------------------------------------------
def rules(mode: str, *, fsdp: bool = True, pods_in_batch: bool = True,
          seq_axis: str | tuple | None = None,
          act_embed_axis: str | None = None,
          kv_seq_axis: str | tuple | None = None,
          fsdp_axes: tuple = ("data",)) -> dict[str, Any]:
    """Logical-axis -> mesh-axis rule table.

    modes: train | prefill | decode | long.
    ``fsdp``       — shard the non-TP weight dim over ``fsdp_axes`` (ZeRO-3
                     for training; "zero-inference" weight sharding when a
                     serving config sets it).
    ``seq_axis``   — shard the residual stream's sequence dim (Megatron-SP /
                     Ulysses style; attention internals reshard seq<->heads).
    ``act_embed_axis`` — shard activations' embed dim instead (SSM/hybrid
                     families, where sequence must stay contiguous for the
                     chunked scan).
    ``kv_seq_axis``— shard KV caches' sequence dim (flash-decoding SP for
                     long-context decode, or `model` for MLA's head-free
                     latent cache).
    """
    batch = ("pod", "data") if pods_in_batch else ("data",)
    r: dict[str, Any] = {
        # weight axes
        "vocab": "model", "embed": None, "heads": "model", "kv": "model",
        "hd": None, "ff": "model", "experts": "model", "eff": None,
        "layers": None,
        "state": None, "conv": None, "inner": "model", "latent": None,
        # activation axes
        "batch": batch, "seq": seq_axis, "kv_seq": None,
        "act_embed": act_embed_axis,
        "act_heads": "model", "act_kv": "model", "act_ff": "model",
        "act_vocab": "model", "act_inner": "model", "act_experts": "model",
    }
    if mode == "long":
        r["batch"] = None          # long_500k: global_batch=1 cannot shard
    if fsdp:
        r["embed"] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
    if kv_seq_axis is not None:
        r["kv_seq"] = kv_seq_axis
    return r


def spec_of(rec: PRec, rule: dict[str, Any]) -> P:
    return P(*(rule.get(a) if a is not None else None for a in rec.axes))


def spec_tree(recs, rule: dict[str, Any]):
    return tmap(lambda r: spec_of(r, rule), recs)


def abstract_tree(recs, default_dtype=jnp.bfloat16):
    return tmap(lambda r: jax.ShapeDtypeStruct(
        r.shape, r.dtype or default_dtype), recs)


def materialize(recs, key, default_dtype=jnp.bfloat16):
    """Random init; deterministic per-leaf via fold_in over the leaf index."""
    leaves, treedef = jax.tree.flatten(recs, is_leaf=is_rec)

    def one(i, r: PRec):
        dt = r.dtype or default_dtype
        if r.init == "zeros":
            return jnp.zeros(r.shape, dt)
        if r.init == "ones":
            return jnp.ones(r.shape, dt)
        if r.init == "fill":      # constant fill; value in r.scale
            return jnp.full(r.shape, r.scale, dt)
        k = jax.random.fold_in(key, i)
        fan_in = r.shape[-2] if len(r.shape) >= 2 else max(1, r.shape[-1])
        scale = r.scale if r.scale is not None else fan_in ** -0.5
        return (jax.random.normal(k, r.shape, jnp.float32) * scale).astype(dt)

    return jax.tree.unflatten(treedef, [one(i, r) for i, r in enumerate(leaves)])


def shardings(recs, mesh, rule: dict[str, Any]):
    from jax.sharding import NamedSharding
    return tmap(lambda r: NamedSharding(mesh, spec_of(r, rule)), recs)


# ----------------------------------------------------------------------
# Numerics
# ----------------------------------------------------------------------
def rms_norm(x, gamma, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * gamma + beta).astype(x.dtype)


def rope(x, positions, theta: float = 10000.0, scale: float = 1.0,
         yarn: YarnConfig | None = None):
    """Rotary embedding over the last dim of x: (..., seq, heads, hd);
    with ``yarn``, YaRN's frequencies."""
    hd = x.shape[-1]
    half = hd // 2
    if yarn is None:
        freqs = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)) * scale
    else:
        freqs = yarn_inv_freq(hd, theta, yarn)
    # positions: (..., seq) -> angles (..., seq, 1, half)
    ang = positions.astype(jnp.float32)[..., None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin,
                            xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (arXiv:2309.00071), as DeepSeek-V3's config
    ``rope_scaling`` states it. Its ``mscale`` equals ``mscale_all_dim``,
    so cos and sin stay unscaled and only the softmax scale carries
    ``mscale_all_dim``."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 1.0


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, y: YarnConfig):
    """(dim // 2,) frequencies: the extrapolated ``theta^(-2i/dim)`` below
    the correction range, those divided by ``factor`` above it, and a
    linear ramp between, the range's ends counted in dims from
    ``beta_fast`` and ``beta_slow`` rotations at the original length."""
    def dim_of(rot):
        return (dim * math.log(y.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_of(y.beta_fast)), 0)
    high = min(math.ceil(dim_of(y.beta_slow)), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * i / dim)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra * keep + extra / y.factor * (1.0 - keep)


def pad_heads(n: int, tp: int = 16) -> int:
    """Pad head counts up to TP divisibility (Megatron-style GQA padding;
    see DESIGN.md §4 — llama4 40→48 Q heads, 8→16 KV heads etc.)."""
    return -(-n // tp) * tp


def with_sharding(x, *spec):
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain(x, rule: dict[str, Any], axes: tuple[str | None, ...]):
    resolved = tuple(rule.get(a) if a is not None else None for a in axes)
    if all(r is None for r in resolved):
        return x                      # fully replicated: no mesh needed
    return jax.lax.with_sharding_constraint(x, P(*resolved))
