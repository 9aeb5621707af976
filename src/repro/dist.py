"""Distributed primitives (DESIGN.md §5): int8 gradient compression with
error feedback for the data axis, and a GPipe schedule over the pod axis.

Both are exact-math-preserving at the API level: ``compressed_psum_tree``
returns the quantization residual so callers re-inject it next step (error
feedback — the residual telescopes and the accumulated mean converges to
the exact mean), and ``gpipe`` reproduces the sequential composition of
stages bit-for-bit while executing the (M + P - 1)-tick pipeline schedule
with stage weights sharded one-per-device along the pipeline axis.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

# jax is optional at import time: the analytic collective wire models at
# the bottom of this module (used by repro.fleet for collective pricing)
# must stay importable in jax-free environments; the executable primitives
# above them raise on use instead.
try:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    HAVE_JAX = True
except ImportError:                   # pragma: no cover
    jax = jnp = P = shard_map = None  # type: ignore[assignment]
    HAVE_JAX = False


class CompressionState(NamedTuple):
    """Per-device error-feedback residual carried across steps."""
    error: jax.Array

    @classmethod
    def zeros_like(cls, grad: jax.Array) -> "CompressionState":
        return cls(error=jnp.zeros_like(grad))


def _quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8: returns (q, scale) with x ~ q * scale."""
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum_tree(grad: jax.Array, error: jax.Array,
                         axis: str) -> tuple[jax.Array, jax.Array]:
    """Mean-reduce ``grad`` over ``axis`` with int8 wire compression.

    Call inside shard_map.  Each device quantizes ``grad + error`` to int8
    (the wire format of the tree all-reduce), the dequantized values are
    summed across the axis, and the local quantization residual is returned
    as the next step's ``error`` — so the compression error telescopes
    instead of accumulating.
    """
    x = grad + error
    q, scale = _quantize_int8(x)
    deq = q.astype(x.dtype) * scale
    new_error = x - deq
    n = jax.lax.psum(jnp.ones((), x.dtype), axis)
    mean = jax.lax.psum(deq, axis) / n
    return mean, new_error


def gpipe(stage, mesh, axis: str = "pod", n_microbatches: int = 4):
    """GPipe pipeline over a mesh axis: ``stage(w, x) -> y`` applied by P
    consecutive stages whose weights ``ws[p]`` live one-per-device.

    The returned callable ``piped(ws, x)`` splits the batch into
    ``n_microbatches``, runs the (M + P - 1)-tick schedule — device p
    executes microbatch t - p at tick t, activations hop to the next device
    via ppermute — and reassembles the full batch.  Differentiable: the
    backward pipeline is the transposed permutation schedule.
    """
    n_stages = mesh.shape[axis]

    def piped(ws, x):
        m = n_microbatches
        assert x.shape[0] % m == 0, \
            "n_microbatches must divide the batch size"
        mbs = x.reshape(m, x.shape[0] // m, *x.shape[1:])
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(axis), P(None)),
            out_specs=P(None), check_vma=False)
        def run(w_local, mbs):
            p = jax.lax.axis_index(axis)
            w = w_local[0]
            h = jnp.zeros_like(mbs[0])
            outs = jnp.zeros_like(mbs)
            for t in range(m + n_stages - 1):
                # stage 0 ingests microbatch t; everyone else continues the
                # activation handed over at the previous tick
                if t < m:
                    inp = jnp.where(p == 0, mbs[t], h)
                else:
                    inp = h
                y = stage(w, inp)
                done = t - (n_stages - 1)
                if 0 <= done < m:     # last stage emits microbatch `done`
                    outs = outs.at[done].add(
                        jnp.where(p == n_stages - 1, y, jnp.zeros_like(y)))
                h = jax.lax.ppermute(y, axis, fwd)
            return jax.lax.psum(outs, axis)   # only the last stage wrote

        outs = run(ws, mbs)
        return outs.reshape(x.shape[0], *x.shape[1:])

    return piped


# ----------------------------------------------------------------------
# Analytic collective wire models (jax-free; used by repro.fleet)
# ----------------------------------------------------------------------
def collective_bandwidth(machine) -> float:
    """Bytes/s at which collective wire traffic drains on ``machine``.

    TPU machines price the ring on one ICI link per hop
    (``ici link bandwidth``), matching the module-level
    ``HLORooflineResult.t_collective`` term so per-op collective times
    conserve against the whole-module roofline.  Cache machines (x86)
    have no interconnect field: intra-node collectives move through
    shared memory, so the main memory bandwidth is the wire rate.
    """
    bw = float(getattr(machine, "ici_link_bandwidth", 0.0) or 0.0)
    if bw:
        return bw
    return float(getattr(machine, "main_memory_bandwidth", 0.0) or 0.0)


def collective_wire_time(wire_bytes: float, machine) -> float:
    """Seconds on the wire for already-ring-expanded ``wire_bytes``."""
    bw = collective_bandwidth(machine)
    return wire_bytes / bw if bw else 0.0


def collective_time(kind: str, payload_bytes: float, group: int,
                    machine) -> float:
    """Ring-model seconds for one collective: expand ``payload_bytes``
    through the per-kind wire factor (all-reduce 2(n-1)/n, all-gather
    (n-1)/n, reduce-scatter (n-1)x, all-to-all (n-1)/n, permute 1x —
    the factors of ``hlo_analysis._collective_wire_bytes``) and divide
    by :func:`collective_bandwidth`."""
    from repro.core.hlo_analysis import _collective_wire_bytes
    return collective_wire_time(
        _collective_wire_bytes(kind, payload_bytes, group), machine)
