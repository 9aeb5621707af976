"""Public wrappers for the Pallas kernels, with block sizes chosen by the
layer-condition blocking advisor (core.blocking) against the target
machine's VMEM — the paper's §2.4.2 "ab-initio blocking factors" applied to
software-managed memory. Kernels compile on a TPU backend and run in the
Pallas interpreter on any other (``interpret=None``, see
:mod:`repro.kernels.backend`); an explicit bool overrides that."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import blocking, machine as machine_mod
from repro.kernels import flash_attention as _fa
from repro.kernels import longrange3d as _lr
from repro.kernels import stencil3d7pt as _s7

_V5E = None


def _v5e():
    global _V5E
    if _V5E is None:
        _V5E = machine_mod.load("V5E")
    return _V5E


def _check_vmem(name: str, need: int, radius: int, shape, n_arrays: int,
                elem_bytes: int) -> None:
    """Refuse a plane size whose pipelined working set exceeds the chip's
    physical VMEM, before the compiler sees it."""
    vmem = _v5e().vmem_bytes
    if need > vmem:
        blk = blocking.stencil_blocks(radius, shape, n_arrays=n_arrays,
                                      elem_bytes=elem_bytes,
                                      vmem_bytes=vmem)
        raise ValueError(
            f"{name}: N={shape[-1]} planes need {need / 2**20:.2f} MiB of "
            f"VMEM (double-buffered pipeline + temporaries), more than the "
            f"chip's {vmem / 2**20:.0f} MiB; the blocking advisor suggests "
            f"bk={blk.bk}, bj={blk.bj}, bi={blk.bi} tiles")


def stencil3d7pt(a, coeffs, interpret: bool | None = None):
    """Validates the 3-plane working set (3D layer condition) fits VMEM."""
    N = a.shape[-1]
    _check_vmem("stencil3d7pt", _s7.vmem_bytes(N, a.dtype.itemsize), 1,
                a.shape, 2, a.dtype.itemsize)
    return _s7.stencil3d7pt(a, jnp.asarray(coeffs, a.dtype),
                            interpret=interpret)


def longrange3d(u, v, roc, coeffs, interpret: bool | None = None):
    """Validates the working set (the 9-plane V ring, and the V, U, ROC and
    output planes in flight) fits VMEM."""
    N = u.shape[-1]
    _check_vmem("longrange3d", _lr.vmem_bytes(N, u.dtype.itemsize), 4,
                u.shape, 3, u.dtype.itemsize)
    return _lr.longrange3d(u, v, roc, jnp.asarray(coeffs, u.dtype),
                           interpret=interpret)


def flash_blocks(sq: int, skv: int, d: int, elem_bytes: int
                 ) -> tuple[int, int]:
    """``(block_q, block_kv)`` from the LC advisor, clamped to divisors of
    the sequence lengths."""
    tiles = blocking.attention_tiles(sq, skv, d, elem_bytes,
                                     _v5e().vmem_bytes)
    bq = max(8, min(tiles.bq, sq))
    bkv = max(128 if skv % 128 == 0 else skv, 1) if skv < 128 else \
        min(tiles.bkv, skv)
    while sq % bq:
        bq //= 2
    while skv % bkv:
        bkv //= 2
    return bq, bkv


def flash_attention(q, k, v, causal: bool = True,
                    interpret: bool | None = None,
                    q_offset: int | None = None):
    """Block sizes from the LC advisor; kv heads broadcast for GQA callers."""
    bq, bkv = flash_blocks(q.shape[2], k.shape[2], q.shape[3],
                           q.dtype.itemsize)
    return _fa.flash_attention(q, k, v, causal=causal, block_q=bq,
                               block_kv=bkv, q_offset=q_offset,
                               interpret=interpret)
