"""Pallas TPU kernel for the paper's Listing-1 3D 7-point star stencil.

TPU adaptation of the paper's cache analysis (DESIGN.md §2): the kernel
streams k-planes through VMEM — the grid walks k, and each step holds the
THREE (N, N) planes k-1, k, k+1 resident. That working set is *exactly* the
3D layer condition of paper §2.4.2 (`3 layers must fit the cache`), realized
here as a software decision instead of an LRU prediction: pallas double-
buffers the plane fetches (HBM→VMEM DMA overlaps compute — the `overlap`
flag of the TPU-ECM machine model).

The three planes arrive as three BlockSpecs of the *same* input array with
shifted index maps (k-1, k, k+1) — Pallas' way of expressing halo reads.
In-plane neighbours are whole-plane rolls (static slices), and an iota
mask keeps the boundary equal to the input, so every store is a full,
aligned plane; the seven coefficients sit in SMEM as scalars.
:func:`vmem_bytes` counts what the kernel holds in VMEM and sets the
compiler's scoped-VMEM limit from it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import blocking
from repro.core.frontends.trace import kernel_spec
from repro.kernels.backend import resolve_interpret

#: planes of temporaries the kernel body keeps live in VMEM beyond the
#: pipeline's buffers (the rolled neighbours and the masked result); the
#: v5e compiler needs at most 2.6 of them for N <= 1015
TEMP_PLANES = 3


@kernel_spec(name="3d-7pt",
             arrays={"a": ("M", "N", "N"), "b": ("M", "N", "N")},
             loops=[("k", 1, "M-1"), ("j", 1, "N-1"), ("i", 1, "N-1")],
             element_bytes=8)
def point(a, b, w, k, j, i):
    """One innermost iteration of the stencil — the analyzable face of the
    Pallas kernel below.  Tracing this through the ``trace`` frontend yields
    the same :class:`LoopKernel` IR as parsing the paper's Listing-1 C file
    (``configs/stencils/stencil_3d7pt.c``): 7 affine reads of ``a``, one
    write of ``b``, 7 muls + 6 adds.  ``element_bytes=8`` matches the C
    double; analyze with ``frontend_opts={"element_bytes": 4}`` for the
    float32 the TPU kernel actually runs."""
    b[k, j, i] = (w.wC * a[k, j, i]
                  + w.wW * a[k, j, i - 1] + w.wE * a[k, j, i + 1]
                  + w.wS * a[k, j - 1, i] + w.wN * a[k, j + 1, i]
                  + w.wB * a[k - 1, j, i] + w.wF * a[k + 1, j, i])


def _kernel(prev_ref, cur_ref, nxt_ref, coef_ref, out_ref):
    k = pl.program_id(0)
    nk = pl.num_programs(0)
    cur = cur_ref[0]            # (N, N) plane k; prev/nxt: k-1, k+1 clamped
    cW, cE, cN, cS, cF, cB, s = (coef_ref[i] for i in range(7))
    # jnp.roll with a static shift is two static slices; the wrapped edge
    # lands only on boundary points, which the mask below discards
    val = (cW * jnp.roll(cur, 1, 1) + cE * jnp.roll(cur, -1, 1)
           + cN * jnp.roll(cur, 1, 0) + cS * jnp.roll(cur, -1, 0)
           + cF * prev_ref[0] + cB * nxt_ref[0] + s * cur)
    nj, ni = cur.shape
    j = lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    i = lax.broadcasted_iota(jnp.int32, cur.shape, 1)
    interior = ((j > 0) & (j < nj - 1) & (i > 0) & (i < ni - 1)
                & (k > 0) & (k < nk - 1))
    out_ref[0] = jnp.where(interior, val.astype(cur.dtype), cur)


def vmem_bytes(n: int, elem_bytes: int) -> int:
    """VMEM the kernel holds for (N, N) planes: three input planes and the
    output plane, double-buffered, plus :data:`TEMP_PLANES`."""
    return blocking.plane_pipeline_vmem(3, TEMP_PLANES, n, n,
                                        elem_bytes)


@functools.partial(jax.jit, static_argnames=("interpret",))
def stencil3d7pt(a, coeffs, *, interpret: bool | None = None):
    """a: (M, N, N) float32/float64->float32. coeffs: (7,) in W,E,N,S,F,B,s
    order. Returns b with boundary = a. ``interpret=None`` compiles on a
    TPU and interprets elsewhere."""
    M, N, _ = a.shape

    def shifted(dk):
        return pl.BlockSpec((1, N, N),
                            lambda k: (jnp.clip(k + dk, 0, M - 1), 0, 0))

    return pl.pallas_call(
        _kernel,
        grid=(M,),
        in_specs=[shifted(-1), shifted(0), shifted(+1),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((1, N, N), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(N, a.dtype.itemsize)),
        interpret=resolve_interpret(interpret),
    )(a, a, a, coeffs)
