"""Pallas TPU kernel for the paper's Listing-3 3D long-range (radius-4,
25-point) star stencil — the paper's §3 case study.

Working set per grid step: NINE V-planes (k-4..k+4) + the U and ROC planes
at k — the 3D layer condition of the long-range stencil (the paper's
Listing 5 shows it breaking in L3 at N = 546 on IVY). On TPU v5e the
pipeline double-buffers all eleven input planes and the output plane, so
:func:`vmem_bytes` counts 24 planes plus temporaries: 117 MiB at N = 1024,
the largest plane that fits the chip's 128 MiB of VMEM.

Like the 7-point kernel, halo planes are shifted BlockSpecs of V; pallas
pipelines the plane DMAs across grid steps, so consecutive k steps re-fetch
8 of 9 planes from HBM unless the compiler's window reuse kicks in — the
pessimistic (ECM, serial) vs optimistic (Roofline, overlapped) bracket of
DESIGN.md §2 applies verbatim. In-plane neighbours are whole-plane rolls,
an iota mask keeps the width-4 boundary equal to U, and the coefficients
sit in SMEM.
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

RADIUS = 4
#: planes of temporaries the kernel body keeps live in VMEM beyond the
#: pipeline's buffers (rolled neighbours, the Laplacian, the masked
#: result); the v5e compiler needs at most 4.3 of them for N <= 1024
TEMP_PLANES = 5


@kernel_spec(name="3d-long-range",
             arrays={"U": ("M", "N", "N"), "V": ("M", "N", "N"),
                     "ROC": ("M", "N", "N")},
             loops=[("k", 4, "M-4"), ("j", 4, "N-4"), ("i", 4, "N-4")],
             element_bytes=8)
def point(U, V, ROC, c, k, j, i):
    """One innermost iteration of the long-range stencil — traces to the
    same :class:`LoopKernel` IR as the paper's Listing-3 C file
    (``configs/stencils/stencil_3d_long_range.c``): 25 reads of ``V`` plus
    ``U``/``ROC`` at the center, one write of ``U``, 15 muls + 26 adds.
    The ``range`` loop unrolls at trace time, mirroring the C body's
    textual sum."""
    lap = c[0] * V[k, j, i]
    for d in range(1, RADIUS + 1):
        lap = (lap + c[d] * (V[k, j, i + d] + V[k, j, i - d])
                   + c[d] * (V[k, j + d, i] + V[k, j - d, i])
                   + c[d] * (V[k + d, j, i] + V[k - d, j, i]))
    U[k, j, i] = 2.0 * V[k, j, i] - U[k, j, i] + ROC[k, j, i] * lap


def _kernel(*refs):
    # refs: v[k-4] .. v[k+4] (9), u, roc, coef (SMEM), out
    vrefs, u_ref, roc_ref, c, out_ref = refs[:9], *refs[9:]
    k = pl.program_id(0)
    nk = pl.num_programs(0)
    r = RADIUS
    cur = vrefs[r][0]
    # jnp.roll with a static shift is two static slices; wrapped edges land
    # only on the width-r boundary, which the mask below discards
    lap = c[0] * cur
    for d in range(1, r + 1):
        lap = lap + c[d] * (
            jnp.roll(cur, -d, 1) + jnp.roll(cur, d, 1)          # i+-d
            + jnp.roll(cur, -d, 0) + jnp.roll(cur, d, 0)        # j+-d
            + vrefs[r + d][0] + vrefs[r - d][0])                # k+-d
    u = u_ref[0]
    upd = 2.0 * cur - u + roc_ref[0] * lap
    nj, ni = cur.shape
    j = lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    i = lax.broadcasted_iota(jnp.int32, cur.shape, 1)
    interior = ((j >= r) & (j < nj - r) & (i >= r) & (i < ni - r)
                & (k >= r) & (k < nk - r))
    out_ref[0] = jnp.where(interior, upd.astype(u.dtype), u)


def vmem_bytes(n: int, elem_bytes: int) -> int:
    """VMEM the kernel holds for (N, N) planes: eleven input planes and the
    output plane, double-buffered, plus :data:`TEMP_PLANES`."""
    return blocking.plane_pipeline_vmem(11, TEMP_PLANES, n, n,
                                        elem_bytes)


@functools.partial(jax.jit, static_argnames=("interpret",))
def longrange3d(u, v, roc, coeffs, *, interpret: bool | None = None):
    """u, v, roc: (M, N, N); coeffs: (5,) = c0..c4. Returns updated U
    (boundary width 4 = u, matching the paper's loop bounds).
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    M, N, _ = u.shape

    def vplane(dk):
        return pl.BlockSpec((1, N, N),
                            lambda k, _dk=dk: (jnp.clip(k + _dk, 0, M - 1),
                                               0, 0))

    in_specs = [vplane(dk) for dk in range(-RADIUS, RADIUS + 1)]
    in_specs += [pl.BlockSpec((1, N, N), lambda k: (k, 0, 0)),   # u
                 pl.BlockSpec((1, N, N), lambda k: (k, 0, 0)),   # roc
                 pl.BlockSpec(memory_space=pltpu.SMEM)]          # coeffs
    args = [v] * 9 + [u, roc, coeffs]
    return pl.pallas_call(
        _kernel,
        grid=(M,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, N, N), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(N, u.dtype.itemsize)),
        interpret=resolve_interpret(interpret),
    )(*args)
