"""Pallas TPU kernel for the paper's Listing-3 3D long-range (radius-4,
25-point) star stencil — the paper's §3 case study.

Working set per output plane k: NINE V-planes (k-4..k+4) + the U and ROC
planes at k — the 3D layer condition of the long-range stencil (the paper's
Listing 5 shows it breaking in L3 at N = 546 on IVY). The kernel keeps the
nine V planes in a VMEM ring, so each plane crosses HBM once: grid step s
brings in V plane s through the one V BlockSpec, stores it in ring slot
s % 9, and computes output plane s - 4, whose U, ROC and output BlockSpecs
lag V by the radius. The ring carries state from step to step, so the grid
runs in order. A step moves four planes (V, U, ROC, the output), what the
kernel's ECM and Roofline predictions assume (DESIGN.md §2).

In-plane neighbours are whole-plane rolls, an iota mask keeps the width-4
boundary equal to U, the boundary planes (and the steps that fill the
ring) copy U, and the coefficients sit in SMEM. :func:`vmem_bytes` counts
29 planes plus 1 MiB: 117 MiB at N = 1024, the largest plane that fits the
chip's 128 MiB of VMEM.
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
#: V planes the ring holds: k-4 .. k+4
RING_PLANES = 2 * RADIUS + 1
#: planes of temporaries the kernel body keeps live in VMEM beyond the
#: pipeline's buffers and the ring (rolled neighbours, the Laplacian, the
#: masked result); the v5e compiler needs at most 12.0 of them for
#: N <= 1024 (28.996 planes in all at N = 1015)
TEMP_PLANES = 12


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


def _kernel(v_ref, u_ref, roc_ref, c, out_ref, ring):
    # step s brings in V plane s and computes output plane kc = s - r from
    # the ring, which holds V planes kc-r .. kc+r in slots plane % (2r+1)
    r = RADIUS
    slots = RING_PLANES
    s = pl.program_id(0)
    m = pl.num_programs(0) - r
    kc = s - r

    @pl.when(s < m)
    def _():
        ring[s % slots] = v_ref[0]

    @pl.when((kc >= r) & (kc < m - r))
    def _():
        cur = ring[kc % slots]
        # jnp.roll with a static shift is two static slices; wrapped edges
        # land only on the width-r boundary, which the mask below discards
        lap = c[0] * cur
        for d in range(1, r + 1):
            lap = lap + c[d] * (
                jnp.roll(cur, -d, 1) + jnp.roll(cur, d, 1)          # i+-d
                + jnp.roll(cur, -d, 0) + jnp.roll(cur, d, 0)        # j+-d
                + ring[(kc + d) % slots] + ring[(kc - d) % slots])  # k+-d
        u = u_ref[0]
        upd = 2.0 * cur - u + roc_ref[0] * lap
        nj, ni = cur.shape
        j = lax.broadcasted_iota(jnp.int32, cur.shape, 0)
        i = lax.broadcasted_iota(jnp.int32, cur.shape, 1)
        interior = (j >= r) & (j < nj - r) & (i >= r) & (i < ni - r)
        out_ref[0] = jnp.where(interior, upd.astype(u.dtype), u)

    # while the ring fills and at the top, the whole plane is boundary
    @pl.when((kc < r) | (kc >= m - r))
    def _():
        out_ref[0] = u_ref[0]


def vmem_bytes(n: int, elem_bytes: int) -> int:
    """VMEM the kernel holds for (N, N) planes: the V, U and ROC input
    planes and the output plane, double-buffered, plus the ring's
    :data:`RING_PLANES` and :data:`TEMP_PLANES`."""
    return blocking.plane_pipeline_vmem(3, RING_PLANES + TEMP_PLANES, n, n,
                                        elem_bytes)


@functools.partial(jax.jit, static_argnames=("interpret",))
def longrange3d(u, v, roc, coeffs, *, interpret: bool | None = None):
    """u, v, roc: (M, N, N); coeffs: (5,) = c0..c4. Returns updated U
    (boundary width 4 = u, matching the paper's loop bounds).
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    M, N, _ = u.shape
    r = RADIUS

    def lagged(s):          # U, ROC and the output trail V by r planes
        return (jnp.clip(s - r, 0, M - 1), 0, 0)

    return pl.pallas_call(
        _kernel,
        name="longrange3d",
        grid=(M + r,),
        in_specs=[
            pl.BlockSpec((1, N, N), lambda s: (jnp.minimum(s, M - 1), 0, 0)),
            pl.BlockSpec((1, N, N), lagged),                     # u
            pl.BlockSpec((1, N, N), lagged),                     # roc
            pl.BlockSpec(memory_space=pltpu.SMEM)],              # coeffs
        out_specs=pl.BlockSpec((1, N, N), lagged),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        scratch_shapes=[pltpu.VMEM((RING_PLANES, N, N), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(N, u.dtype.itemsize)),
        interpret=resolve_interpret(interpret),
    )(v, u, roc, coeffs)
