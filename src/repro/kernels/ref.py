"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract:
tests assert_allclose kernels in interpret mode against these)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


# ----------------------------------------------------------------------
# paper Listing 1: 3D 7-point star stencil (radius 1, per-direction coeffs)
# ----------------------------------------------------------------------
def stencil3d7pt(a, coeffs):
    """a: (M, N, N). coeffs: dict(W, E, N, S, F, B, s). Boundary (width 1)
    copies the untouched output (the paper's loops run 1..N-2); we define
    out = a at the boundary."""
    c = coeffs
    interior = (
        c["W"] * a[1:-1, 1:-1, :-2] + c["E"] * a[1:-1, 1:-1, 2:]
        + c["N"] * a[1:-1, :-2, 1:-1] + c["S"] * a[1:-1, 2:, 1:-1]
        + c["F"] * a[:-2, 1:-1, 1:-1] + c["B"] * a[2:, 1:-1, 1:-1]
        + c["s"] * a[1:-1, 1:-1, 1:-1])
    out = a
    return out.at[1:-1, 1:-1, 1:-1].set(interior.astype(a.dtype))


# ----------------------------------------------------------------------
# paper Listing 3: 3D long-range star stencil (radius 4, symmetric coeffs)
# ----------------------------------------------------------------------
def longrange3d(u, v, roc, c):
    """u, v, roc: (M, N, N); c: array-like of 5 coefficients c0..c4.
    Returns the updated U. Boundary width 4 copies u."""
    r = 4
    M, J, I = v.shape
    vi = v[r:-r, r:-r, r:-r]
    lap = c[0] * vi
    for d in range(1, r + 1):
        lap = lap + c[d] * (
            v[r:-r, r:-r, r + d:I - r + d] + v[r:-r, r:-r, r - d:I - r - d]
            + v[r:-r, r + d:J - r + d, r:-r] + v[r:-r, r - d:J - r - d, r:-r]
            + v[r + d:M - r + d, r:-r, r:-r] + v[r - d:M - r - d, r:-r, r:-r])
    upd = 2.0 * vi - u[r:-r, r:-r, r:-r] + roc[r:-r, r:-r, r:-r] * lap
    return u.at[r:-r, r:-r, r:-r].set(upd.astype(u.dtype))


# ----------------------------------------------------------------------
# flash attention (causal / full), grouped heads handled by the caller
# ----------------------------------------------------------------------
def attention(q, k, v, causal: bool = True):
    """q: (b, h, sq, d), k/v: (b, h, skv, d) -> (b, h, sq, d); fp32 inside."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    if causal:
        mask = (jnp.arange(sq)[:, None] + (skv - sq)) >= jnp.arange(skv)[None]
        scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# ----------------------------------------------------------------------
# grouped matmul over a MoE layer's held experts (kernels/moe_gmm.py)
# ----------------------------------------------------------------------
def grouped_matmul(x, w, group_sizes):
    """x: (P, d) rows sorted by group; w: (E, d, f); group_sizes: (E,).
    Row ``p`` of group ``g`` -> ``x[p] @ w[g]`` in float32; rows past the
    last group -> 0. Returns (P, f) in ``x``'s dtype."""
    ends = jnp.cumsum(group_sizes)
    g = jnp.searchsorted(ends, jnp.arange(x.shape[0]), side="right")
    out = jnp.zeros((x.shape[0], w.shape[-1]), jnp.float32)
    for e in range(w.shape[0]):
        y = jnp.dot(x.astype(jnp.float32), w[e].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        out = jnp.where((g == e)[:, None], y, out)
    return out.astype(x.dtype)
