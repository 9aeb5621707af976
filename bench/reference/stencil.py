"""Plain references for the paper's two stencils, in ``jax.numpy``.

They follow the listings' loop bodies (arXiv:1702.04653, Listings 1 and
3) over the interior, and leave the boundary as the array that the
sweep writes into had it: the input for the 7-point Jacobi sweep, U for
the long-range leapfrog step. ``dtype`` is the precision the arithmetic
runs in; the result is returned in the inputs' dtype."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def jacobi7pt(a, coeffs, dtype=jnp.float32):
    """coeffs: (W, E, N, S, F, B, s) weights of the i-1, i+1, j-1, j+1,
    k-1, k+1 neighbours and the centre."""
    x = a.astype(dtype)
    w = [jnp.asarray(c, dtype) for c in coeffs]
    c = x[1:-1, 1:-1, 1:-1]
    new = (w[0] * x[1:-1, 1:-1, :-2] + w[1] * x[1:-1, 1:-1, 2:]
           + w[2] * x[1:-1, :-2, 1:-1] + w[3] * x[1:-1, 2:, 1:-1]
           + w[4] * x[:-2, 1:-1, 1:-1] + w[5] * x[2:, 1:-1, 1:-1]
           + w[6] * c)
    return a.at[1:-1, 1:-1, 1:-1].set(new.astype(a.dtype))


def longrange25pt(u, v, roc, coeffs, dtype=jnp.float32):
    """One leapfrog step U' = 2V - U + ROC * lap(V), lap the radius-4
    star with weights c0 (centre) .. c4 (distance 4)."""
    r = 4
    m, n, _ = v.shape
    x = v.astype(dtype)
    c = [jnp.asarray(ci, dtype) for ci in coeffs]

    def at(dk, dj, di):
        return x[r + dk:m - r + dk, r + dj:n - r + dj, r + di:n - r + di]

    lap = c[0] * at(0, 0, 0)
    for d in range(1, r + 1):
        lap = lap + c[d] * (at(0, 0, d) + at(0, 0, -d)
                            + at(0, d, 0) + at(0, -d, 0)
                            + at(d, 0, 0) + at(-d, 0, 0))
    inner = (slice(r, m - r), slice(r, n - r), slice(r, n - r))
    new = (2 * at(0, 0, 0) - u[inner].astype(dtype)
           + roc[inner].astype(dtype) * lap)
    return u.at[inner].set(new.astype(u.dtype))


jacobi7pt_jit = jax.jit(jacobi7pt, static_argnames=("dtype",))
longrange25pt_jit = jax.jit(longrange25pt, static_argnames=("dtype",))
