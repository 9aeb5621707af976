"""Work one sweep of a 3D star stencil needs, from its shape alone.

Sites are the paper's loop count, the interior (M-2r)(N-2r)^2. Needed
bytes are each input array read once and the output written once, over
the whole (M, N, N) arrays: the least any implementation moves, however
it tiles or fuses. Needed flops are the arithmetic the paper's listing
spells out per interior site. Nothing here depends on how the program
implements the sweep, so a faster kernel cannot make the count stale."""
from __future__ import annotations

#: per stencil: radius, arrays read, arrays written, flops per site
#: (Listing 1: 7 mul + 6 add; Listing 3: 15 mul + 26 add)
STENCILS = {
    "jacobi7pt": {"radius": 1, "reads": 1, "writes": 1, "flops": 13},
    "longrange25pt": {"radius": 4, "reads": 3, "writes": 1, "flops": 41},
}


def sites(kind: str, m: int, n: int) -> int:
    r = STENCILS[kind]["radius"]
    return (m - 2 * r) * (n - 2 * r) ** 2


def sweep(kind: str, m: int, n: int, elem_bytes: int) -> dict:
    """``{"sites", "flops", "bytes"}`` one sweep needs."""
    s = STENCILS[kind]
    arrays = s["reads"] + s["writes"]
    return {"sites": sites(kind, m, n),
            "flops": s["flops"] * sites(kind, m, n),
            "bytes": arrays * m * n * n * elem_bytes}
