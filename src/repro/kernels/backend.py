"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

Every kernel entry point and the tuner take ``interpret: bool | None``;
``None`` is resolved here, from the platform JAX runs on, and nowhere
else."""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> compiled (False) on a TPU backend, the Pallas
    interpreter (True) on any other; an explicit bool stands."""
    if interpret is None:
        return not on_tpu()
    return bool(interpret)
