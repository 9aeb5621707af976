"""Pipeline parallelism over the pod axis (DESIGN.md §5): run a GPipe
schedule across 8 simulated pods, verify it against the sequential model,
and differentiate through it.

NOTE: sets XLA_FLAGS before importing jax — run as a standalone script.

    PYTHONPATH=src python examples/multipod_pipeline.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from repro.dist import gpipe    # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

mesh = make_mesh((8,), ("pod",))
P_STAGES, D, B, M = 8, 64, 32, 4

key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (P_STAGES, D, D)) * 0.2


def stage(w, x):
    return jnp.tanh(x @ w)


piped = jax.jit(gpipe(stage, mesh, axis="pod", n_microbatches=M))
x = jax.random.normal(jax.random.fold_in(key, 1), (B, D))
y = piped(ws, x)

want = x
for i in range(P_STAGES):
    want = stage(ws[i], want)
np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
print(f"GPipe over {P_STAGES} pods == sequential forward "
      f"({M} microbatches, {M + P_STAGES - 1} ticks)")

grads = jax.grad(lambda w: jnp.sum(piped(w, x) ** 2))(ws)
print(f"backward pipeline OK: grad norm {float(jnp.linalg.norm(grads)):.3f}")
bubble = (P_STAGES - 1) / (M + P_STAGES - 1)
print(f"pipeline bubble fraction at M={M}: {bubble:.2f} "
      "(drops as microbatches increase)")
