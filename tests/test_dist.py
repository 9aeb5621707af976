"""Distributed pieces (run in subprocesses with 8 forced host devices):
int8 gradient compression with error feedback, GPipe pipeline over the pod
axis, and the sharded train step itself on a small mesh."""


def test_compressed_psum_error_feedback(devices8):
    code = """
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.dist import CompressionState, compressed_psum_tree
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
key = jax.random.PRNGKey(0)
g = jax.random.normal(key, (8, 64, 32))     # per-device gradient slices

@partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
         out_specs=(P(None), P("data")), check_vma=False)
def cpsum(gl, el):
    out, err = compressed_psum_tree(gl[0], el[0], "data")
    return out[None], err[None]

err0 = jnp.zeros_like(g)
out, err = cpsum(g, err0)
exact = g.mean(0)
rel = float(jnp.linalg.norm(out[0] - exact) / jnp.linalg.norm(exact))
assert rel < 0.02, rel                      # one-shot int8 error small

# error feedback: repeated compression of the SAME gradient converges to
# the exact mean (residual is re-injected)
acc = jnp.zeros_like(exact)
e = err0
for i in range(8):
    o, e = cpsum(g, e)
    acc += o[0]
rel_acc = float(jnp.linalg.norm(acc/8 - exact) / jnp.linalg.norm(exact))
assert rel_acc < rel / 2, (rel_acc, rel)
print("compression OK", rel, rel_acc)
"""
    assert "compression OK" in devices8(code)


def test_gpipe_matches_sequential(devices8):
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.dist import gpipe
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("pod",))
P_stages, D, B = 8, 16, 32
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (P_stages, D, D)) * 0.3

def stage(w, x):
    return jnp.tanh(x @ w)

piped = gpipe(stage, mesh, axis="pod", n_microbatches=4)
x = jax.random.normal(jax.random.fold_in(key, 1), (B, D))
y = piped(ws, x)
want = x
for i in range(P_stages):
    want = stage(ws[i], want)
np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)

# differentiable: grad through the pipeline matches sequential grad
def loss_p(ws_):
    return jnp.sum(piped(ws_, x) ** 2)
def loss_s(ws_):
    h = x
    for i in range(P_stages):
        h = stage(ws_[i], h)
    return jnp.sum(h ** 2)
g1 = jax.grad(loss_p)(ws)
g2 = jax.grad(loss_s)(ws)
np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)
print("gpipe OK")
"""
    assert "gpipe OK" in devices8(code)


def test_sharded_train_step_small_mesh(devices8):
    """The production train step (FSDP+TP rules) runs REAL numerics on a
    (2, 4) mesh and matches the single-device step loss."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.launch.cell import rule_for, batch_specs, shard
from repro.launch.mesh import make_local_mesh
from repro.models.common import materialize, spec_tree
from repro.models.lm import LM
from repro.optim import OptConfig, adamw_init
from repro.train import TrainConfig, make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P

cfg = configs.reduced(configs.get_config("granite-8b"))
model = LM(cfg)
# (2, 2): the reduced config has 2 kv heads, so model axis must divide 2
mesh = make_local_mesh(data=2, model=2)
shape = configs.SHAPES["train_4k"]
rule = rule_for(cfg, shape, multi_pod=False)
tcfg = TrainConfig(opt=OptConfig(lr=1e-3), warmup_steps=1, total_steps=10)

params = materialize(model.param_recs(), jax.random.PRNGKey(0))
opt = adamw_init(params, tcfg.opt)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}

# single device reference
step0 = jax.jit(make_train_step(model, tcfg))
_, _, m0 = step0(params, opt, batch, jnp.int32(0))

with jax.set_mesh(mesh):
    step1 = jax.jit(make_train_step(model, tcfg, rule=rule))
    p = jax.device_put(params, shard(mesh, spec_tree(model.param_recs(), rule)))
    _, _, m1 = step1(p, opt, batch, jnp.int32(0))
l0, l1 = float(m0["loss"]), float(m1["loss"])
assert abs(l0 - l1) / l0 < 2e-2, (l0, l1)
print("sharded step OK", l0, l1)
"""
    assert "sharded step OK" in devices8(code)
