"""MoE: the capacity layer's scatter dispatch against a one-hot einsum
reference (GShard semantics), capacity dropping, the one-level routers,
and the held layer against the capacity layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import moe
from repro.models.common import is_rec, materialize, rms_norm
from repro.models.lm import LM
from repro.models.mlp import mlp_apply


def _moe_block(arch, key=0):
    cfg = configs.reduced(configs.get_config(arch))
    model = LM(cfg)
    params = materialize(model.param_recs(), jax.random.PRNGKey(key))
    # find the first MoE ffn block in the last stage; layer 0 of the stack
    for blk in params["stages"][-1]["blocks"]:
        if "router" in blk:
            return cfg, jax.tree.map(lambda a: a[0], blk)
    raise AssertionError("no MoE block found")


def _einsum_reference(p, x, cfg):
    """The capacity layer in the classic GShard form, one token group:
    one-hot dispatch and combine tensors contracted by einsum. Pairs past
    an expert's capacity are dropped, in token order."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xt = rms_norm(x, p["ln"]).reshape(t, d)
    idx, w = moe.route_topk(p, xt, m)
    onehot = jax.nn.one_hot(idx, m.n_experts, dtype=x.dtype)    # (t, k, e)
    khot = onehot.sum(1)
    weights = (onehot * w[..., None]).sum(1)                    # (t, e)
    capacity = max(min(8, t), int(m.capacity_factor * m.top_k * t
                                  / m.n_experts))
    pos = jnp.cumsum(khot, axis=0) - khot
    keep = (pos < capacity) & (khot > 0)
    disp = jax.nn.one_hot(jnp.where(keep, pos, capacity).astype(jnp.int32),
                          capacity, dtype=x.dtype)              # (t, e, c)
    xin = jnp.einsum("tec,td->ecd", disp, xt)
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"]))
         * jnp.einsum("ecd,edf->ecf", xin, p["w_up"]))
    eout = jnp.einsum("ecf,efd->ecd", h, p["w_out"])
    out = jnp.einsum("ecd,tec->td", eout, disp * weights[..., None])
    out = out.reshape(b, s, d)
    return out + mlp_apply(p["shared"], x, cfg) if m.n_shared else out


@pytest.mark.parametrize("arch", ["deepseek-v3-671b",
                                  "llama4-maverick-400b-a17b"])
def test_scatter_equals_einsum(arch):
    cfg, blk = _moe_block(arch)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    o_e = _einsum_reference(blk, x, cfg)
    o_s = moe.moe_apply(blk, x, cfg)
    np.testing.assert_allclose(np.asarray(o_e, np.float32),
                               np.asarray(o_s, np.float32),
                               rtol=2e-3, atol=2e-3)


def test_capacity_drops_consistently():
    """With a tiny capacity factor both forms drop the SAME tokens."""
    cfg, blk = _moe_block("deepseek-v3-671b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model),
                          jnp.float32)
    o_e = _einsum_reference(blk, x, cfg)
    o_s = moe.moe_apply(blk, x, cfg)
    np.testing.assert_allclose(np.asarray(o_e, np.float32),
                               np.asarray(o_s, np.float32),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("router", ["softmax", "sigmoid_bias"])
def test_route_topk_weights_and_bias(router):
    """softmax: the top-k logits, weighted by the softmax over all experts
    (not renormalised); sigmoid_bias (DeepSeek aux-loss-free): the bias
    shifts the choice only, and the chosen sigmoid scores, renormalised,
    weight it. A large bias on expert 0 puts it in every token's top-k
    under sigmoid_bias and changes nothing under softmax."""
    cfg, blk = _moe_block("deepseek-v3-671b")
    m = dataclasses.replace(cfg.moe, router=router)
    xt = jax.random.normal(jax.random.PRNGKey(3), (8, cfg.d_model),
                           jnp.float32)
    idx, w = moe.route_topk(blk, xt, m)
    assert idx.shape == w.shape == (8, m.top_k) and idx.dtype == jnp.int32
    assert all(len(set(row)) == m.top_k for row in np.asarray(idx).tolist())
    logits = np.asarray(xt, np.float64) @ np.asarray(blk["router"],
                                                     np.float64)
    np.testing.assert_array_equal(
        np.sort(np.asarray(idx), 1),
        np.sort(np.argsort(-logits, 1)[:, :m.top_k], 1))
    if router == "softmax":
        p = np.exp(logits - logits.max(1, keepdims=True))
        want = np.take_along_axis(p / p.sum(1, keepdims=True),
                                  np.asarray(idx), 1)
    else:
        s = np.take_along_axis(1 / (1 + np.exp(-logits)), np.asarray(idx), 1)
        want = s / s.sum(1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-5)
    blk2 = dict(blk, router_bias=blk["router_bias"].at[0].set(100.0))
    idx2, w2 = moe.route_topk(blk2, xt, m)
    if router == "softmax":
        np.testing.assert_array_equal(np.asarray(idx2), np.asarray(idx))
        np.testing.assert_array_equal(np.asarray(w2), np.asarray(w))
    else:
        assert bool((idx2 == 0).any(1).all())
        np.testing.assert_allclose(np.asarray(w2.sum(1)), 1.0, rtol=1e-5)


@pytest.mark.parametrize("router", ["softmax", "sigmoid_bias"])
def test_held_layer_equals_capacity_layer_without_drops(router):
    """One rank holding every expert (ep_size=1) computes what the
    capacity layer computes when its capacity drops nothing: the same
    router, experts and shared expert. Float32 throughout: 1e-5 of the
    output's scale covers the order of the sums."""
    cfg, blk = _moe_block("deepseek-v3-671b")
    blk = jax.tree.map(lambda a: a.astype(jnp.float32), blk)
    m = dataclasses.replace(cfg.moe, router=router, capacity_factor=8.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, cfg.d_model),
                          jnp.float32)
    want = moe.moe_apply(blk, x, dataclasses.replace(cfg, moe=m))
    got, stats = moe.held_apply(blk, x, dataclasses.replace(
        cfg, moe=dataclasses.replace(m, ep_size=1)))
    assert int(stats[0]) == 2 * 16 * m.top_k
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * scale)


def test_group_local_dispatch_matches_global():
    """G groups with ample capacity == G=1 (no drops => same math)."""
    cfg, blk = _moe_block("deepseek-v3-671b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 16, cfg.d_model),
                          jnp.float32)
    o1 = moe.moe_apply(blk, x, cfg, rule=None)                 # G = 1
    o4 = moe.moe_apply(blk, x, cfg, rule={"moe_groups": 4})
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o4, np.float32),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("held", [False, True], ids=["capacity", "held"])
def test_block_owns_the_held_or_capacity_choice(held):
    """The block answers for its layer kind: a capacity block has no cache
    and no unscanned weights, passes its cache through and leaves the
    model no counter; a held block keeps the expert stacks out of the
    scan, adds its counts to its cache, and the model's counter sums
    them."""
    cfg, blk = _moe_block("deepseek-v3-671b")
    blk = jax.tree.map(lambda a: a.astype(jnp.float32), blk)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=8.0, ep_size=int(held)))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, cfg.d_model),
                          jnp.float32)
    recs = moe.moe_cache_recs(cfg)
    cache = {k: jnp.full(r.shape, 3, r.dtype) for k, r in recs.items()}
    dx, new = moe.block_apply(blk, x, cfg, cache=cache)
    if not held:
        assert recs == {} and moe.scan_stacked(cfg) == ()
        assert LM(cfg).held_counts is None and new is cache
        np.testing.assert_array_equal(np.asarray(dx),
                                      np.asarray(moe.moe_apply(blk, x, cfg)))
        return
    assert moe.scan_stacked(cfg) == moe.EXPERT_WEIGHTS
    out, stats = moe.held_apply(blk, x, cfg)
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(new["moe_stats"]),
                                  3 + np.asarray(stats))
    assert int(stats[0]) == 2 * 8 * cfg.moe.top_k
    filled = jax.tree.map(lambda r: jnp.ones(r.shape, r.dtype or jnp.float32),
                          LM(cfg).cache_recs(1, 8), is_leaf=is_rec)
    moe_layers = cfg.n_layers - cfg.n_dense_layers
    np.testing.assert_array_equal(
        np.asarray(LM(cfg).held_counts(filled)), [moe_layers, moe_layers])
