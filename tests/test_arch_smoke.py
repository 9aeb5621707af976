"""Per-architecture smoke tests (required deliverable f): a REDUCED config
of the same family runs one forward + one train step on CPU, asserting
output shapes and finiteness; prefill+decode must agree with the full
forward (the KV-cache/ring-buffer/SSM-state correctness proof)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.common import materialize
from repro.models.lm import LM
from repro.optim import OptConfig, adamw_init
from repro.serve.engine import make_caches
from repro.train import TrainConfig, make_train_step


def _batch(cfg, b, s, key):
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.n_img_tokens:
        batch["patch_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 1), (b, cfg.n_img_tokens, cfg.d_model),
            jnp.bfloat16)
    if cfg.encdec:
        batch["frames"] = jax.random.normal(
            jax.random.fold_in(key, 2), (b, cfg.enc_len, cfg.d_model),
            jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_forward_and_train_step(arch):
    cfg = configs.reduced(configs.get_config(arch))
    model = LM(cfg)
    params = materialize(model.param_recs(), jax.random.PRNGKey(0))
    b, s = 2, 32
    batch = _batch(cfg, b, s, jax.random.PRNGKey(1))

    logits = jax.jit(lambda p, bt: model.forward(p, bt))(params, batch)
    assert logits.shape == (b, s, model.padded_vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    tcfg = TrainConfig(opt=OptConfig(lr=1e-3), warmup_steps=1, total_steps=10)
    step = jax.jit(make_train_step(model, tcfg))
    opt = adamw_init(params, tcfg.opt)
    p2, o2, metrics = step(params, opt, batch, jnp.int32(0))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually moved
    moved = jax.tree.reduce(
        lambda acc, ab: acc or bool(jnp.any(ab[0] != ab[1])),
        jax.tree.map(lambda a, b_: (a, b_), params, p2), False)
    assert moved


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_prefill_decode_matches_forward(arch):
    cfg = configs.reduced(configs.get_config(arch))
    model = LM(cfg)
    params = materialize(model.param_recs(), jax.random.PRNGKey(0))
    b, s = 2, 16
    batch = _batch(cfg, b, s, jax.random.PRNGKey(1))
    toks = batch["tokens"]

    full = model.forward(params, batch)
    caches = make_caches(model, b, 64)
    _, caches = model.prefill(params, dict(batch, tokens=toks[:, :s - 2]),
                              caches)
    lg = None
    for i in (s - 2, s - 1):    # two decode steps
        lg, caches = model.decode_step(params, caches, toks[:, i:i + 1],
                                       jnp.int32(i))
    err = jnp.max(jnp.abs(lg[:, 0].astype(jnp.float32)
                          - full[:, -1].astype(jnp.float32)))
    # MLA decode uses the fp32 absorbed form (DeepSeek inference math); it
    # is *more* precise than the bf16 expanded forward, so allow a larger
    # numeric gap but require identical argmax
    tol = 0.25 if cfg.mla else 0.05
    assert float(err) < tol, f"{arch}: decode/forward logit gap {err}"
    agree = jnp.all(jnp.argmax(lg[:, 0], -1) == jnp.argmax(full[:, -1], -1))
    assert bool(agree), f"{arch}: decode/forward argmax mismatch"


def test_local_window_ring_buffer():
    """llama4 iRoPE: decoding far past the window must agree with the full
    forward (which uses chunked-local masking)."""
    cfg = configs.reduced(configs.get_config("llama4-maverick-400b-a17b"))
    model = LM(cfg)
    params = materialize(model.param_recs(), jax.random.PRNGKey(0))
    b, s = 1, 3 * cfg.local_window // 2   # 1.5 windows
    batch = _batch(cfg, b, s, jax.random.PRNGKey(1))
    toks = batch["tokens"]
    full = model.forward(params, batch)
    caches = make_caches(model, b, 2 * s)
    _, caches = model.prefill(params, dict(batch, tokens=toks[:, :s - 1]),
                              caches)
    lg, _ = model.decode_step(params, caches, toks[:, s - 1:], jnp.int32(s - 1))
    err = jnp.max(jnp.abs(lg[:, 0].astype(jnp.float32)
                          - full[:, -1].astype(jnp.float32)))
    assert float(err) < 0.05


# Cache entries with one row per position, sequence axis last; the rest
# (ring positions, SSM state, encoder K/V) is small per-layer state.
_SEQ_ENTRIES = ("k", "v", "latent", "k_rope")


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint8)


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_donated_decode_rewrites_only_its_row(arch):
    """A jitted decode step that donates its caches writes only the row it
    decodes: every other row of each sequence cache, and every other slot
    of a ring buffer's positions, comes back bit-identical; encoder K/V
    come back whole. The caches start as noise, so a copy that moved or
    zeroed a row would show."""
    cfg = configs.reduced(configs.get_config(arch))
    model = LM(cfg)
    params = materialize(model.param_recs(), jax.random.PRNGKey(0))
    b, max_len, pos = 2, 96, 80      # local layers: ring of 64, slot 16
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        make_caches(model, b, max_len))
    noise = [jnp.arange(x.size, dtype=x.dtype).reshape(x.shape)
             if x.dtype == jnp.int32 else
             jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(2), i),
                               x.shape).astype(x.dtype)
             for i, (_, x) in enumerate(leaves)]
    before = [np.asarray(x) for x in noise]
    step = jax.jit(model.decode_step, donate_argnums=1)
    _, out = step(params, jax.tree_util.tree_unflatten(tree, noise),
                  jnp.ones((b, 1), jnp.int32), jnp.int32(pos))
    after, out_tree = jax.tree_util.tree_flatten(out)
    assert out_tree == tree
    for (path, _), old, new in zip(leaves, before, after):
        new = np.asarray(new)
        assert new.shape == old.shape and new.dtype == old.dtype, path
        name = path[-1].key
        if name in _SEQ_ENTRIES or name == "pos":
            row = pos % old.shape[-1]
            keep = np.arange(old.shape[-1]) != row
            assert (_bits(new[..., keep]) == _bits(old[..., keep])).all(), \
                f"{arch}: {jax.tree_util.keystr(path)} rows moved"
            assert (_bits(new[..., row]) != _bits(old[..., row])).any(), \
                f"{arch}: {jax.tree_util.keystr(path)} row {row} unwritten"
            if name == "pos":
                assert (new[..., row] == pos).all()
        elif name in ("ck", "cv"):
            assert (_bits(new) == _bits(old)).all(), path


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_decode_to_last_row_matches_forward(arch):
    """Prefill 112 tokens (past a local layer's 64-token window, so its
    ring keeps the tail), then decode one token at a time to the cache's
    last row (the ring wraps and crosses a chunk boundary): every step's
    logits match the full forward at that position."""
    cfg = configs.reduced(configs.get_config(arch))
    model = LM(cfg)
    params = materialize(model.param_recs(), jax.random.PRNGKey(0))
    b, s0, max_len = 2, 112, 160   # s0: a whole number of SSD chunks
    batch = _batch(cfg, b, max_len, jax.random.PRNGKey(1))
    toks = batch["tokens"]
    full = jax.jit(model.forward)(params, batch).astype(jnp.float32)
    prefill = jax.jit(model.prefill, donate_argnums=2)
    decode = jax.jit(model.decode_step, donate_argnums=1)
    _, caches = prefill(params, dict(batch, tokens=toks[:, :s0]),
                        make_caches(model, b, max_len))
    errs, agree = [], []
    for i in range(s0, max_len):
        lg, caches = decode(params, caches, toks[:, i:i + 1], jnp.int32(i))
        lg = lg[:, 0].astype(jnp.float32)
        errs.append(float(jnp.max(jnp.abs(lg - full[:, i]))))
        agree.append(bool(jnp.all(jnp.argmax(lg, -1)
                                  == jnp.argmax(full[:, i], -1))))
    # 48 steps drift more than the two of test_prefill_decode_matches_forward
    # (zamba2's recurrent SSM against the chunked scan): allow four bf16
    # rounding steps at the largest logit, half what the serving replay
    # check allows (2**-4 of it)
    tol = 2 ** -5 * float(jnp.max(jnp.abs(full[:, s0:])))
    assert max(errs) < tol, f"{arch}: decode/forward logit gap {max(errs)}"
    assert agree[-1], f"{arch}: argmax mismatch at the last row"
