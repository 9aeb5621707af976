"""Serving engine: generate correctness (greedy decode == argmax of the
full forward at each step), batched request driver, decode shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.common import materialize
from repro.models.lm import LM
from repro.serve import Engine
from repro.serve.engine import BatchedServer, Request


@pytest.fixture(scope="module")
def setup():
    cfg = configs.reduced(configs.get_config("granite-8b"))
    model = LM(cfg)
    params = materialize(model.param_recs(), jax.random.PRNGKey(0))
    return cfg, model, params


def test_greedy_matches_forward(setup):
    cfg, model, params = setup
    eng = Engine(model, params, max_len=64)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    gen = eng.generate(toks, 5)
    # teacher-force the full forward over prompt+generated; argmax must
    # reproduce each generated token
    seq = jnp.concatenate([toks, gen], axis=1)
    logits = model.forward(params, {"tokens": seq})
    for i in range(5):
        pred = jnp.argmax(logits[:, 8 + i - 1], axis=-1)
        np.testing.assert_array_equal(np.asarray(pred),
                                      np.asarray(gen[:, i]))


def test_generated_tokens_in_vocab(setup):
    cfg, model, params = setup
    eng = Engine(model, params, max_len=64)
    toks = jnp.zeros((2, 4), jnp.int32)
    gen = eng.generate(toks, 8, temperature=1.0)
    assert int(gen.max()) < cfg.vocab       # vocab padding never sampled
    assert gen.shape == (2, 8)


def test_batched_server(setup):
    cfg, model, params = setup
    eng = Engine(model, params, max_len=64)
    srv = BatchedServer(eng, batch_size=3)
    for i in range(7):
        srv.submit(Request(uid=i, tokens=[1 + i, 2, 3], max_new=4))
    done = srv.drain()
    assert len(done) == 7
    assert all(len(r.result) == 4 for r in done)
    assert srv._served == [3, 3, 1]         # bucketed batching


def test_temperature_sampling_reproducible(setup):
    cfg, model, params = setup
    eng = Engine(model, params, max_len=32)
    toks = jnp.zeros((1, 4), jnp.int32)
    g1 = eng.generate(toks, 6, temperature=0.8, key=jax.random.PRNGKey(7))
    g2 = eng.generate(toks, 6, temperature=0.8, key=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))


def test_launcher_reduced(capsys, monkeypatch):
    from repro.launch import compile_cache
    from repro.launch.serve import main
    # keep the suite's compiles out of the checkout's cache directory
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    main(["--reduced", "--requests", "4", "--prompt-len", "8", "16",
          "--max-new", "4", "--max-len", "32", "--batch-size", "2"])
    out = capsys.readouterr().out
    assert "4 requests, 16 tokens" in out and "batches=[2, 2]" in out


def test_served_logits_match_forward(setup):
    """The logits each token was picked from (prefill + cached decode,
    replayed in the served batches) pick exactly the served tokens, and
    agree with one full forward over the same tokens."""
    from repro.launch import serve as serve_mod
    cfg, model, params = setup
    engine = Engine(model, params, max_len=64)
    reqs = serve_mod.make_requests(cfg.vocab, 6, (8, 24), 6, seed=3)
    res = serve_mod.serve(engine, reqs, batch_size=4)
    assert res.tokens == 36 and res.batches == [4, 2]
    logits = serve_mod.replay_logits(engine, res.done, 4)
    for r in res.done:
        assert logits[r.uid].shape == (6, cfg.vocab)
        assert np.argmax(logits[r.uid], -1).tolist() == r.result
    req = serve_mod.unpadded(res, 4)
    assert len(req.tokens) == max(len(r.tokens) for r in reqs[:4])
    chk = serve_mod.forward_logit_error(model, params, req, logits[req.uid])
    assert chk["positions"] == 6
    assert chk["max_abs_err"] <= 2 ** -4 * chk["max_abs_logit"]


def test_attention_init_scores_order_one():
    """q and k projections scale by the model width, so attention scores
    of a fresh model have std O(1), not O(head_dim)."""
    from repro.models import attention
    cfg = configs.get_config("phi3-mini-3.8b")
    p = materialize(attention.gqa_recs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    q = jnp.einsum("sd,dnh->snh", x, p["wq"].astype(jnp.float32))
    k = jnp.einsum("sd,dnh->snh", x, p["wk"].astype(jnp.float32))
    s = jnp.einsum("qnh,knh->nqk", q, k) / cfg.head_dim ** 0.5
    assert 0.5 < float(jnp.std(s)) < 2.0


def test_sharded_engine_matches_one_device(devices8):
    """The 2x2 (data, model) serving mesh: params and caches placed by the
    decode rule give the one-device logits up to bf16 rounding, at every
    position each placement served (its tokens replayed through the
    other)."""
    code = """
import numpy as np
from repro import configs
from repro.launch import serve as S
from repro.launch.cell import rule_for
from repro.launch.mesh import make_local_mesh
from repro.serve import Engine

cfg, model, params = S.build("phi3-mini-3.8b", reduced=True)
one = Engine(model, params, max_len=32)
mesh = make_local_mesh(data=2, model=2)
shd = Engine(model, params, max_len=32, mesh=mesh,
             rule=rule_for(cfg, configs.SHAPES["decode_32k"], False))
runs = [S.serve(e, S.make_requests(cfg.vocab, 6, (8, 24), 5, seed=0),
                batch_size=4).done for e in (one, shd)]
err, top = 0.0, 0.0
for done in runs:
    a, b = (S.replay_logits(e, done, 4) for e in (one, shd))
    assert sorted(a) == sorted(b) == list(range(6))
    for u in a:
        assert a[u].shape == (5, cfg.vocab)
        err = max(err, float(np.abs(a[u] - b[u]).max()))
        top = max(top, float(np.abs(a[u]).max()))
assert err <= 2 ** -4 * top, (err, top)
print("sharded serve OK", err, top)
"""
    assert "sharded serve OK" in devices8(code)


def test_compile_cache_placement(monkeypatch):
    from repro.launch import compile_cache
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None   # JAX's own
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.DEFAULT_DIR)
        assert got.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
