"""One decode rank of DeepSeek-V3 at a small size on the CPU, against the
benchmark's plain float32 reference (``bench/reference/mla_moe.py``):
serving (prefill, then decode through the latent cache) against the
reference's full forward; the expert share against the uncut layer; no
dropped token; the group-limited router against brute force; YaRN at the
published rope width; and the held-expert counters the server reads."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serve_mla_moe as driver
from bench.reference import mla_moe as reference
from repro import configs
from repro.launch import serve as launch
from repro.models import attention, moe
from repro.models.common import YarnConfig, rope, yarn_inv_freq
from repro.models.lm import LM
from repro.serve import Engine
from repro.serve.engine import make_caches

#: DeepSeek-V3's config keys at a small width: 16 routed experts in 4
#: groups (2 kept), 4 per token, 4 ranks of 4 experts, 1 dense + 2 MoE
#: layers, YaRN over 8 rope dims, untied head; float32 activations
SMALL = {"name": "ds-small", "hidden_size": 64, "intermediate_size": 128,
         "moe_intermediate_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "n_routed_experts": 16, "n_shared_experts": 1, "n_group": 4,
         "topk_group": 2, "num_experts_per_tok": 4, "ep_size": 4,
         "routed_scaling_factor": 2.5, "norm_topk_prob": True,
         "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "num_nextn_predict_layers": 0, "vocab_size": 200,
         "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
         "tie_word_embeddings": False, "torch_dtype": "float32",
         "rope_scaling": {"type": "yarn", "factor": 40,
                          "original_max_position_embeddings": 4096,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                          "mscale_all_dim": 1}}


def _model(cfg=SMALL, seed=0):
    model = LM(driver.arch_config(cfg))
    return model, driver.make_weights(model, cfg, jax.random.PRNGKey(seed))


def test_prefill_then_decode_matches_reference_forward():
    """Prefill of 10 tokens, then 6 decode steps through the latent cache
    (absorbed MLA, the grouped-matmul kernel in interpret mode), against
    the reference's full float32 forward (expanded MLA, dense experts) on
    the same 16 tokens. Both compute in float32 with the same weights, so
    they differ by rounding in another order of the same sums: 1e-4 of
    the logits' scale (3.1 here) is 40x the gap measured on the CPU
    (7.9e-6), and far below what one routing flip moves."""
    model, params = _model()
    b, s0, n = 2, 10, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s0 + n), 0,
                              SMALL["vocab_size"])
    caches = make_caches(model, b, 32)
    logits, caches = model.prefill(params, {"tokens": toks[:, :s0]}, caches)
    got = [logits[:, -1]]
    for i in range(n - 1):
        logits, caches = model.decode_step(params, caches,
                                           toks[:, s0 + i:s0 + i + 1],
                                           jnp.int32(s0 + i))
        got.append(logits[:, -1])
    got = np.stack([np.asarray(g)[:, :SMALL["vocab_size"]] for g in got], 1)
    want = np.asarray(reference.logits(SMALL, reference.weights_of(params),
                                       toks, s0 - 1, n))
    scale = np.abs(want).max()
    assert 0.1 < scale < 100
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * scale)


def _moe_layer(cfg, seed=0):
    """The first MoE layer's weights of a model, as float32."""
    model, params = _model(cfg, seed)
    return jax.tree.map(lambda a: a[0].astype(jnp.float32),
                        params["stages"][1]["blocks"][1])


def _uncut(ep_size=1):
    return dict(SMALL, ep_size=ep_size)


def test_shares_sum_to_the_uncut_layer():
    """Over the 4 ranks of a 16-expert layer (4 experts each), what each
    rank's routed experts add, summed, with the shared expert (which
    every rank computes alike) counted once, is the uncut layer of the
    reference, holding all 16. Float32 throughout: 1e-5 of the output's
    scale covers the summation order."""
    f = _moe_layer(_uncut())
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64), jnp.float32)
    cfg = driver.arch_config(SMALL)
    shared = None
    total = 0.0
    for rank in range(4):
        c = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, ep_rank=rank))
        p = dict(f, **{k: f[k][4 * rank:4 * rank + 4]
                       for k in ("w_gate", "w_up", "w_out")})
        out, _ = moe.held_apply(p, x, c)
        shared = moe.mlp_apply(f["shared"], x, c)
        total = total + out
    total = total - 3 * shared
    with jax.default_matmul_precision("highest"):
        want = reference.moe_ffn(_uncut(), f, x)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=0, atol=1e-5 * scale)


def test_every_token_on_one_held_expert_drops_nothing(monkeypatch):
    """A router bias that sends every one of 256 tokens to held expert 0
    (besides three more each): all 256 pairs of expert 0 are computed,
    with the rest of the held pairs, and the output equals the
    reference's, which drops nothing by construction."""
    f = _moe_layer(SMALL)
    f["router_bias"] = f["router_bias"].at[0].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 64, 64), jnp.float32)
    cfg = driver.arch_config(SMALL)
    out, stats = moe.held_apply(f, x, cfg)
    with jax.default_matmul_precision("highest"):
        want = reference.moe_ffn(SMALL, f, x)
        cw = reference.route(SMALL, reference._rms(x, f["ln"], 1e-6),
                             f["router"], f["router_bias"])
    assert bool((cw[..., 0] > 0).all())
    held_pairs = int((cw[..., :4] > 0).sum())
    assert int(stats[0]) == held_pairs >= 256
    assert int(stats[1]) == int((cw[..., :4] > 0).any((0, 1)).sum())
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0,
                               atol=1e-5 * scale)
    # the same, 64 tokens at a time: every chunk holds all its pairs
    monkeypatch.setattr(moe, "HELD_CHUNK", 64)
    chunked, chunk_stats = moe.held_apply(f, x, cfg)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(want),
                               rtol=0, atol=1e-5 * scale)
    np.testing.assert_array_equal(np.asarray(chunk_stats),
                                  np.asarray(stats))


def _noaux_tc_numpy(logits, bias, n_group, topk_group, top_k, scale):
    """DeepSeek-V3's router, one token at a time, by brute force."""
    idx_out, w_out = [], []
    for row in np.asarray(logits, np.float64):
        s = 1 / (1 + np.exp(-row))
        sel = s + np.asarray(bias, np.float64)
        per = len(row) // n_group
        g_score = [sum(sorted(sel[g * per:(g + 1) * per])[-2:])
                   for g in range(n_group)]
        groups = sorted(range(n_group), key=lambda g: -g_score[g])
        allowed = [e for g in groups[:topk_group]
                   for e in range(g * per, (g + 1) * per)]
        chosen = sorted(allowed, key=lambda e: -sel[e])[:top_k]
        w = np.array([s[e] for e in chosen])
        idx_out.append(chosen)
        w_out.append(w / w.sum() * scale)
    return np.array(idx_out), np.array(w_out)


def test_grouped_router_equals_brute_force():
    f = _moe_layer(SMALL)
    f["router_bias"] = jax.random.normal(jax.random.PRNGKey(4), (16,)) * 0.1
    xt = jax.random.normal(jax.random.PRNGKey(5), (40, 64), jnp.float32)
    m = driver.arch_config(SMALL).moe
    idx, w = moe.route_topk(f, xt, m)
    logits = np.asarray(xt, np.float64) @ np.asarray(f["router"],
                                                     np.float64)
    want_idx, want_w = _noaux_tc_numpy(logits, f["router_bias"], 4, 2, 4,
                                       2.5)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    # the reference's dense form routes the same
    with jax.default_matmul_precision("highest"):
        cw = np.asarray(reference.route(SMALL, xt[None], f["router"],
                                        f["router_bias"]))[0]
    dense = np.zeros_like(cw)
    np.put_along_axis(dense, want_idx, want_w, axis=1)
    np.testing.assert_allclose(cw, dense, rtol=1e-5, atol=1e-7)


def test_yarn_at_the_published_rope_width():
    """DeepSeek-V3's YaRN (factor 40, original 4096, beta 32/1) over 64
    rope dims: the ramp runs from dim 10 to 23, cos and sin are unscaled
    (mscale = mscale_all_dim), and the softmax scale is 192^-1/2 times
    (0.1 ln 40 + 1)^2 = 1.87385."""
    y = YarnConfig(factor=40.0, original_max_position=4096)
    got = np.asarray(yarn_inv_freq(64, 10000.0, y))
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    m = 1 - np.clip((i - 10) / (23 - 10), 0, 1)
    np.testing.assert_allclose(got, extra * m + extra / 40 * (1 - m),
                               rtol=1e-6)
    q = jax.random.normal(jax.random.PRNGKey(6), (1, 5, 2, 64))
    turned = rope(q, jnp.arange(5)[None] * 1000, yarn=y)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(turned), axis=-1),
                               np.linalg.norm(np.asarray(q), axis=-1),
                               rtol=1e-5)
    with pytest.raises(ValueError, match="mscale"):
        driver.arch_config(dict(SMALL, rope_scaling=dict(
            SMALL["rope_scaling"], mscale=0.707)))
    cfg = driver.arch_config(dict(SMALL, qk_nope_head_dim=128,
                                  qk_rope_head_dim=64))
    assert (0.1 * math.log(40) + 1) ** 2 == pytest.approx(1.87385, abs=1e-5)
    assert attention.mla_scale(cfg) == pytest.approx(
        192 ** -0.5 * 1.87385, rel=1e-5)
    ref_freq, ref_scale = reference.yarn(
        dict(SMALL, qk_nope_head_dim=128, qk_rope_head_dim=64))
    np.testing.assert_allclose(np.asarray(ref_freq), got, rtol=1e-6)
    assert ref_scale == pytest.approx(attention.mla_scale(cfg), rel=1e-6)


def test_untied_head_is_its_own_weight():
    model, params = _model()
    assert params["head"].shape == params["embed"].shape
    toks = jnp.zeros((1, 4), jnp.int32)
    zero = dict(params, head=jnp.zeros_like(params["head"]))
    logits = model.forward(zero, {"tokens": toks})
    assert float(jnp.abs(logits[..., :SMALL["vocab_size"]]).max()) == 0.0


def test_server_counts_held_expert_work():
    """``BatchedServer`` reads the held experts' counters once a batch:
    pairs and active experts by call kind, for a held-share model, and
    nothing for a model without one."""
    model, params = _model()
    cfg = model.cfg
    engine = Engine(model, params, max_len=32)
    reqs = launch.make_requests(SMALL["vocab_size"], 4, (8, 8), 5)
    res = launch.serve(engine, reqs, batch_size=4)
    moe_layers = cfg.n_layers - cfg.n_dense_layers
    top_k, held = cfg.moe.top_k, cfg.moe.n_held
    # prefill: 4 rows x 8 tokens x top_k pairs a layer at most; decode:
    # 4 steps of 4 rows
    assert 0 < res.counts.moe_pairs["prefill"] <= moe_layers * 32 * top_k
    assert 0 < res.counts.moe_pairs["decode"] <= moe_layers * 4 * 4 * top_k
    assert res.counts.moe_active["prefill"] <= moe_layers * held
    assert 0 < res.counts.moe_active["decode"] <= moe_layers * 4 * held
    dense = launch.build("phi3-mini-3.8b", reduced=True)
    res = launch.serve(Engine(dense[1], dense[2], max_len=32),
                       launch.make_requests(dense[0].vocab, 2, (8, 8), 3),
                       batch_size=2)
    assert res.counts.moe_pairs == {} and res.counts.moe_active == {}


def test_reduced_moe_configs_keep_the_capacity_path():
    """Configs without an expert share keep today's capacity path and its
    cache: no counter, all experts held."""
    for arch in ("deepseek-v3-671b", "llama4-maverick-400b-a17b"):
        cfg = configs.reduced(configs.get_config(arch))
        assert cfg.moe.ep_size == 0 and cfg.moe.n_held == cfg.moe.n_experts
        paths = jax.tree_util.tree_flatten_with_path(
            LM(cfg).cache_recs(1, 8))[0]
        assert not any("moe_stats" in jax.tree_util.keystr(p)
                       for p, _ in paths)
