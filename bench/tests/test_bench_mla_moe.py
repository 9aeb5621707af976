"""The MLA + routed-expert serving driver end to end at a tiny size on the
CPU (the grouped-matmul kernel in interpret mode), through the harness
with its look for a chip skipped; its control, which must fail the limit
the program passes; the work counts against hand counts; and the
``moe_gmm_roofline`` reader on hand-made trace events.

The tiny cell is added as ``bench_tiny_root.add_tiny_cells`` adds its
cells: by new files and new entries only."""
import json
import types

import pytest

from bench_tiny_root import PEAKS, REPO, copy_checkout, run_cell
from bench import control, harness, trace
from bench.counts import mla_moe as counts

CELL = "mla-tiny.tiny-decode"
#: DeepSeek-V3's equations at a tiny width: 16 routed experts in 4 groups
#: (2 kept), 4 per token, held over 4 ranks (4 here), 1 dense + 2 MoE
#: layers, YaRN over 8 rope dims
TINY_MLA = {"name": "mla-tiny", "hidden_size": 64, "intermediate_size": 128,
            "moe_intermediate_size": 32, "num_attention_heads": 4,
            "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "n_routed_experts": 16, "n_group": 4,
            "topk_group": 2, "num_experts_per_tok": 4, "ep_size": 4,
            "num_hidden_layers": 3, "first_k_dense_replace": 1,
            "vocab_size": 200, "torch_dtype": "float32"}
TINY_DECODE = {"driver": "serve_mla_moe", "prompt_len": 8, "max_new": 8,
               "clients": 2, "batch": 2, "max_len": 16, "check_requests": 2,
               "ref_batch": 1, "trace_seconds": 0.3}
#: set from CPU readings at these sizes (float32, like the reference), over
#: 12 seeds with 0.2 s windows and 8 with 2 s windows: the program's gaps
#: 0, the float8 control's widest 0.259 to 1.346 and mean 0.047 to 0.274
TINY_LIMIT = {"logit_gap": 0.1, "mean_gap": 0.02}


def add_tiny_mla_cell(root):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    b = root / "bench"
    cfg = json.loads((b / "configs" / "deepseek-v3-ep32.json").read_text())
    cfg.update(TINY_MLA)
    (b / "configs" / "mla-tiny.json").write_text(json.dumps(cfg))
    (b / "traffic" / "tiny-decode.json").write_text(json.dumps(TINY_DECODE))
    (b / "limits" / f"{CELL}.json").write_text(json.dumps(TINY_LIMIT))
    bench["configs"].append({"name": "mla-tiny", "source": "test",
                             "file": "bench/configs/mla-tiny.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": CELL, "config": "mla-tiny",
                               "traffic": "tiny-decode", "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "deepseek-v3-ep32.dp-decode" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = copy_checkout(tmp_path_factory.mktemp("co"))
    add_tiny_mla_cell(r)
    return r


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)


def test_tiny_mla_cell_end_to_end(root):
    out = run_cell(root, CELL)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "request_p95_s",
                                   "setup_s"}


def test_tiny_mla_counts_come_from_the_server(root):
    c = harness.resolve(root, CELL)
    drv = c.driver.Driver(c.cfg, c.traffic, 5, harness.seed_key(5))
    drv.setup()
    drv.window(0.2)
    got = drv.counts()
    rounds = drv.rounds
    # every step routes 2 rows x 4 experts in each of 2 MoE layers; some
    # of those pairs land on the 4 experts held here
    for kind, steps in (("prefill", 8), ("decode", 7)):
        assert 0 < got["moe_pairs"][kind] <= rounds * 2 * 4 * 2 * steps
        assert 0 < got["moe_active"][kind] <= rounds * 2 * 4 * (
            1 if kind == "prefill" else 7)
    assert got["moe_gmm_needed_bytes"] > 0
    assert got["decode_calls"] == rounds * 7


def test_tiny_mla_control_fails_where_the_program_passes(root):
    limit = harness.resolve(root, CELL).limits["logit_gap"]
    for rec in control.readings(root, CELL, [11, 12], 0.2,
                                require_tpu=False):
        assert rec["program"]["logit_gap"] <= limit
        assert rec["program"]["mean_gap"] <= TINY_LIMIT["mean_gap"]
        assert rec["control"]["logit_gap"] > limit
        assert rec["control"]["mean_gap"] > TINY_LIMIT["mean_gap"]


def test_parent_program_is_refused_fast(tmp_path):
    """A checkout whose program has no held-expert layer (as before this
    configuration) refuses the cell before it touches a device."""
    root = copy_checkout(tmp_path / "co", with_src=False)
    (root / "src" / "repro").mkdir(parents=True)
    with pytest.raises(harness.Refused, match="held-expert"):
        harness.resolve(root, "deepseek-v3-ep32.dp-decode")


# ----------------------------------------------------------------------
# counts by hand
# ----------------------------------------------------------------------
# d=8, 2 heads; q rank 4, kv rank 2, nope 2, rope 2, v 2; dense FFN 16,
# experts 4 wide, 8 of them, 2 per token, 4 ranks (2 held), 1 shared;
# 1 dense + 2 MoE layers; vocabulary 10
HAND = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
        "kv_lora_rank": 2, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
        "v_head_dim": 2, "intermediate_size": 16,
        "moe_intermediate_size": 4, "n_routed_experts": 8,
        "num_experts_per_tok": 2, "ep_size": 4, "n_shared_experts": 1,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "vocab_size": 10}


def test_mla_params_by_hand():
    # wq_a 8x4 + wq_b 4x2x4 + wkv_a 8x4 + wk_b 2x2x2 + wv_b 2x2x2 + wo 2x2x8
    assert counts.mla_params(HAND) == 32 + 32 + 32 + 8 + 8 + 32
    assert counts.expert_params(HAND) == 3 * 8 * 4


def test_token_params_and_weight_bytes_by_hand():
    # 3 MLA blocks 144 each; dense FFN 3x8x16; 2 MoE layers of a shared
    # expert 96 and a router 8x8
    assert counts.token_params(HAND) == 3 * 144 + 384 + 2 * (96 + 64)
    # norms: 3 x (2 x 8 + 4 + 2) + 2 shared-expert norms 8 + final 8 = 90;
    # router 2 x (64 + 8) x 4 B; head 10 x 8; bf16 2 B
    matrices = 3 * 144 + 384 + 2 * 96
    assert counts.weight_bytes(HAND, 2) == \
        2 * (matrices + 90 + 80) + 2 * 72 * 4


def test_decode_step_by_hand():
    # batch 2 writing position 3: per row 2 x token params + absorbed
    # attention over 4 rows 2 x 3 layers x 2 heads x (2 x 2 + 2) x 4 + head
    # 2 x 10 x 8; bytes: weights + 2 rows x (3 + 1) positions x 3 layers x
    # (2 + 2) x 2 B
    got = counts.decode_step(HAND, 2, 3, 2)
    per_row = 2 * counts.token_params(HAND) + 2 * 3 * 2 * 6 * 4 + 160
    assert got["flops"] == 2 * per_row
    assert got["bytes"] == counts.weight_bytes(HAND, 2) + 2 * 4 * 3 * 4 * 2


@pytest.mark.parametrize("pairs,active,flops,nbytes", [
    # no pair routed here: nothing to read, nothing to compute
    (0, 0, 0, 0),
    # 5 pairs over 3 active (layer, expert) sets: 6 d f flops a pair;
    # 3 experts' 96 weights and each pair's 3 x (8 + 4) row elements, 2 B
    (5, 3, 5 * 192, 2 * (3 * 96 + 5 * 36)),
])
def test_routed_by_hand(pairs, active, flops, nbytes):
    assert counts.routed(HAND, pairs, active, 2) == {"flops": flops,
                                                     "bytes": nbytes}


def test_request_flops_by_hand():
    # prompt 3, 2 new: 4 tokens pass the layers; prefill attention over
    # 1+2+3 keys: 2 x 3 layers x 2 heads x (2+2+2) x 6; one decode query
    # over 4 latent rows: 2 x 3 x 2 x 6 x 4; head at 2 positions
    want = (2 * counts.token_params(HAND) * 4 + 2 * 3 * 2 * 6 * 6
            + 2 * 3 * 2 * 6 * 4 + 2 * 10 * 8 * 2)
    assert counts.request_flops(HAND, 3, 2) == want


def test_served_weights_match_the_counts():
    """The counts' weights are the program's parameter tree at the cell's
    size, less the embedding and the vocabulary padding, plus the
    experts: 8.68 GB of bfloat16 in all."""
    import math
    import jax
    import jax.numpy as jnp
    from repro.models.common import PRec
    from repro.models.lm import LM
    from bench.drivers import serve_mla_moe
    cfg = json.loads((REPO / "bench/configs/deepseek-v3-ep32.json")
                     .read_text())
    model = LM(serve_mla_moe.arch_config(cfg))
    recs = jax.tree.leaves(model.param_recs(),
                           is_leaf=lambda x: isinstance(x, PRec))
    nbytes = sum(math.prod(r.shape) * jnp.dtype(r.dtype or "bfloat16")
                 .itemsize for r in recs)
    d, v, pv = cfg["hidden_size"], cfg["vocab_size"], model.padded_vocab
    experts = 6 * 8 * counts.expert_params(cfg) * 2
    assert counts.weight_bytes(cfg, 2) + experts == \
        nbytes - 2 * pv * d - 2 * (pv - v) * d
    assert 8.67e9 < nbytes < 8.70e9


# ----------------------------------------------------------------------
# the moe_gmm_roofline reader
# ----------------------------------------------------------------------
DEV = "/device:TPU:0"


def _ev(line, name, start, dur):
    return trace.Event(DEV, line, name, float(start), float(dur))


def _reader():
    return harness.load_module(REPO / "bench/metrics/moe_gmm_roofline.py",
                               "bench_metric_moe_gmm_roofline")


def test_moe_gmm_roofline_on_synthetic_trace():
    """Two decode runs, each with three moe_gmm ops of 1 ms; one more in a
    prefill run, which does not count. Needed per call: 2 experts' 88 MB
    (the cell's widths) and 8 pairs, bound by bytes."""
    cfg = json.loads((REPO / "bench/configs/deepseek-v3-ep32.json")
                     .read_text())
    need = counts.routed(cfg, 16, 4, 2)
    events = [_ev("XLA Modules", "jit__decode", 0, 10e6),
              _ev("XLA Modules", "jit__decode", 20e6, 10e6),
              _ev("XLA Modules", "jit__prefill", 40e6, 10e6)]
    for t0 in (0, 20e6, 40e6):
        events += [_ev("XLA Ops", f"moe_gmm.{i}", t0 + 1e6 + 2e6 * i, 1e6)
                   for i in range(3)]
    events.append(trace.Event("/host:CPU", "python", trace.WINDOW_SPAN,
                              0.0, 60e6))
    ctx = types.SimpleNamespace(
        trace=trace.summarize(events), peaks=PEAKS,
        counts={"decode_program": "_decode", "decode_calls": 2,
                "moe_gmm_needed_flops": need["flops"],
                "moe_gmm_needed_bytes": need["bytes"]})
    got = _reader().read(ctx)
    least = need["bytes"] / PEAKS["hbm_bytes_per_s"] / 2     # a call
    assert got == pytest.approx(100 * least / 3e-3)
    assert 0 < got < 100


def test_moe_gmm_roofline_silent_without_kernel_or_counts():
    events = [_ev("XLA Modules", "jit__decode", 0, 10e6),
              _ev("XLA Ops", "fusion.1", 0, 1e6),
              trace.Event("/host:CPU", "python", trace.WINDOW_SPAN, 0.0,
                          20e6)]
    ctx = types.SimpleNamespace(
        trace=trace.summarize(events), peaks=PEAKS,
        counts={"decode_program": "_decode", "decode_calls": 1,
                "moe_gmm_needed_flops": 1, "moe_gmm_needed_bytes": 1})
    assert _reader().read(ctx) is None
    ctx.counts = {"decode_program": "_decode", "decode_calls": 1}
    assert _reader().read(ctx) is None
