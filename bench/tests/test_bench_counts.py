"""The work counts in bench/counts against hand counts at tiny shapes."""
import json
import math

import pytest

from bench_tiny_root import REPO  # noqa: F401  (puts the repo on sys.path)
from bench.counts import lm, stencil

# d=8, 2 heads of 4 (one K/V head), MLP 16, 2 layers, vocabulary 10
TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "intermediate_size": 16, "num_hidden_layers": 2, "vocab_size": 10}


@pytest.mark.parametrize("kind,m,n,sites,flops,nbytes", [
    # interior (4-2)(5-2)^2 = 18; 7 mul + 6 add; read a, write b
    ("jacobi7pt", 4, 5, 18, 13 * 18, 2 * 4 * 5 * 5 * 4),
    # interior (10-8)(11-8)^2 = 18; 15 mul + 26 add; read U V ROC, write U
    ("longrange25pt", 10, 11, 18, 41 * 18, 4 * 10 * 11 * 11 * 4),
])
def test_stencil_sweep(kind, m, n, sites, flops, nbytes):
    assert stencil.sweep(kind, m, n, 4) == {"sites": sites, "flops": flops,
                                            "bytes": nbytes}


def test_lm_layer_params():
    # q 8x8, k 8x4, v 8x4, o 8x8 = 192; gate, up, down 3 x 8x16 = 384
    assert lm.layer_matrix_params(TINY) == 576


def test_lm_request_flops():
    # prompt 3, 2 new: 4 tokens pass the layers (the last answer never
    # does): 2 x 576 x 2 layers x 4; causal attention over 1+2+3+4 keys:
    # 4 x 2 layers x 2 heads x 4 x 10; head at 2 positions: 2 x 10 x 8 x 2
    assert lm.request_flops(TINY, 3, 2) == 9216 + 640 + 320


def test_lm_decode_step():
    # batch 2 writing position 3: flops per row 2x576x2 + 4x2x2x4x4 (4
    # keys) + 2x10x8; bytes: weights (2 layers x (576 + 2 norms of 8) + final
    # norm 8 + head 80) x 2 B, K/V of 3 positions x 2 rows and 1 new row x 2,
    # each row 2 (K, V) x 2 layers x 1 head x 4 x 2 B = 32 B
    got = lm.decode_step(TINY, 2, 3, 2)
    assert got["flops"] == 2 * (2304 + 256 + 160)
    assert got["bytes"] == 2 * (2 * 592 + 88) + 2 * 3 * 32 + 2 * 32


def test_lm_weights_match_the_served_tree():
    """The count of weights a decode step reads is the program's own
    parameter tree, less the rows padding the vocabulary to 128."""
    import jax
    from repro.models.common import PRec
    from repro.models.lm import LM
    from bench.drivers import serve
    cfg = json.loads((REPO / "bench/configs/phi3-mini-3.8b.json").read_text())
    model = LM(serve.arch_config(cfg))
    n = sum(math.prod(r.shape) for r in jax.tree.leaves(
        model.param_recs(), is_leaf=lambda x: isinstance(x, PRec)))
    pad = (model.padded_vocab - cfg["vocab_size"]) * cfg["hidden_size"]
    assert lm.weight_bytes(cfg, 2) == 2 * (n - pad)
    # 3.72 B parameters, 7.44 GB of bfloat16
    assert 7.44e9 < lm.weight_bytes(cfg, 2) < 7.45e9
