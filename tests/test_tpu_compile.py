"""Deviceless TPU v5e compiles of the Pallas kernels at the sizes
``chip_smoke.py`` runs them (the paper's M=130, N=1015 stencils; flash
attention at phi3-mini's head shape), with ``interpret=False`` passed
explicitly. The TPU compiler runs here against a described, unattached
chip, so it refuses what the chip would refuse: unsupported lowerings,
and more VMEM than the chip has. Also the served decode step at phi3-mini
widths, whose compiled program must update the donated KV cache in place.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file. All such compiles stay in this one file for the same reason.
"""
import dataclasses
import functools
import importlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops
from repro.models.common import abstract_tree
from repro.models.lm import LM

_lr = importlib.import_module("repro.kernels.longrange3d")
_s7 = importlib.import_module("repro.kernels.stencil3d7pt")

M, N = 130, 1015
HEADS, HEAD_DIM = 32, 96                 # phi3-mini


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a deviceless compile is written to the persistent cache but cannot
    # be read back without a chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("n", [N, 1024], ids=["paper_N1015", "max_N1024"])
def test_longrange3d_compiles(one_chip, n):
    """Compiles at the paper's N and at the largest N the wrapper admits
    (its VMEM count must cover what the compiler allocates)."""
    a = _spec((M, n, n), jnp.float32, one_chip)
    c = _spec((5,), jnp.float32, one_chip)
    compiled = _compile(
        lambda u, v, roc, cf: ops.longrange3d(u, v, roc, cf,
                                              interpret=False), a, a, a, c)
    assert "tpu_custom_call" in compiled.as_text()


def test_longrange3d_reads_v_once(one_chip):
    """At the paper's size the compiled kernel takes V as one operand (v,
    u, roc, coeffs), not nine shifted copies of it: each V plane crosses
    HBM once, into the kernel's VMEM ring."""
    a = _spec((M, N, N), jnp.float32, one_chip)
    c = _spec((5,), jnp.float32, one_chip)
    text = _compile(
        lambda u, v, roc, cf: ops.longrange3d(u, v, roc, cf,
                                              interpret=False),
        a, a, a, c).as_text()
    calls = [re.search(r"custom-call\(([^)]*)\)", line)[1]
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert calls[0].count("%") == 4, calls[0]


def test_stencil3d7pt_compiles(one_chip):
    a = _spec((M, N, N), jnp.float32, one_chip)
    c = _spec((7,), jnp.float32, one_chip)
    compiled = _compile(
        lambda x, cf: ops.stencil3d7pt(x, cf, interpret=False), a, c)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b,sq,skv", [(1, 4096, 4096), (8, 1, 8192)],
                         ids=["prefill", "decode"])
def test_flash_attention_compiles(one_chip, b, sq, skv):
    q = _spec((b, HEADS, sq, HEAD_DIM), jnp.bfloat16, one_chip)
    kv = _spec((b, HEADS, skv, HEAD_DIM), jnp.bfloat16, one_chip)
    compiled = _compile(
        lambda q_, k_, v_: ops.flash_attention(q_, k_, v_, interpret=False),
        q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_longrange3d_refused_beyond_vmem(one_chip):
    """At N=1032 the wrapper's count (132.52 MiB) exceeds the chip's 128
    MiB: the wrapper refuses with its own error before compiling, and the
    compiler, given the raw kernel, refuses too (it was found to need
    129.91 MiB there). From N=1025 on the count is above 128 MiB; the
    compiler still fits N=1025-1028 and refuses N=1029-1032."""
    n = 1032
    a = _spec((M, n, n), jnp.float32, one_chip)
    c = _spec((5,), jnp.float32, one_chip)
    with pytest.raises(ValueError, match="more than the chip's 128 MiB"):
        _compile(lambda u, v, roc, cf: ops.longrange3d(
            u, v, roc, cf, interpret=False), a, a, a, c)
    with pytest.raises(Exception, match="vmem"):
        _compile(functools.partial(_lr.longrange3d, interpret=False),
                 a, a, a, c)


def test_vmem_count_admits_paper_sizes():
    """The counts the compiles above rely on, in numbers: the paper's
    N=1015 fits both kernels, N=1032 does not fit the long-range one."""
    vmem = 128 * 2**20
    assert _s7.vmem_bytes(N, 4) < vmem / 2
    assert _lr.vmem_bytes(N, 4) < _lr.vmem_bytes(1024, 4) < vmem
    assert _lr.vmem_bytes(1032, 4) > vmem


# one instruction of compiled HLO text: name, result type(s), opcode
_INSTR = re.compile(r"%(?P<name>[\w.\-]+) = (?P<type>.+?) (?P<op>[\w\-]+)\(")


def _largest_result(typ: str) -> int:
    """Elements of the largest array among an instruction's results."""
    return max((math.prod(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"\w+\[([\d,]*)\]", typ)),
               default=0)


def test_decode_step_updates_kv_cache_in_place(one_chip):
    """phi3-mini's decode step, cut to 2 layers, batch 4, 2048 cache rows,
    bf16, caches donated: the compiled program writes each layer's new row
    into the stacked K/V and moves no cache. No copy as large as one
    layer's K, no dynamic-update-slice fusion that rewrites a whole stacked
    buffer, and scratch memory below one stacked K."""
    layers, batch, rows = 2, 4, 2048
    cfg = dataclasses.replace(configs.get_config("phi3-mini-3.8b"),
                              n_layers=layers, tp=1)
    model = LM(cfg)

    def spec(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                            abstract_tree(tree, default_dtype=jnp.bfloat16))

    caches = spec(model.cache_recs(batch, rows))
    compiled = jax.jit(model.decode_step, donate_argnums=1).lower(
        spec(model.param_recs()), caches,
        _spec((batch, 1), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip)).compile()

    k_layer = batch * cfg.n_kv_heads * cfg.head_dim * rows
    stacked_k_bytes = layers * k_layer * 2
    assert {x.size for x in jax.tree.leaves(caches)} == {layers * k_layer}
    for m in _INSTR.finditer(compiled.as_text()):
        size = _largest_result(m["type"])
        if m["op"] in ("copy", "copy-start"):
            assert size < k_layer, f"cache copy: {m[0]}"
        if m["op"] == "fusion" and "dynamic-update-slice" in m["name"]:
            assert size < layers * k_layer, f"whole-cache write: {m[0]}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < stacked_k_bytes, (temp, stacked_k_bytes)


@pytest.mark.parametrize("rows", [128, 8192], ids=["decode", "prefill"])
@pytest.mark.parametrize("d,f", [(7168, 2048), (2048, 7168)],
                         ids=["gate_up", "down"])
def test_moe_gmm_compiles_at_cell_shapes(one_chip, rows, d, f):
    """The held experts' grouped matmul at the deepseek-v3-ep32 cell's
    widths over its 8 held experts: a decode step's 16 rows x 8 experts
    per token (about 4-16 of them held here), a prefill chunk's 1024
    tokens x 8 (about 4096 held in the 16 x 1024 prompt)."""
    from repro.kernels import moe_gmm
    compiled = _compile(
        lambda x, w, g: moe_gmm.moe_gmm(x, w, g, interpret=False),
        _spec((rows, d), jnp.bfloat16, one_chip),
        _spec((8, d, f), jnp.bfloat16, one_chip),
        _spec((8,), jnp.int32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def _decode_text(model, one_chip, batch, rows):
    """The compiled decode step's HLO text, donated caches."""
    def spec(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                            abstract_tree(tree, default_dtype=jnp.bfloat16))

    return jax.jit(model.decode_step, donate_argnums=1).lower(
        spec(model.param_recs()), spec(model.cache_recs(batch, rows)),
        _spec((batch, 1), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip)).compile().as_text()


def _cache_names(model) -> set:
    return {jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(model.cache_recs(1, 8))[0]}


def test_phi3_decode_program_unchanged(one_chip):
    """A model without a held-expert layer gains nothing from the expert
    path: no ``moe_stats`` cache entry, no ``moe_gmm`` kernel and no op
    from the ``moe/`` scopes in its decode program. A small held-share
    model, compiled alike, has all three."""
    from bench.drivers import serve_mla_moe
    from test_deepseek_share import SMALL
    phi3 = LM(dataclasses.replace(configs.get_config("phi3-mini-3.8b"),
                                  n_layers=2, tp=1))
    text = _decode_text(phi3, one_chip, 4, 2048)
    assert not any("moe_stats" in n for n in _cache_names(phi3))
    assert "moe_gmm" not in text and "/moe/" not in text
    held = LM(serve_mla_moe.arch_config(dict(SMALL,
                                             torch_dtype="bfloat16")))
    text = _decode_text(held, one_chip, 4, 32)
    assert any("moe_stats" in n for n in _cache_names(held))
    assert "moe_gmm" in text and "/moe/route" in text
