"""Deviceless TPU v5e compiles of the Pallas kernels at the sizes
``chip_smoke.py`` runs them (the paper's M=130, N=1015 stencils; flash
attention at phi3-mini's head shape), with ``interpret=False`` passed
explicitly. The TPU compiler runs here against a described, unattached
chip, so it refuses what the chip would refuse: unsupported lowerings,
and more VMEM than the chip has.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file. All such compiles stay in this one file for the same reason.
"""
import functools
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

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
    """At N=1040 the wrapper's count (133.5 MiB) exceeds the chip's 128
    MiB: the wrapper refuses with its own error before compiling, and the
    compiler, given the raw kernel, refuses too."""
    n = 1040
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
    N=1015 fits both kernels, N=1040 does not fit the long-range one."""
    vmem = 128 * 2**20
    assert _s7.vmem_bytes(N, 4) < vmem / 2
    assert _lr.vmem_bytes(N, 4) < _lr.vmem_bytes(1024, 4) < vmem
    assert _lr.vmem_bytes(1040, 4) > vmem
