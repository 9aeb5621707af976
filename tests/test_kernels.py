"""Pallas kernels (interpret mode) vs the pure-jnp oracles in ref.py:
shape/dtype sweeps + hypothesis property sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:    # container without hypothesis: deterministic shim
    from _hypothesis_fallback import given, settings, st

from repro.core import blocking
from repro.kernels import flash_attention, longrange3d, ref, stencil3d7pt

COEFFS = dict(W=0.1, E=0.2, N=0.3, S=0.15, F=0.25, B=0.05, s=-1.0)
CVEC = [COEFFS[c] for c in "WENSFB"] + [COEFFS["s"]]


@pytest.mark.parametrize("shape", [(6, 16, 16), (12, 40, 40), (3, 9, 9),
                                   (20, 8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_stencil7pt_sweep(shape, dtype):
    a = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    out = stencil3d7pt(a, CVEC)
    np.testing.assert_allclose(out, ref.stencil3d7pt(a, COEFFS),
                               rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("shape", [(10, 16, 16), (14, 24, 24), (9, 40, 40)])
def test_longrange_sweep(shape):
    key = jax.random.PRNGKey(0)
    u = jax.random.normal(key, shape, jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
    roc = jax.random.normal(jax.random.fold_in(key, 2), shape,
                            jnp.float32) * 0.1
    c = jnp.array([0.5, 0.1, 0.05, 0.02, 0.01], jnp.float32)
    out = longrange3d(u, v, roc, c)
    np.testing.assert_allclose(out, ref.longrange3d(u, v, roc, c),
                               rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("n", [16, 24, 37])
@pytest.mark.parametrize("m", [9, 12, 17])
def test_longrange_ring_planes(m, n):
    """The V ring from the first interior plane (M = 9 = 2r+1) up, N off
    the sublane tile: the four boundary planes at each end and the width-4
    rim of every plane equal U exactly; the interior matches the oracle."""
    key = jax.random.PRNGKey(m * 100 + n)
    shape = (m, n, n)
    u = jax.random.normal(key, shape, jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
    roc = jax.random.normal(jax.random.fold_in(key, 2), shape,
                            jnp.float32) * 0.1
    c = jnp.array([0.5, 0.1, 0.05, 0.02, 0.01], jnp.float32)
    out = np.asarray(longrange3d(u, v, roc, c))
    want = np.asarray(ref.longrange3d(u, v, roc, c))
    u = np.asarray(u)
    r = 4
    np.testing.assert_array_equal(out[:r], u[:r])
    np.testing.assert_array_equal(out[m - r:], u[m - r:])
    for rim in (np.s_[:, :r], np.s_[:, n - r:], np.s_[:, :, :r],
                np.s_[:, :, n - r:]):
        np.testing.assert_array_equal(out[rim], u[rim])
    inner = np.s_[r:m - r, r:n - r, r:n - r]
    np.testing.assert_allclose(out[inner], want[inner], rtol=2e-4, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(3, 10), n=st.integers(3, 24))
def test_stencil7pt_property(m, n):
    """Property: kernel == oracle for arbitrary (M, N, N); boundary
    untouched."""
    a = jax.random.normal(jax.random.PRNGKey(m * 31 + n), (m, n, n),
                          jnp.float32)
    out = stencil3d7pt(a, CVEC)
    np.testing.assert_allclose(out, ref.stencil3d7pt(a, COEFFS),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(out[0], a[0])       # k boundary copied
    np.testing.assert_array_equal(out[:, 0], a[:, 0])


@pytest.mark.parametrize("b,h,sq,skv,d", [
    (2, 4, 256, 256, 64), (1, 2, 128, 512, 64),
    (1, 1, 512, 512, 128), (2, 2, 256, 256, 32)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, h, sq, skv, d, causal, dtype):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, h, sq, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, h, skv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, skv, d), dtype)
    out = flash_attention(q, k, v, causal=causal)
    want = ref.attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), rtol=tol, atol=tol)


def test_flash_attention_decode_offset():
    """decode: 1 query against a long kv prefix (q_offset = skv - 1)."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 2, 8, 64), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 512, 64),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 512, 64),
                          jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, rtol=2e-3, atol=2e-3)


def test_blocking_advisor_fits_vmem():
    """Property: advisor tiles always fit the budget (paper §2.4.2 applied
    to VMEM)."""
    vmem = 128 * 2**20
    for sq in (1024, 8192, 32768):
        t = blocking.attention_tiles(sq, sq, 128, 2, vmem)
        assert t.vmem_bytes <= 0.4 * vmem
        assert t.bq % 8 == 0 and t.bkv % 128 == 0
    for n in (512, 1015, 4096):
        b = blocking.stencil_blocks(4, (128, n, n), 3, 8, vmem)
        assert b.vmem_bytes <= 0.5 * vmem


def test_vmem_guard_raises():
    """ops.py refuses plane sizes whose LC working set exceeds VMEM."""
    a = jnp.zeros((3, 8, 8), jnp.float32)
    stencil3d7pt(a, CVEC)     # small: fine
    big = jax.ShapeDtypeStruct((3, 9000, 9000), jnp.float32)
    with pytest.raises(ValueError):
        stencil3d7pt(jnp.zeros(big.shape, big.dtype), CVEC)


class TestFlashBlockValidation:
    """Block sizes must tile the sequence lengths (satellite of the
    autotuner PR): the Pallas grid floor-divides, so a non-dividing block
    would silently drop trailing rows/keys."""

    def _qkv(self, sq=256, skv=256):
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (1, 1, sq, 128), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1),
                              (1, 1, skv, 128), jnp.float32)
        return q, k, k

    def test_bad_block_q_raises(self):
        from repro.kernels.flash_attention import (
            flash_attention as raw_flash)
        q, k, v = self._qkv()
        with pytest.raises(ValueError, match="block_q=96 does not divide"):
            raw_flash(q, k, v, block_q=96, block_kv=128)

    def test_bad_block_kv_raises(self):
        from repro.kernels.flash_attention import (
            flash_attention as raw_flash)
        q, k, v = self._qkv()
        with pytest.raises(ValueError,
                           match="block_kv=192 does not divide"):
            raw_flash(q, k, v, block_q=128, block_kv=192)

    def test_nonpositive_blocks_raise(self):
        from repro.kernels.flash_attention import validate_blocks
        with pytest.raises(ValueError, match="must be positive"):
            validate_blocks(256, 256, 0, 128)
        with pytest.raises(ValueError, match="must be positive"):
            validate_blocks(256, 256, 128, -8)

    def test_error_names_divisors_helper(self):
        from repro.kernels.flash_attention import validate_blocks
        with pytest.raises(ValueError, match="default_config"):
            validate_blocks(1000, 1000, 128, 128)

    def test_default_config_table(self):
        """Every DEFAULT_CONFIGS row is reachable and always validates
        after the divisor clamp, across awkward sequence lengths."""
        from repro.kernels.flash_attention import (DEFAULT_CONFIGS,
                                                   default_config,
                                                   validate_blocks)
        floors = [f for f, _ in DEFAULT_CONFIGS]
        assert floors == sorted(floors, reverse=True)
        assert floors[-1] == 0                  # catch-all row
        for sq in (8, 48, 256, 1000, 1024, 4096, 12288):
            for skv in (8, 48, 256, 1000, 1024, 4096, 12288):
                bq, bkv = default_config(sq, skv)
                validate_blocks(sq, skv, bq, bkv)   # must not raise

    def test_good_blocks_still_work(self):
        from repro.kernels.flash_attention import (
            flash_attention as raw_flash)
        q, k, v = self._qkv()
        out = raw_flash(q, k, v, block_q=128, block_kv=128)
        want = ref.attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, want, rtol=2e-3, atol=2e-3)


class TestPlatformAndVmem:
    """Interpret mode follows the platform; the wrappers count the VMEM
    the plane pipeline holds and refuse what the chip cannot hold."""

    def test_interpret_resolves_from_platform(self):
        from repro.kernels.backend import on_tpu, resolve_interpret
        assert resolve_interpret(None) is (not on_tpu())
        assert resolve_interpret(None) is True          # CPU test backend
        assert resolve_interpret(False) is False
        assert resolve_interpret(True) is True

    def test_padded_plane_bytes(self):
        assert blocking.padded_plane_bytes(8, 128, 4) == 8 * 128 * 4
        assert blocking.padded_plane_bytes(1015, 1015, 4) == \
            1016 * 1024 * 4
        assert blocking.padded_plane_bytes(3, 3, 4) == 8 * 128 * 4
        # two inputs + output, double-buffered, one temporary, headroom
        assert blocking.plane_pipeline_vmem(2, 1, 8, 128, 4) == \
            7 * 8 * 128 * 4 + blocking.PLANE_VMEM_HEADROOM

    def test_longrange_refused_before_compile(self):
        """N=1032 needs 132.52 MiB: refused by the wrapper while tracing,
        with the advisor's suggestion, before any kernel is built."""
        spec = jax.ShapeDtypeStruct((9, 1032, 1032), jnp.float32)
        c = jax.ShapeDtypeStruct((5,), jnp.float32)
        with pytest.raises(ValueError,
                           match=r"132\.52 MiB.*advisor suggests"):
            jax.eval_shape(longrange3d, spec, spec, spec, c)
        ok = jax.ShapeDtypeStruct((9, 1024, 1024), jnp.float32)
        assert jax.eval_shape(longrange3d, ok, ok, ok, c).shape == ok.shape

    @pytest.mark.parametrize("n", [8, 128, 1015, 1024])
    def test_kernel_vmem_counts_cover_compiler(self, n):
        """The counts stay above what the v5e compiler was found to
        allocate: 10.52 planes (stencil3d7pt), and for longrange3d's VMEM
        ring the smallest ``vmem_limit_bytes`` (to 1/64 MiB) under which a
        deviceless compile at M=130 passed: 12.0, 19.25, 28.996 and
        28.359 planes at N = 8, 128, 1015, 1024."""
        import importlib
        s7 = importlib.import_module("repro.kernels.stencil3d7pt")
        lr = importlib.import_module("repro.kernels.longrange3d")
        plane = blocking.padded_plane_bytes(n, n, 4)
        lr_need = {8: 12.0, 128: 19.25, 1015: 28.996, 1024: 28.359}[n]
        assert s7.vmem_bytes(n, 4) >= 10.52 * plane
        assert lr.vmem_bytes(n, 4) >= lr_need * plane


# ----------------------------------------------------------------------
# moe_gmm: grouped matmul over a MoE layer's held experts
# ----------------------------------------------------------------------
@pytest.mark.parametrize("sizes,rows,d,f", [
    ([3, 0, 5, 1], 12, 128, 256),          # uneven, one empty, spare rows
    ([0, 0, 0, 0], 8, 64, 128),            # no row for any group
    ([20], 20, 128, 128),                  # one group over two row tiles
    ([1, 30, 0, 2], 40, 256, 384),         # a group across tiles
    ([0, 0, 17, 0], 300, 256, 128),        # prefill tiles of 128 rows
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_matches_ref(sizes, rows, d, f, dtype):
    from repro.kernels import moe_gmm
    key = jax.random.PRNGKey(len(sizes) * rows)
    x = jax.random.normal(key, (rows, d), jnp.float32).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (len(sizes), d, f),
                           jnp.float32) * d ** -0.5).astype(dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(moe_gmm.moe_gmm(x, w, gs), np.float32)
    want = np.asarray(ref.grouped_matmul(x, w, gs), np.float32)
    # bf16 outputs round to 8 bits of mantissa: 1/128 of values about 3
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert not np.any(got[sum(sizes):])        # rows past the groups: 0


def test_moe_gmm_widens_narrow_weights_in_the_kernel():
    """float32 rows on bfloat16 weights, as a float32 check of a bfloat16
    model runs: the weights are widened exactly, a block at a time, so
    the product is the float32 one on the widened weights."""
    from repro.kernels import moe_gmm
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (24, 256), jnp.float32)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (3, 256, 128),
                           jnp.float32) * 256 ** -0.5).astype(jnp.bfloat16)
    gs = jnp.asarray([5, 0, 11], jnp.int32)
    got = moe_gmm.moe_gmm(x, w, gs)
    assert got.dtype == jnp.float32
    want = ref.grouped_matmul(x, w.astype(jnp.float32), gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert moe_gmm.vmem_bytes(16, 1024, 1024, 4, 2) == (
        2 * ((16 + 16) * 1024 * 4 + 1024 * 1024 * 2) + 1024 * 1024 * 4
        + 2 * 16 * 1024 * 4)


def test_moe_gmm_layout_aligns_groups_to_tiles():
    """Each group starts on a row tile; rows past the groups go nowhere."""
    from repro.kernels import moe_gmm
    src, dest, tile_group, used = moe_gmm.layout(
        jnp.asarray([3, 0, 17, 1], jnp.int32), 24, 16)
    n_pad = (2 + 4) * 16
    assert list(np.asarray(dest)) == (
        [0, 1, 2] + list(range(16, 33)) + [48] + [n_pad] * 3)
    assert int(used[0]) == 4
    assert list(np.asarray(tile_group))[:4] == [0, 2, 2, 3]
    # the inverse: each padded row's input row, 24 (none) for padding
    want = np.full(n_pad, 24)
    want[np.asarray(dest)[:21]] = np.arange(21)
    np.testing.assert_array_equal(np.asarray(src), want)
