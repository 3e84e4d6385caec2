"""Pallas kernel validation: shape/dtype sweeps, allclose vs ref.py oracles
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # optional dev dep — fixed-grid fallback
    from _hypothesis_fallback import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.binarize_apply import binarize_apply
from repro.kernels.hist2side import SPAN_OCTAVES, hist2side
from repro.kernels.moments import masked_moments

SHAPES = [63, 1024, 4096, 100_000, 262_145]
DTYPES = [jnp.float32, jnp.bfloat16]


def _x(seed, n, dtype=jnp.float32):
    return (jax.random.normal(jax.random.PRNGKey(seed), (n,)) * 2.0).astype(dtype)


class TestHist2Side:
    @pytest.mark.parametrize("n", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, n, dtype):
        x = _x(0, n, dtype)
        absmax = float(jnp.max(jnp.abs(x.astype(jnp.float32)))) + 1e-30
        lo, hi = absmax * 2.0**-SPAN_OCTAVES, absmax * 1.0001
        got = hist2side(x.astype(jnp.float32), lo, hi, nbins=64, bm=32, lanes=128)
        want = ref.hist2side_ref(x.astype(jnp.float32), lo, hi, nbins=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    def test_total_count(self):
        x = _x(1, 10_000)
        absmax = float(jnp.max(jnp.abs(x))) + 1e-30
        h = hist2side(x, absmax * 2.0**-SPAN_OCTAVES, absmax * 1.0001)
        # all nonzero entries land in some bucket
        assert float(jnp.sum(h)) == float(jnp.sum(x != 0))

    def test_per_side_ranges(self):
        x = jnp.array([0.5, -0.5, 2.0, -2.0, 0.01, -0.01])
        lo = jnp.array([0.4, 1.0])  # side 0 (pos) range vs side 1 (neg) range
        hi = jnp.array([1.0, 4.0])
        got = hist2side(x, lo, hi, nbins=8, bm=8, lanes=128)
        want = ref.hist2side_ref(x, lo, hi, nbins=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))
        assert float(jnp.sum(got[0])) == 1  # only +0.5
        assert float(jnp.sum(got[1])) == 1  # only -2.0


class TestMaskedMoments:
    @pytest.mark.parametrize("n", SHAPES)
    def test_matches_ref(self, n):
        x = _x(2, n)
        got = masked_moments(x, 0.7, 0.9, bm=32, lanes=128)
        want = ref.masked_moments_ref(x, 0.7, 0.9)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4)


class TestBinarizeApply:
    @pytest.mark.parametrize("n", SHAPES)
    def test_matches_ref(self, n):
        x = _x(3, n)
        for pos_wins in (1.0, 0.0):
            got_out, got_res = binarize_apply(x, 0.5, 0.6, 0.55, pos_wins,
                                              bm=32, lanes=128)
            want_out, want_res = ref.binarize_apply_ref(x, 0.5, 0.6, 0.55, pos_wins)
            np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out))
            np.testing.assert_allclose(np.asarray(got_res), np.asarray(want_res))

    def test_residual_identity(self):
        x = _x(4, 5000)
        out, res = binarize_apply(x, 0.5, 0.5, 1.0, 1.0)
        np.testing.assert_allclose(np.asarray(out + res), np.asarray(x), rtol=1e-6)


class TestFullPipeline:
    @pytest.mark.parametrize("n", [4096, 50_000])
    @pytest.mark.parametrize("p", [0.05, 0.01])
    def test_hist_close_to_exact(self, n, p):
        """Histogram-threshold SBC ≈ exact top-k SBC (the paper's Alg. 2):
        exactly k survivors, means within 2%."""
        x = _x(5, n)
        got = ops.sbc_compress_hist(x, p=p)
        want = ops.sbc_compress_exact(x, p=p)
        k = max(1, round(p * n))
        assert float(got.count) == k
        assert int(jnp.sum(got.delta_star != 0)) == k
        assert abs(float(got.mean) - float(want.mean)) <= 0.02 * abs(float(want.mean))

    def test_exact_matches_oracle(self):
        x = _x(6, 8192)
        k = 82
        got = ops.sbc_compress_exact(x, p=0.01)
        want = ref.sbc_exact_ref(x, k)
        np.testing.assert_allclose(np.asarray(got.delta_star), np.asarray(want),
                                   rtol=1e-5)

    @given(seed=st.integers(0, 40), logn=st.integers(8, 14))
    @settings(max_examples=20, deadline=None)
    def test_hist_residual_identity_property(self, seed, logn):
        n = 2**logn + seed % 7  # off-aligned sizes exercise padding
        x = _x(seed, n)
        out = ops.sbc_compress_hist(x, p=0.02)
        np.testing.assert_allclose(
            np.asarray(out.delta_star + out.residual), np.asarray(x), rtol=1e-5,
            atol=1e-6,
        )

    def test_all_equal_values(self):
        """Degenerate input: all entries identical."""
        x = jnp.ones((1000,))
        out = ops.sbc_compress_hist(x, p=0.01)
        assert bool(jnp.all(jnp.isfinite(out.delta_star)))
        # every entry ties: exactly k are kept, every 100th by position
        kept = np.flatnonzero(np.asarray(out.delta_star))
        np.testing.assert_array_equal(kept, np.arange(0, 1000, 100))
        assert float(out.mean) == 1.0

    def test_dense_to_sparse_extraction(self):
        x = jnp.zeros((100,)).at[jnp.array([3, 50, 99])].set(2.5)
        idx, valid = ops.dense_to_sparse(x, k_cap=8)
        assert set(np.asarray(idx[:3]).tolist()) == {3, 50, 99}
        np.testing.assert_array_equal(np.asarray(valid), [1, 1, 1, 0, 0, 0, 0, 0])
