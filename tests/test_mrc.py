"""MRC codec: roundtrip identity, estimator behaviour, property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import mrc
from repro.core.bernoulli import bern_kl, clip01, inv_sigmoid, log_ratio_coeffs, sigmoid

KEY = jax.random.PRNGKey(0)


def _qp(key, b=6, s=32, spread=0.1):
    q = jax.random.uniform(jax.random.fold_in(key, 1), (b, s), minval=0.15, maxval=0.85)
    p = jnp.clip(q + spread * jax.random.normal(jax.random.fold_in(key, 2), (b, s)),
                 0.05, 0.95)
    return q, p


class TestFixedCodec:
    def test_roundtrip_identity(self):
        q, p = _qp(KEY)
        res = mrc.encode_fixed(KEY, jax.random.fold_in(KEY, 3), q, p, n_is=32)
        dec = mrc.decode_fixed(KEY, res.indices, p, n_is=32)
        np.testing.assert_array_equal(np.asarray(res.sample), np.asarray(dec))

    def test_indices_in_range(self):
        q, p = _qp(KEY)
        res = mrc.encode_fixed(KEY, jax.random.fold_in(KEY, 3), q, p, n_is=16)
        idx = np.asarray(res.indices)
        assert idx.min() >= 0 and idx.max() < 16

    def test_sample_is_binary(self):
        q, p = _qp(KEY)
        res = mrc.encode_fixed(KEY, jax.random.fold_in(KEY, 3), q, p, n_is=16)
        s = np.asarray(res.sample)
        assert set(np.unique(s)).issubset({0.0, 1.0})

    def test_zero_kl_is_exact_prior_sample(self):
        """q == p => W uniform => the sample is a prior draw (still valid)."""
        p = jnp.full((4, 16), 0.5)
        res = mrc.encode_fixed(KEY, jax.random.fold_in(KEY, 3), p, p, n_is=8)
        assert res.sample.shape == (4, 16)

    def test_estimator_improves_with_nis(self):
        """Mean-sample estimate approaches q as n_is grows (Chatterjee-Diaconis)."""
        q, p = _qp(jax.random.fold_in(KEY, 9), b=4, s=64, spread=0.05)
        errs = []
        for n_is in (4, 64, 1024):
            _, qhat = mrc.transmit_fixed(
                jax.random.fold_in(KEY, n_is), jax.random.fold_in(KEY, n_is + 1),
                q, p, n_is=n_is, n_samples=256)
            errs.append(float(jnp.mean(jnp.abs(qhat - q))))
        assert errs[2] < errs[0], errs

    def test_many_samples_concentrate(self):
        q, p = _qp(jax.random.fold_in(KEY, 11), b=4, s=32, spread=0.02)
        _, qhat = mrc.transmit_fixed(KEY, jax.random.fold_in(KEY, 1), q, p,
                                     n_is=256, n_samples=512)
        assert float(jnp.mean(jnp.abs(qhat - q))) < 0.1

    def test_chunking_invariance(self):
        """Same indices regardless of the encode chunk size."""
        q, p = _qp(KEY, b=10)
        r1 = mrc.encode_fixed(KEY, jax.random.fold_in(KEY, 3), q, p, n_is=16, chunk=2)
        r2 = mrc.encode_fixed(KEY, jax.random.fold_in(KEY, 3), q, p, n_is=16, chunk=10)
        np.testing.assert_array_equal(np.asarray(r1.indices), np.asarray(r2.indices))

    def test_pallas_logw_path_matches_default(self):
        from repro.kernels.ops import mrc_logw_fn
        q, p = _qp(KEY, b=5, s=48)
        r1 = mrc.encode_fixed(KEY, jax.random.fold_in(KEY, 3), q, p, n_is=32)
        r2 = mrc.encode_fixed(KEY, jax.random.fold_in(KEY, 3), q, p, n_is=32,
                              logw_fn=mrc_logw_fn())
        np.testing.assert_array_equal(np.asarray(r1.indices), np.asarray(r2.indices))


class TestSegmentCodec:
    def test_roundtrip(self):
        d, n_seg = 64, 4
        q = jax.random.uniform(KEY, (d,), minval=0.2, maxval=0.8)
        p = jnp.clip(q + 0.05, 0.05, 0.95)
        seg = jnp.repeat(jnp.arange(n_seg), d // n_seg)
        res = mrc.encode_segments(KEY, jax.random.fold_in(KEY, 3), q, p, seg,
                                  n_is=16, n_seg=n_seg)
        dec = mrc.decode_segments(KEY, res.indices, p, seg, n_is=16)
        np.testing.assert_array_equal(np.asarray(res.sample), np.asarray(dec))

    def test_rejects_permuted_seg_ids(self):
        """The wire plan header is run-length coded, so a permuted seg_ids
        would silently round-trip to a different segmentation: the codec
        boundary must refuse it."""
        d, n_seg = 16, 4
        q = jax.random.uniform(KEY, (d,), minval=0.2, maxval=0.8)
        p = jnp.clip(q + 0.05, 0.05, 0.95)
        good = jnp.repeat(jnp.arange(n_seg), d // n_seg)
        permuted = good[::-1]
        with pytest.raises(ValueError, match="non-decreasing"):
            mrc.encode_segments(KEY, jax.random.fold_in(KEY, 3), q, p,
                                permuted, n_is=8, n_seg=n_seg)
        with pytest.raises(ValueError, match="non-decreasing"):
            mrc.decode_segments(KEY, jnp.zeros((n_seg,), jnp.int32), p,
                                permuted, n_is=8)
        with pytest.raises(ValueError, match="non-decreasing"):
            mrc.encode_segments(KEY, jax.random.fold_in(KEY, 3), q, p,
                                good + 1, n_is=8, n_seg=n_seg + 1)

    def test_matches_fixed_when_blocks_equal(self):
        """Uniform segments == fixed blocks of the same size (same estimate
        family; indices differ by key layout, so compare statistically)."""
        d, bs = 128, 32
        q = jax.random.uniform(KEY, (d,), minval=0.3, maxval=0.7)
        p = jnp.full((d,), 0.5)
        seg = jnp.repeat(jnp.arange(d // bs), bs)
        _, qs = mrc.transmit_segments(KEY, jax.random.fold_in(KEY, 1), q, p, seg,
                                      n_is=64, n_seg=d // bs, n_samples=128)
        _, qf = mrc.transmit_fixed(KEY, jax.random.fold_in(KEY, 1),
                                   q.reshape(-1, bs), p.reshape(-1, bs),
                                   n_is=64, n_samples=128)
        assert abs(float(jnp.mean(qs) - jnp.mean(qf))) < 0.05


class TestBernoulliUtils:
    @given(st.floats(0.01, 0.99), st.floats(0.01, 0.99))
    @settings(max_examples=50, deadline=None)
    def test_kl_nonnegative(self, q, p):
        kl = float(bern_kl(jnp.float32(q), jnp.float32(p)))
        assert kl >= -1e-6

    @given(st.floats(0.01, 0.99))
    @settings(max_examples=30, deadline=None)
    def test_kl_zero_iff_equal(self, q):
        assert float(bern_kl(jnp.float32(q), jnp.float32(q))) < 1e-9

    @given(st.floats(0.05, 0.95), st.floats(0.05, 0.95))
    @settings(max_examples=50, deadline=None)
    def test_log_ratio_coeffs_consistent(self, q, p):
        """a*x + b must equal log(Q(x)/P(x)) for x in {0, 1}."""
        a, b = log_ratio_coeffs(jnp.float32(q), jnp.float32(p))
        lr1 = np.log(q / p)
        lr0 = np.log((1 - q) / (1 - p))
        assert abs(float(a + b) - lr1) < 1e-4
        assert abs(float(b) - lr0) < 1e-4

    @given(st.floats(0.01, 0.99))
    @settings(max_examples=30, deadline=None)
    def test_sigmoid_inverse(self, t):
        assert abs(float(sigmoid(inv_sigmoid(jnp.float32(t)))) - t) < 1e-4

    def test_clip01_bounds(self):
        x = jnp.array([-1.0, 0.0, 0.5, 1.0, 2.0])
        c = clip01(x)
        assert float(c.min()) > 0.0 and float(c.max()) < 1.0


class TestSharedRandomness:
    def test_same_key_same_candidates(self):
        """Encoder and decoder derive identical candidates: decode of the
        transmitted index reproduces the encoder's selected sample exactly --
        the operational meaning of 'shared randomness'."""
        q, p = _qp(KEY)
        for t in range(3):
            kt = mrc.round_key(KEY, t)
            res = mrc.encode_fixed(kt, jax.random.fold_in(kt, 1), q, p, n_is=32)
            dec = mrc.decode_fixed(kt, res.indices, p, n_is=32)
            np.testing.assert_array_equal(np.asarray(res.sample), np.asarray(dec))

    def test_client_keys_distinct(self):
        k1 = mrc.client_key(KEY, 1)
        k2 = mrc.client_key(KEY, 2)
        assert not np.array_equal(np.asarray(k1), np.asarray(k2))
