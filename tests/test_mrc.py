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


def _materialising_encode(shared_key, select_key, q, p, *, n_is, chunk):
    """The fixed-block encode written out with the full candidate tensor:
    every chunk's (nb, n_is, S) f32 0/1 ``x``, the sample gathered from it."""
    B, S = q.shape
    nb = min(chunk, B)
    n_chunks = -(-B // nb)
    half = jnp.full((n_chunks * nb - B, S), 0.5, q.dtype)
    q, p = jnp.concatenate([q, half]), jnp.concatenate([p, half])
    a, b = log_ratio_coeffs(q, p)
    idxs, samples = [], []
    for c in range(n_chunks):
        ids = c * nb + jnp.arange(nb)
        u = jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(shared_key, i), (n_is, S)))(ids)
        x = (u < clip01(p[ids])[:, None, :]).astype(jnp.float32)
        logw = jnp.einsum("bis,bs->bi", x, a[ids]) + jnp.sum(b[ids], -1, keepdims=True)
        gu = jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(select_key, i), (n_is,)))(ids)
        gumbel = -jnp.log(-jnp.log(jnp.clip(gu, 1e-12, 1.0 - 1e-12)))
        idx = jnp.argmax(logw + gumbel, axis=-1).astype(jnp.int32)
        idxs.append(idx)
        samples.append(jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0, :])
    return jnp.concatenate(idxs)[:B], jnp.concatenate(samples)[:B]


class TestCandidateRow:
    """The selected row is regenerated from its own threefry counters, bit
    for bit the row of the block's full candidate tensor."""

    @pytest.mark.parametrize("typed", [False, True], ids=["raw", "typed"])
    @pytest.mark.parametrize("n_is,size,block,row", [
        (256, 256, 0, 0),
        (256, 256, 776, 255),
        (256, 256, 3, 128),
        (16, 200, 5, 15),      # n_is != S, S not a multiple of 128
        (64, 33, 1, 7),
        (5, 300, 2, 0),
    ])
    def test_matches_full_tensor_row(self, typed, n_is, size, block, row):
        key = jax.random.key(11) if typed else jax.random.PRNGKey(11)
        block_key = jax.random.fold_in(key, block)
        full = jax.random.uniform(block_key, (n_is, size))[row]
        got = jax.jit(mrc._candidate_row, static_argnums=(2, 3))(
            block_key, row, n_is, size)
        np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                      np.asarray(full).view(np.uint32))

    def test_vmapped_rows_match(self):
        """Batched blocks and rows, as the encoder and decoder call it."""
        key = jax.random.PRNGKey(5)
        ids, rows = jnp.arange(6), jnp.array([0, 31, 4, 17, 31, 9])
        got = jax.vmap(lambda b, r: mrc._candidate_row(
            jax.random.fold_in(key, b), r, 32, 40))(ids, rows)
        full = jax.vmap(lambda b: mrc._block_candidates(key, b, 32, 40))(ids)
        want = jnp.take_along_axis(full, rows[:, None, None], axis=1)[:, 0]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_refuses_non_partitionable_threefry(self):
        prev = jax.config.jax_threefry_partitionable
        jax.config.update("jax_threefry_partitionable", False)
        try:
            with pytest.raises(ValueError, match="partitionable"):
                mrc._candidate_row(KEY, 0, 8, 16)
        finally:
            jax.config.update("jax_threefry_partitionable", prev)

    def test_refuses_other_prng_impl(self):
        with pytest.raises(ValueError, match="threefry2x32"):
            mrc._candidate_row(jax.random.key(0, impl="rbg"), 0, 8, 16)

    def test_refuses_more_candidates_than_counters(self):
        with pytest.raises(ValueError, match="2\\*\\*32"):
            mrc._candidate_row(KEY, 0, 2 ** 24, 512)


class TestMaterialisingOracle:
    @pytest.mark.parametrize("chunk", [4, 16])
    def test_encode_matches_materialising_encode(self, chunk):
        """Indices and sample equal the encode that gathers from the full
        (nb, n_is, S) tensor, with a padded tail at both chunk sizes."""
        q, p = _qp(jax.random.fold_in(KEY, 21), b=21, s=40)
        sk, sel = jax.random.fold_in(KEY, 4), jax.random.fold_in(KEY, 5)
        res = mrc.encode_fixed(sk, sel, q, p, n_is=32, chunk=chunk)
        idx, sample = _materialising_encode(sk, sel, q, p, n_is=32, chunk=chunk)
        np.testing.assert_array_equal(np.asarray(res.indices), np.asarray(idx))
        np.testing.assert_array_equal(np.asarray(res.sample), np.asarray(sample))


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
