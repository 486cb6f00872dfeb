"""Stochastic quantizers: unbiasedness + variance bound + the Lemma-1
contraction of C_mrc(Q_s(.)) checked empirically."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import mrc, quantizers as Q
from repro.core.bernoulli import clip01

KEY = jax.random.PRNGKey(1)


class TestQsgd:
    def test_unbiased(self):
        """E[Q_s(x)] == x  (Alistarh et al. 2017)."""
        g = jax.random.normal(KEY, (64,))
        post = Q.qsgd(g, s=4)
        keys = jax.random.split(jax.random.fold_in(KEY, 1), 4000)
        samples = jax.vmap(lambda k: Q.qsgd_sample(k, post))(keys)
        err = float(jnp.max(jnp.abs(jnp.mean(samples, 0) - g)))
        assert err < 0.05 * float(jnp.linalg.norm(g)), err

    def test_variance_bound(self):
        """E||Q_s(x) - x||^2 <= min(d/s^2, sqrt(d)/s) ||x||^2."""
        d, s = 128, 16
        g = jax.random.normal(KEY, (d,))
        post = Q.qsgd(g, s=s)
        keys = jax.random.split(jax.random.fold_in(KEY, 2), 2000)
        samples = jax.vmap(lambda k: Q.qsgd_sample(k, post))(keys)
        var = float(jnp.mean(jnp.sum((samples - g) ** 2, -1)))
        bound = min(d / s ** 2, np.sqrt(d) / s) * float(jnp.sum(g ** 2))
        assert var <= bound * 1.1, (var, bound)

    @given(st.integers(2, 64))
    @settings(max_examples=20, deadline=None)
    def test_levels_hit(self, s):
        g = jax.random.normal(KEY, (32,))
        post = Q.qsgd(g, s=s)
        bits = jnp.ones_like(post.q)
        vals = np.abs(np.asarray(post.value(bits))) / float(post.norm) * s
        assert np.all(vals <= s + 1e-4)


class TestStochasticSign:
    def test_posterior_monotone(self):
        g = jnp.array([-3.0, -0.1, 0.0, 0.1, 3.0])
        q = np.asarray(Q.stochastic_sign(g, temperature=1.0).q)
        assert np.all(np.diff(q) >= 0)
        assert abs(q[2] - 0.5) < 1e-6

    def test_value_mapping(self):
        post = Q.stochastic_sign(jnp.zeros((4,)))
        np.testing.assert_allclose(np.asarray(post.value(jnp.ones(4))), 1.0)
        np.testing.assert_allclose(np.asarray(post.value(jnp.zeros(4))), -1.0)


class TestBaselines:
    def test_topk_keeps_largest(self):
        g = jnp.array([0.1, -5.0, 0.3, 2.0])
        out = np.asarray(Q.topk_compress(g, 2))
        assert out[1] == -5.0 and out[3] == 2.0 and out[0] == 0.0

    def test_randk_unbiased(self):
        g = jax.random.normal(KEY, (32,))
        keys = jax.random.split(KEY, 3000)
        outs = jax.vmap(lambda k: Q.randk_compress(k, g, 8))(keys)
        err = float(jnp.max(jnp.abs(jnp.mean(outs, 0) - g)))
        assert err < 0.25 * float(jnp.max(jnp.abs(g))), err

    def test_sign_compress_scale(self):
        g = jnp.array([1.0, -2.0, 3.0])
        out = np.asarray(Q.sign_compress(g))
        np.testing.assert_allclose(np.abs(out), 2.0, rtol=1e-6)


class TestLemma1Contraction:
    """Empirical check of Lemma 1:  E||C_mrc(Q_s(x)) - x||^2 <= (1-d)||x||^2
    with a strictly positive d for s >= sqrt(2 d_model) and adequate n_IS."""

    @pytest.mark.parametrize("n_is", [16, 256])
    def test_contraction(self, n_is):
        d = 64
        s = int(np.ceil(np.sqrt(2 * d))) + 2
        g = jax.random.normal(KEY, (d,))
        post = Q.qsgd(g, s=s)
        prior = jnp.full((1, d), 0.5)

        def one(key):
            _, bits = mrc.transmit_fixed(
                key, jax.random.fold_in(key, 1), post.q.reshape(1, d),
                prior, n_is=n_is, n_samples=1)
            return post.value(bits.reshape(d))

        keys = jax.random.split(jax.random.fold_in(KEY, n_is), 300)
        recon = jax.vmap(one)(keys)
        mse = float(jnp.mean(jnp.sum((recon - g) ** 2, -1)))
        norm2 = float(jnp.sum(g ** 2))
        assert mse < norm2, f"no contraction: {mse} >= {norm2}"

    def test_contraction_improves_with_nis(self):
        d = 64
        s = int(np.ceil(np.sqrt(2 * d))) + 2
        g = jax.random.normal(jax.random.fold_in(KEY, 5), (d,))
        post = Q.qsgd(g, s=s)
        prior = jnp.full((1, d), 0.5)
        mses = []
        for n_is in (4, 512):
            def one(key):
                _, bits = mrc.transmit_fixed(
                    key, jax.random.fold_in(key, 1), post.q.reshape(1, d),
                    prior, n_is=n_is, n_samples=1)
                return post.value(bits.reshape(d))
            keys = jax.random.split(jax.random.fold_in(KEY, 100 + n_is), 200)
            recon = jax.vmap(one)(keys)
            mses.append(float(jnp.mean(jnp.sum((recon - g) ** 2, -1))))
        assert mses[1] < mses[0], mses
