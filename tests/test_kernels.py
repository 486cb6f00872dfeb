"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(2)

LOGW_SHAPES = [
    (1, 16, 32),       # tiny, everything padded
    (3, 100, 70),      # ragged both dims
    (2, 128, 128),     # exactly tile-aligned
    (4, 256, 256),     # multi-tile
    (1, 129, 257),     # one past alignment
    (7, 64, 300),
]


@pytest.mark.parametrize("shape", LOGW_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mrc_logw_matches_ref(shape, dtype):
    nb, nis, s = shape
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = (jax.random.uniform(k1, (nb, nis, s)) < 0.5).astype(dtype)
    a = jax.random.normal(k2, (nb, s), dtype)
    b = jax.random.normal(k3, (nb, s), dtype)
    out = ops.mrc_logw(x, a, b, interpret=True)
    expect = ref.mrc_logw_ref(x.astype(jnp.float32), a.astype(jnp.float32),
                              b.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol * s)


KL_SHAPES = [(1, 16), (5, 100), (2, 128), (3, 256), (4, 300), (16, 129)]


@pytest.mark.parametrize("shape", KL_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bernoulli_kl_matches_ref(shape, dtype):
    nb, s = shape
    q = jax.random.uniform(KEY, (nb, s), minval=0.05, maxval=0.95).astype(dtype)
    p = jax.random.uniform(jax.random.fold_in(KEY, 1), (nb, s),
                           minval=0.05, maxval=0.95).astype(dtype)
    out = ops.bernoulli_kl(q, p, interpret=True)
    expect = ref.bernoulli_kl_ref(q.astype(jnp.float32), p.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 0.1
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", [(1, 16), (3, 100), (4, 700), (10, 1536)])
def test_bernoulli_kl_total_matches_mean_reduction(shape):
    """The engine-facing profile statistic: mean-over-clients total KL via
    the Pallas streaming reduction == the plain elementwise reduction
    (padding rows carry q == p == 0.5, zero KL, so the pad is exact)."""
    n, d = shape
    q = jax.random.uniform(KEY, (n, d), minval=0.05, maxval=0.95)
    p = jax.random.uniform(jax.random.fold_in(KEY, 2), (n, d),
                           minval=0.05, maxval=0.95)
    out = ops.bernoulli_kl_total(q, p, interpret=True)
    expect = jnp.mean(ref.bernoulli_kl_ref(q, p))  # mean of per-client totals
    np.testing.assert_allclose(float(out), float(expect), rtol=1e-5)


def test_logw_zero_padding_exact():
    """Padded entries contribute exactly zero -- unpadded prefix identical."""
    nb, nis, s = 2, 60, 50
    x = (jax.random.uniform(KEY, (nb, nis, s)) < 0.3).astype(jnp.float32)
    a = jax.random.normal(jax.random.fold_in(KEY, 1), (nb, s))
    b = jax.random.normal(jax.random.fold_in(KEY, 2), (nb, s))
    np.testing.assert_allclose(
        np.asarray(ops.mrc_logw(x, a, b, interpret=True)),
        np.asarray(ref.mrc_logw_ref(x, a, b)), rtol=1e-5, atol=1e-4)


def test_kernels_under_jit_and_grad_free():
    """The ops wrappers are jit-stable (no retraces explode, shapes static)."""
    x = (jax.random.uniform(KEY, (2, 64, 96)) < 0.5).astype(jnp.float32)
    a = jnp.ones((2, 96))
    b = jnp.zeros((2, 96))
    f = jax.jit(lambda x, a, b: ops.mrc_logw(x, a, b, interpret=True))
    out1 = f(x, a, b)
    out2 = f(x + 0, a, b)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
