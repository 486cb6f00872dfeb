"""Segment-logW Pallas kernel: interpret-mode parity vs the jnp route.

The kernel (``repro.kernels.segment_logw``) must emit the same
(n_is, n_seg) weight matrix as ``repro.core.mrc.default_segment_logw``
(vmapped ``segment_sum``) up to f32 grouping order -- over arbitrary
segmentations including the degenerate single-segment and all-singleton
shapes -- and the pluggable ``seg_logw_fn`` hook must leave the
``encode_segments`` output unchanged end to end.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import mrc
from repro.core.bernoulli import bern_kl, clip01, log_ratio_coeffs
from repro.kernels import ops
from repro.kernels.segment_logw import (NSEG_LANE, TILE_D, TILE_I,
                                        segment_logw_pallas)

KEY = jax.random.PRNGKey(0)


def _random_segmentation(rng, d):
    """A random non-decreasing segmentation of [0, d): (seg_ids, n_seg)."""
    n_cuts = int(rng.integers(0, d))
    if d > 1 and n_cuts:
        cuts = np.sort(rng.choice(np.arange(1, d), size=min(n_cuts, d - 1),
                                  replace=False))
    else:
        cuts = np.array([], dtype=np.int64)
    lengths = np.diff(np.concatenate([[0], cuts, [d]]))
    seg = np.repeat(np.arange(lengths.size), lengths)
    return jnp.asarray(seg, jnp.int32), lengths.size


def _inputs(seed, n_is, d):
    k = jax.random.fold_in(KEY, seed)
    k1, k2, k3 = jax.random.split(k, 3)
    u = mrc._segment_candidates(k1, n_is, d)
    q = clip01(jax.random.uniform(k2, (d,), minval=0.02, maxval=0.98))
    p = clip01(jax.random.uniform(k3, (d,), minval=0.02, maxval=0.98))
    a, b = log_ratio_coeffs(q, p)
    return u, p, a, b


def _assert_parity(n_is, d, seg, n_seg, seed=0):
    u, p, a, b = _inputs(seed, n_is, d)
    ref = mrc.default_segment_logw(u, p, a, b, seg, n_seg)
    out = ops.segment_logw(u, p, a, b, seg, n_seg=n_seg, interpret=True)
    assert out.shape == (n_is, n_seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


class TestKernelParity:
    @given(st.integers(0, 10**6), st.integers(1, 150), st.integers(1, 200))
    @settings(max_examples=10, deadline=None)
    def test_matches_jnp_route(self, seed, n_is, d):
        seg, n_seg = _random_segmentation(np.random.default_rng(seed), d)
        _assert_parity(n_is, d, seg, n_seg, seed=seed)

    def test_single_segment(self):
        d = 70
        _assert_parity(12, d, jnp.zeros((d,), jnp.int32), 1, seed=1)

    def test_all_singletons(self):
        d = 40
        _assert_parity(12, d, jnp.arange(d, dtype=jnp.int32), d, seed=2)

    @pytest.mark.parametrize("d, lengths", [
        # many short segments: one d-tile spans several segment windows
        (3 * TILE_D + 70, None),
        # few long segments: one window spans several d-tiles
        (3 * TILE_D + 70, [TILE_D + 5, 2 * TILE_D, 65]),
        # empty segments: ids jump past whole windows mid d-tile
        (2 * TILE_D, [300, 0, 0, 112] + [0] * (2 * NSEG_LANE) + [612]),
    ])
    def test_staircase_over_tiles_and_windows(self, d, lengths):
        if lengths is None:
            seg = np.arange(d) // 3
        else:
            assert sum(lengths) == d
            seg = np.repeat(np.arange(len(lengths)), lengths)
        n_seg = int(seg[-1]) + 1
        assert n_seg > NSEG_LANE or d > 2 * TILE_D
        _assert_parity(8, d, jnp.asarray(seg, jnp.int32), n_seg, seed=4)

    def test_tile_aligned_no_padding(self):
        # exercise the raw kernel entry point without the ops padding wrapper
        n_is, d, n_seg = TILE_I, 2 * TILE_D, NSEG_LANE
        seg = jnp.asarray(np.repeat(np.arange(n_seg), d // n_seg), jnp.int32)
        u, p, a, b = _inputs(3, n_is, d)
        ref = mrc.default_segment_logw(u, p, a, b, seg, n_seg)
        out = segment_logw_pallas(u, p[None], a[None], b[None], seg[None],
                                  n_seg=n_seg, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)


class TestEncodeSegmentsEndToEnd:
    """The pluggable hook reproduces the default route bit-for-bit at these
    fixed seeds: the two logW evaluations differ only by f32 grouping
    order, far below the Gumbel-argmax gaps at these sizes."""

    SEG = np.repeat(np.arange(5), [10, 2, 40, 30, 14])

    def _keys(self):
        k = jax.random.fold_in(KEY, 99)
        ks, kq, kp, ksel = jax.random.split(k, 4)
        q = clip01(jax.random.uniform(kq, (96,)))
        p = clip01(jax.random.uniform(kp, (96,)))
        return ks, ksel, q, p

    def test_encode_matches_default(self):
        ks, ksel, q, p = self._keys()
        seg = jnp.asarray(self.SEG, jnp.int32)
        r0 = mrc.encode_segments(ks, ksel, q, p, seg, n_is=32, n_seg=5)
        r1 = mrc.encode_segments(ks, ksel, q, p, seg, n_is=32, n_seg=5,
                                 seg_logw_fn=ops.segment_logw_fn())
        np.testing.assert_array_equal(np.asarray(r0.indices),
                                      np.asarray(r1.indices))
        np.testing.assert_array_equal(np.asarray(r0.sample),
                                      np.asarray(r1.sample))

    def test_transmit_matches_default(self):
        ks, ksel, q, p = self._keys()
        seg = jnp.asarray(self.SEG, jnp.int32)
        i0, e0 = mrc.transmit_segments(ks, ksel, q, p, seg, n_is=16, n_seg=5,
                                       n_samples=3)
        i1, e1 = mrc.transmit_segments(ks, ksel, q, p, seg, n_is=16, n_seg=5,
                                       n_samples=3,
                                       seg_logw_fn=ops.segment_logw_fn())
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))


class TestBernoulliKLProfile:
    def test_matches_host_mean(self):
        kq, kp = jax.random.split(jax.random.fold_in(KEY, 5))
        q = jax.random.uniform(kq, (5, 700), minval=0.01, maxval=0.99)
        p = jax.random.uniform(kp, (5, 700), minval=0.01, maxval=0.99)
        ref = jnp.mean(jax.vmap(bern_kl)(q, p), axis=0)
        out = ops.bernoulli_kl_profile(q, p, interpret=True)
        assert out.shape == (700,)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-6)


class TestShapePreconditions:
    """Tile-alignment misuse raises ValueError (not a stripped assert)."""

    def test_segment_logw_pallas(self):
        u, p, a, b = _inputs(0, 8, 40)
        seg = jnp.zeros((40,), jnp.int32)
        with pytest.raises(ValueError, match="segment_logw_pallas"):
            segment_logw_pallas(u, p[None], a[None], b[None], seg[None],
                                n_seg=NSEG_LANE, interpret=True)

    def test_bernoulli_kl_pallas(self):
        from repro.kernels.bernoulli_kl import bernoulli_kl_pallas
        bad = jnp.full((2, 100), 0.5)
        with pytest.raises(ValueError, match="bernoulli_kl_pallas"):
            bernoulli_kl_pallas(bad, bad, interpret=True)

    def test_mrc_logw_pallas(self):
        from repro.kernels.mrc_weights import mrc_logw_pallas
        with pytest.raises(ValueError, match="mrc_logw_pallas"):
            mrc_logw_pallas(jnp.zeros((1, 100, 128)), jnp.zeros((1, 128)),
                            jnp.zeros((1, 128)), interpret=True)

    def test_rwkv_chunk_pallas(self):
        from repro.kernels.rwkv_chunk import rwkv_chunk_pallas
        t = jnp.zeros((1, 5, 128))
        with pytest.raises(ValueError, match="rwkv_chunk_pallas"):
            rwkv_chunk_pallas(t, t, t, t, jnp.zeros((1, 1, 128)),
                              interpret=True)

    def test_flash_attention_pallas(self):
        from repro.kernels.flash_attn import flash_attention_pallas
        q = jnp.zeros((1, 5, 128))
        kv = jnp.zeros((1, 128, 128))
        with pytest.raises(ValueError, match="flash_attention_pallas"):
            flash_attention_pallas(q, kv, kv, causal=True, window=0,
                                   scale=1.0, skv=128, interpret=True)
