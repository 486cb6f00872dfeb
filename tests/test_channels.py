"""Unit tests for the composable channels: bit accounting, EF invariants,
flush semantics, and a scheme combination the seed loops could not express.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blocks import FixedAllocation
from repro.core.quantizers import FLOAT_BITS, topk_bits
from repro.fl import channels as ch
from repro.fl.data import make_synthetic, partition_iid
from repro.fl.engine import EngineSpec, FLEngine, MeanModelAggregator
from repro.fl.nets import make_mlp
from repro.fl.tasks import make_mask_task

KEY = jax.random.PRNGKey(0)
N, D = 4, 96


def _ctx(n=N, d=D, active=None, size=32, n_blocks=None):
    active = np.arange(n) if active is None else np.asarray(active)
    n_blocks = -(-d // size) if n_blocks is None else n_blocks
    plan = ch.BlockPlan(size=size, n_blocks=n_blocks, seg_ids=None,
                        overhead_bits=0.0)
    return ch.RoundContext(t=0, key=KEY, n_clients=n, d=d, active=active,
                           plan=plan)


def _payload(n=N, d=D):
    return jax.random.normal(KEY, (n, d))


def _up(chan, ctx, payload, priors):
    """One uplink step from the channel's initial state."""
    state = chan.init_up_state(ctx.n_clients, ctx.d)
    return chan.step_up(ctx, state, payload, priors)


def _down(chan, ctx, update, theta, theta_hat):
    """One downlink step from the channel's initial state."""
    state = chan.init_down_state(ctx.n_clients, ctx.d)
    res, _ = chan.step_down(ctx, state, update, theta, theta_hat)
    return res


class TestMRCChannels:
    def test_fixed_uplink_bits_and_shape(self):
        ctx = _ctx()
        q = jax.random.uniform(KEY, (N, D), minval=0.2, maxval=0.8)
        p = jnp.full((N, D), 0.5)
        chan = ch.MRCFixedChannel(n_is=16, n_samples=2, shared=True)
        q_hat, bits, _ = _up(chan, ctx, q, p)
        assert q_hat.shape == (N, D)
        assert bits == N * 2 * ctx.plan.n_blocks * math.log2(16)
        # estimates are means of {0,1} samples
        assert float(q_hat.min()) >= 0.0 and float(q_hat.max()) <= 1.0

    def test_fixed_uplink_partial_cohort_bills_active_only(self):
        ctx = _ctx(active=[0, 2])
        q = jax.random.uniform(KEY, (2, D), minval=0.2, maxval=0.8)
        p = jnp.full((2, D), 0.5)
        chan = ch.MRCFixedChannel(n_is=16, n_samples=1, shared=False)
        q_hat, bits, _ = _up(chan, ctx, q, p)
        assert q_hat.shape == (2, D)
        assert bits == 2 * ctx.plan.n_blocks * math.log2(16)

    def test_private_downlink_updates_only_active(self):
        ctx = _ctx(active=[1, 3])
        theta_hat = jnp.full((N, D), 0.5)
        update = ch.ServerUpdate(theta=jax.random.uniform(KEY, (D,)))
        chan = ch.MRCPrivateDownlink(n_is=16, n_samples=2)
        res = _down(chan, ctx, update, jnp.zeros(D), theta_hat)
        assert res.bits == 2 * 2 * ctx.plan.n_blocks * math.log2(16)
        th = np.asarray(res.theta_hat)
        np.testing.assert_array_equal(th[0], 0.5 * np.ones(D))
        np.testing.assert_array_equal(th[2], 0.5 * np.ones(D))
        assert not np.array_equal(th[1], 0.5 * np.ones(D))

    def test_split_downlink_bits_divided_by_n(self):
        ctx = _ctx()
        update = ch.ServerUpdate(theta=jax.random.uniform(KEY, (D,)))
        full = ch.MRCPrivateDownlink(n_is=16, n_samples=4)
        split = ch.SplitBlockDownlink(n_is=16, n_samples=4)
        theta_hat = jnp.full((N, D), 0.5)
        rf = _down(full, ctx, update, jnp.zeros(D), theta_hat)
        rs = _down(split, ctx, update, jnp.zeros(D), theta_hat)
        # each client receives ceil(B/n) of the B blocks
        max_len = -(-ctx.plan.n_blocks // N)
        assert rs.bits == N * 4 * max_len * math.log2(16)
        assert rs.bits < rf.bits

    def test_index_relay_bits(self):
        ctx = _ctx()
        update = ch.ServerUpdate(theta=jnp.full((D,), 0.25))
        chan = ch.IndexRelayDownlink(n_is=16, n_samples=3, side_info_bits=32)
        res = _down(chan, ctx, update, jnp.zeros(D), jnp.zeros((N, D)))
        expect = N * (N - 1) * (3 * ctx.plan.n_blocks * math.log2(16) + 32)
        assert res.bits == expect
        np.testing.assert_array_equal(np.asarray(res.theta_hat),
                                      np.full((N, D), 0.25))


class TestBaselineChannels:
    def test_dense_bits(self):
        ctx = _ctx()
        out, bits, _ = _up(ch.DenseChannel(), ctx, _payload(), None)
        assert bits == N * D * FLOAT_BITS
        res = _down(ch.DenseChannel(), ctx,
                    ch.ServerUpdate(theta=jnp.ones(D)), jnp.zeros(D),
                    jnp.zeros((N, D)))
        assert res.bits == N * D * FLOAT_BITS

    @pytest.mark.parametrize("passes", [1, 2])
    def test_sign_ef_invariant_and_bits(self, passes):
        ctx = _ctx()
        chan = ch.SignEFChannel(passes=passes)
        v = _payload()
        c, bits, e = _up(chan, ctx, v, None)
        assert bits == N * passes * (D + FLOAT_BITS)
        # EF invariant: compressed + residual == input (+ zero initial memory)
        np.testing.assert_allclose(np.asarray(c + e), np.asarray(v),
                                   rtol=1e-6, atol=1e-6)

    def test_sign_ef_flush_returns_mean_residual(self):
        ctx = _ctx()
        chan = ch.SignEFChannel()
        v = _payload()
        c, _, e = _up(chan, ctx, v, None)
        resid = np.asarray(jnp.mean(v - c, axis=0))
        r, bits, e = chan.flush_step(e, N, D)
        np.testing.assert_allclose(np.asarray(r), resid, rtol=1e-6, atol=1e-6)
        assert bits == N * D * FLOAT_BITS
        # memory cleared
        np.testing.assert_array_equal(np.asarray(e), np.zeros((N, D)))

    def test_topk_ef_bits(self):
        ctx = _ctx()
        k = D // N
        chan = ch.TopKEFChannel(k=k)
        c, bits, _ = _up(chan, ctx, _payload(), None)
        assert bits == N * topk_bits(D, k)
        assert int(jnp.sum(c[0] != 0)) <= k

    def test_slice_downlink_disjoint(self):
        ctx = _ctx()
        th = jnp.arange(D, dtype=jnp.float32)
        res = _down(ch.SliceDownlink(), ctx, ch.ServerUpdate(theta=th),
                    jnp.zeros(D), jnp.full((N, D), -1.0))
        assert res.bits == N * (D / N) * FLOAT_BITS
        got = np.asarray(res.theta_hat)
        k = D // N
        for i in range(N):
            hi = D if i == N - 1 else (i + 1) * k
            np.testing.assert_array_equal(got[i, i * k:hi],
                                          np.arange(i * k, hi))
            assert np.all(got[i, :i * k] == -1.0)

    def test_ef_uplink_rejects_partial_participation(self):
        ctx = _ctx(active=[0, 1])
        with pytest.raises(ValueError):
            _up(ch.SignEFChannel(), ctx, _payload(2), None)


def test_engine_resets_ef_state_between_runs():
    """Re-running one spec must not leak error-feedback memory."""
    from repro.fl.registry import baseline_spec
    from repro.fl.tasks import make_cfl_task
    k = jax.random.PRNGKey(2)
    train, test = make_synthetic(k, n_train=160, n_test=80, hw=5, noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, 2, 80)
    net = make_mlp(in_dim=25, widths=(16,))
    task, theta0 = make_cfl_task(net, jax.random.fold_in(k, 2), test.x,
                                 test.y, local_epochs=1, batch_size=40)
    spec = baseline_spec("doublesqueeze", n=2, d=int(theta0.shape[0]))
    eng = FLEngine(task, spec)
    first = eng.run(shards, theta0, rounds=2, seed=0)
    second = eng.run(shards, theta0, rounds=2, seed=0)
    np.testing.assert_array_equal(np.asarray(first["theta"]),
                                  np.asarray(second["theta"]))
    assert first["history"] == second["history"]


class TestNovelComposition:
    """MRC uplink + sign-EF downlink: inexpressible in the seed's loops."""

    def test_mrc_up_sign_ef_down_end_to_end(self):
        k = jax.random.PRNGKey(9)
        train, test = make_synthetic(k, n_train=240, n_test=120, hw=6,
                                     noise=0.5)
        shards = partition_iid(jax.random.fold_in(k, 1), train, 3, 80)
        net = make_mlp(in_dim=36, widths=(32,), signed_constant=True)
        task = make_mask_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                              local_epochs=1, batch_size=40)
        spec = EngineSpec(
            uplink=ch.MRCFixedChannel(n_is=16, n_samples=1, shared=True),
            downlink=ch.SignEFChannel(),
            aggregator=MeanModelAggregator(),
            allocation=FixedAllocation(64),
            name="mrc-up+sign-ef-down")
        out = FLEngine(task, spec).run(shards, rounds=3, seed=0)
        assert np.isfinite(out["final_acc"])
        d = task.d
        n_blocks = -(-d // 64)
        rounds = 3
        m = out["meter"]
        # MRC uplink bits + sign-EF downlink bits, both exact
        assert m["uplink_bpp"] * (3 * d * rounds) == pytest.approx(
            3 * n_blocks * math.log2(16) * rounds)
        assert m["downlink_bpp"] * (3 * d * rounds) == pytest.approx(
            3 * (d + FLOAT_BITS) * rounds)
