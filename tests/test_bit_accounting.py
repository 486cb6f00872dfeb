"""Property-based bit-accounting suite over the whole channel matrix.

DoCoFL-style bi-directional compression papers live and die by exact bit
bookkeeping per direction, so the accounting invariants are pinned here for
**every** channel in ``registry.all_schemes`` (static and adaptive):

* bits are non-negative, finite Python floats under a host (static) plan;
* the functional core and the object shell report identical bits
  (``step_up``/``step_down`` vs ``transmit``/``distribute``);
* bits are additive across rounds, and ``BitMeter.book_run`` records
  exactly what the per-round channel reports sum to (== an ``add_round``
  loop, including per-round overhead sequences);
* bits are invariant to cohort permutation (and, for cohort-sized
  formulas, to *which* equally-sized cohort participates);
* a traced bucketed plan (``finalize_plan``) yields the same bits value as
  the host plan with the same billable block count -- the traced-bits
  contract degrades representation, never value.
"""
import math

from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bernoulli import bern_kl, clip01
from repro.core.bitmeter import BitMeter
from repro.core.blocks import AdaptiveAllocation
from repro.fl import registry
from repro.fl.channels import BlockPlan, RoundContext

N, D = 3, 96
SCHEMES = registry.all_schemes(n=N, d=D, n_is=8, block=32, reset_period=2,
                               include_adaptive=True)
SCHEME_IDS = [s[0] for s in SCHEMES]


def _round_inputs(kind: str, key: int = 0):
    rng = np.random.default_rng(key)
    if kind == "mask":
        payload = jnp.asarray(rng.uniform(0.05, 0.95, (N, D)), jnp.float32)
        priors = jnp.asarray(rng.uniform(0.05, 0.95, (N, D)), jnp.float32)
        theta = jnp.asarray(rng.uniform(0.05, 0.95, D), jnp.float32)
    else:
        payload = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
        priors = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
        theta = jnp.asarray(rng.standard_normal(D), jnp.float32)
    return payload, priors, theta


def _host_plan(spec, payload, priors):
    if spec.allocation is None:
        return None
    kl = None
    if getattr(spec.allocation, "needs_kl", True):
        kl = np.asarray(jnp.mean(jax.vmap(bern_kl)(payload, clip01(priors)),
                                 axis=0))
    size, n_blocks, seg_ids, overhead = spec.allocation.plan(kl, D)
    return BlockPlan(size=size, n_blocks=n_blocks, seg_ids=seg_ids,
                     overhead_bits=overhead)


def _ctx(spec, payload, priors, active=None):
    plan = _host_plan(spec, payload, priors)
    active = np.arange(N) if active is None else np.asarray(active)
    return RoundContext(t=0, key=jax.random.PRNGKey(7), n_clients=N, d=D,
                        active=active, plan=plan)


def _one_round(spec, ctx, payload, priors, theta):
    """Functional-core round; returns (ul_bits, dl_bits, shell bits pair)."""
    up_s = spec.uplink.init_up_state(N, D)
    up_out, ul_bits, _ = spec.uplink.step_up(ctx, up_s, payload, priors)
    update = spec.aggregator(ctx, theta, up_out)
    theta_hat = jnp.tile(theta[None], (N, 1))
    dn_s = spec.downlink.init_down_state(N, D)
    res, _ = spec.downlink.step_down(ctx, dn_s, update, theta, theta_hat)

    # object shell must report the identical bits
    for chan in (spec.uplink, spec.downlink):
        reset = getattr(chan, "reset", None)
        if reset is not None:
            reset()
    _, ul_shell = spec.uplink.transmit(ctx, payload, priors)
    res_shell = spec.downlink.distribute(ctx, update, theta, theta_hat)
    return ul_bits, res.bits, ul_shell, res_shell.bits


@pytest.mark.parametrize("name,kind,factory", SCHEMES, ids=SCHEME_IDS)
def test_bits_nonneg_finite_static_float(name, kind, factory):
    spec = factory()
    payload, priors, theta = _round_inputs(kind)
    ctx = _ctx(spec, payload, priors)
    ul, dl, ul_shell, dl_shell = _one_round(spec, ctx, payload, priors, theta)
    for b in (ul, dl):
        # host-plan contract: a plain, data-independent Python number
        assert isinstance(b, (int, float)), (name, type(b))
        assert math.isfinite(b) and b >= 0.0, (name, b)
    assert ul_shell == ul and dl_shell == dl, name
    if ctx.plan is not None:
        oh = float(ctx.plan.overhead_bits)
        assert math.isfinite(oh) and oh >= 0.0


@pytest.mark.parametrize("name,kind,factory", SCHEMES, ids=SCHEME_IDS)
def test_bits_invariant_to_cohort_permutation(name, kind, factory):
    spec = factory()
    payload, priors, theta = _round_inputs(kind)
    ul0, dl0, *_ = _one_round(spec, _ctx(spec, payload, priors),
                              payload, priors, theta)
    perm = np.array([2, 0, 1])
    ul1, dl1, *_ = _one_round(spec, _ctx(spec, payload, priors, active=perm),
                              payload[perm], priors[perm], theta)
    assert ul1 == ul0 and dl1 == dl0, name


@pytest.mark.parametrize("name,kind,factory", SCHEMES, ids=SCHEME_IDS)
def test_bits_additive_and_book_run_matches_steps(name, kind, factory):
    """Booking the per-round channel reports through BitMeter.book_run must
    equal an add_round loop and the plain sums -- for every scheme."""
    spec = factory()
    rounds = []
    for r in range(3):
        payload, priors, theta = _round_inputs(kind, key=r)
        ctx = _ctx(spec, payload, priors)
        ul, dl, *_ = _one_round(spec, ctx, payload, priors, theta)
        oh = float(ctx.plan.overhead_bits) * N if ctx.plan is not None else 0.0
        rounds.append((ul, dl, oh))
    uls, dls, ohs = map(list, zip(*rounds))

    bulk = BitMeter(n_clients=N, d=D)
    snaps = bulk.book_run(uls, dls, overhead_bits=ohs)
    loop = BitMeter(n_clients=N, d=D)
    for u, dl_, oh in rounds:
        loop.add_round(u, dl_, overhead_bits=oh)
    assert bulk.summary() == loop.summary(), name
    assert bulk.total_bits == sum(uls) + sum(dls) + sum(ohs), name
    assert bulk.uplink_bits == sum(uls) + sum(ohs), name
    assert bulk.downlink_bits == sum(dls), name
    # per-round history mirrors what was booked, cumulatively
    assert [h["cum_bits"] for h in bulk.history] == [s[0] for s in snaps]
    assert bulk.rounds == 3


def test_flush_bits_nonneg_finite():
    """EF flush bills a dense sync; the report must be a finite float."""
    for name, kind, factory in SCHEMES:
        spec = factory()
        if not spec.sync_period:
            continue
        for chan, state in ((spec.uplink, spec.uplink.init_up_state(N, D)),
                            (spec.downlink,
                             spec.downlink.init_down_state(N, D))):
            _, bits, _ = chan.flush_step(state, N, D)
            assert isinstance(bits, (int, float))
            assert math.isfinite(bits) and bits >= 0.0, name


def test_traced_bucketed_bits_equal_host_bits():
    """A finalize_plan-built (traced) plan with the same billable count must
    produce the same bits *value* as the host plan -- only the
    representation (jnp scalar vs Python float) may differ."""
    spec = registry.bicompfl_spec("GR", allocation=AdaptiveAllocation(n_is=8),
                                  n_is=8, n_dl=N)
    payload, priors, theta = _round_inputs("mask")
    ctx = _ctx(spec, payload, priors)
    host_plan = ctx.plan
    alloc = spec.allocation
    klp = jnp.mean(jax.vmap(bern_kl)(payload, clip01(priors)), axis=0)
    stats = {"profile": klp, "total": jnp.sum(klp)}
    tmpl = BlockPlan(size=None, n_blocks=host_plan.n_blocks, seg_ids=None,
                     overhead_bits=0.0)
    traced_plan = alloc.finalize_plan(tmpl, stats, D)
    assert int(traced_plan.billable) == host_plan.billable

    ctx_traced = RoundContext(t=0, key=jax.random.PRNGKey(7), n_clients=N,
                              d=D, active=np.arange(N), plan=traced_plan)
    _, bits_host, _ = spec.uplink.step_up(
        ctx, spec.uplink.init_up_state(N, D), payload, priors)
    _, bits_traced, _ = spec.uplink.step_up(
        ctx_traced, spec.uplink.init_up_state(N, D), payload, priors)
    assert isinstance(bits_host, float)
    assert isinstance(bits_traced, jnp.ndarray)  # the traced representation
    assert float(bits_traced) == bits_host
    assert float(traced_plan.overhead_bits) == float(host_plan.overhead_bits)


def test_fused_traced_bits_overflow_guard():
    """Traced per-round bits above the f32 integer-exact bound (2**24) must
    raise loudly instead of booking silently-rounded totals."""
    import jax as _jax
    from repro.fl.channels import IndexRelayDownlink
    from repro.fl.data import make_synthetic, partition_iid
    from repro.fl.engine import FLEngine
    from repro.fl.nets import make_mlp
    from repro.fl.tasks import make_mask_task

    k = _jax.random.PRNGKey(0)
    train, test = make_synthetic(k, n_train=60, n_test=30, hw=4, noise=0.5)
    shards = partition_iid(_jax.random.fold_in(k, 1), train, 3, 20)
    net = make_mlp(in_dim=16, widths=(8,), signed_constant=True)
    task = make_mask_task(net, _jax.random.fold_in(k, 2), test.x, test.y,
                          local_epochs=1, batch_size=20)
    spec = registry.bicompfl_spec("GR", allocation=AdaptiveAllocation(n_is=8),
                                  n_is=8, n_dl=3)
    spec.downlink = IndexRelayDownlink(n_is=8, side_info_bits=2.0 ** 25)
    with pytest.raises(OverflowError):
        FLEngine(task, spec).run(shards, rounds=1, seed=0, mode="fused")


class TestBitMeterProperties:
    @settings(max_examples=8)
    @given(st.floats(min_value=0.0, max_value=1e9),
           st.floats(min_value=0.0, max_value=1e9),
           st.floats(min_value=0.0, max_value=1e6),
           st.integers(min_value=1, max_value=12))
    def test_book_run_additivity(self, ul, dl, oh, rounds):
        m = BitMeter(n_clients=N, d=D)
        m.book_run([ul] * rounds, [dl] * rounds, overhead_bits=oh)
        assert m.rounds == rounds
        np.testing.assert_allclose(m.total_bits, (ul + dl + oh) * rounds,
                                   rtol=1e-12)
        np.testing.assert_allclose(
            m.total_bpp, m.total_bits / (N * D * rounds), rtol=1e-12)

    @settings(max_examples=8)
    @given(st.integers(min_value=1, max_value=10))
    def test_book_run_order_independent_totals(self, rounds):
        """Totals are permutation-invariant in the round order (additivity:
        the meter is a running sum, not an order-sensitive statistic)."""
        rng = np.random.default_rng(rounds)
        uls = list(rng.uniform(0, 1e6, rounds))
        dls = list(rng.uniform(0, 1e6, rounds))
        a = BitMeter(n_clients=N, d=D)
        a.book_run(uls, dls)
        b = BitMeter(n_clients=N, d=D)
        b.book_run(uls[::-1], dls[::-1])
        np.testing.assert_allclose(a.total_bits, b.total_bits, rtol=1e-12)
        assert a.rounds == b.rounds
