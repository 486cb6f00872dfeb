"""Fused-vs-host engine parity: the device-resident ``lax.scan`` path must
reproduce the host round loop **bit-for-bit** -- identical histories
(accuracy floats, cumulative bits), meters, and final ``theta`` /
``theta_hat`` arrays, exact equality with no tolerances.

Covers every registry scheme with a static block plan (all four BiCompFL
variants, BiCompFL-CFL, the seven baselines incl. the CSER/LIEC flush
path), full and partial participation, both cohort RNGs, and non-unit eval
cadence.

Adaptive allocations run fused through *bucketed* plans (``lax.switch``
over precompiled block sets, KL profile computed on device), so the host
loop's exact per-round plan is the parity *oracle* rather than a bitwise
twin: accuracy must agree within tolerance and total bits must respect the
bucketing bound (conservative: never above the exact plan's budget plus the
allocation's declared ``bucket_overhead_bits``).  When the bucket set
contains the exact plan -- always true for AdaptiveAvg, whose buckets *are*
its pow2 plan space, and arranged via ``buckets=`` for the segment codec --
parity is again exact.
"""
import math
import re

import jax
import numpy as np
import pytest

from repro.core.blocks import (AdaptiveAllocation, AdaptiveAvgAllocation,
                               FixedAllocation)
from repro.fl import registry
from repro.fl.data import make_synthetic, partition_iid
from repro.fl.engine import FLEngine
from repro.fl.nets import make_mlp, make_vit
from repro.fl.tasks import make_cfl_task, make_mask_task

SCHEMES = registry.all_schemes(n=3, d=1472, n_is=16, block=64, reset_period=2)
# A small ViT (hw 16, patch 4, width 32, 4 heads, mlp 64, depth 2) under GR.
VIT_ARGS = dict(hw=16, channels=3, patch=4, width=32, depth=2, heads=4,
                mlp_width=64, n_classes=10)
VIT_GR = ("vit-bicompfl-gr", "vit",
          lambda: registry.bicompfl_spec("GR", allocation=FixedAllocation(64),
                                         n_is=16))
SETUPS = {"mask": "mask_setup", "vit": "vit_setup"}


@pytest.fixture(scope="module")
def mask_setup():
    k = jax.random.PRNGKey(3)
    train, test = make_synthetic(k, n_train=240, n_test=120, hw=6, noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, 3, 80)
    net = make_mlp(in_dim=36, widths=(32,), signed_constant=True)
    task = make_mask_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                          local_epochs=1, batch_size=40)
    return task, shards


@pytest.fixture(scope="module")
def vit_setup():
    k = jax.random.PRNGKey(5)
    train, test = make_synthetic(k, n_train=96, n_test=40, hw=16, channels=3,
                                 noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, 3, 32)
    net = make_vit(**VIT_ARGS, signed_constant=True)
    task = make_mask_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                          local_epochs=1, batch_size=16)
    return task, shards


@pytest.fixture(scope="module")
def cfl_setup():
    k = jax.random.PRNGKey(4)
    train, test = make_synthetic(k, n_train=240, n_test=120, hw=6, noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, 3, 80)
    net = make_mlp(in_dim=36, widths=(32,))
    task, theta0 = make_cfl_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                                 local_epochs=2, batch_size=40, local_lr=3e-3)
    assert int(theta0.shape[0]) == 1472  # keep SCHEMES' d in sync
    return task, theta0, shards


def _assert_identical(host, fused):
    assert len(host["history"]) == len(fused["history"])
    for hh, hf in zip(host["history"], fused["history"]):
        for key in hh:
            assert hf[key] == hh[key], (key, hh, hf)
    for key in host["meter"]:
        assert fused["meter"][key] == host["meter"][key], key
    np.testing.assert_array_equal(np.asarray(host["theta"]),
                                  np.asarray(fused["theta"]))
    np.testing.assert_array_equal(np.asarray(host["theta_hat"]),
                                  np.asarray(fused["theta_hat"]))
    np.testing.assert_array_equal(host["active_schedule"],
                                  fused["active_schedule"])
    assert fused["final_acc"] == host["final_acc"]
    assert fused["max_acc"] == host["max_acc"]


def _run_both(task, spec_factory, shards, theta0=None, *, rounds=3, seed=11,
              **kw):
    host = FLEngine(task, spec_factory()).run(
        shards, theta0, rounds=rounds, seed=seed, mode="host", **kw)
    fused = FLEngine(task, spec_factory()).run(
        shards, theta0, rounds=rounds, seed=seed, mode="fused", **kw)
    _assert_identical(host, fused)
    return host


@pytest.mark.parametrize("name,kind,factory", SCHEMES + [VIT_GR],
                         ids=[s[0] for s in SCHEMES + [VIT_GR]])
def test_fused_matches_host(request, name, kind, factory):
    if kind in SETUPS:
        task, shards = request.getfixturevalue(SETUPS[kind])
        _run_both(task, factory, shards)
    else:
        task, theta0, shards = request.getfixturevalue("cfl_setup")
        # reset_period=2 inside 3 rounds exercises the lax.cond flush branch
        _run_both(task, factory, shards, theta0)


@pytest.mark.parametrize("cohort_rng", ["numpy", "jax"])
def test_fused_partial_participation(mask_setup, cohort_rng):
    task, shards = mask_setup
    factory = lambda: registry.bicompfl_spec(
        "PR", allocation=FixedAllocation(64), n_is=16, n_dl=3,
        participation=0.67)
    out = _run_both(task, factory, shards, rounds=3, cohort_rng=cohort_rng)
    assert out["active_schedule"].shape == (3, 2)  # 0.67 of 3 -> 2 active


def test_fused_eval_cadence(mask_setup):
    """lax.cond-gated eval: only scheduled rounds (plus the last) appear."""
    task, shards = mask_setup
    factory = lambda: registry.bicompfl_spec(
        "GR", allocation=FixedAllocation(64), n_is=16, n_dl=3)
    out = _run_both(task, factory, shards, rounds=3, eval_every=2)
    assert [h["round"] for h in out["history"]] == [2, 3]


class _ProbedAdaptive(AdaptiveAllocation):
    """Records each exact host plan's *requested* block count -- the value
    ``select_bucket`` floors onto the grid -- to build exact bucket sets.

    Exact fused-vs-host parity is only constructible when no duplicate
    binning edges collapse (the host gumbel capacity is the post-collapse
    count while a switch branch's capacity is static), so the probe
    asserts the premise loudly instead of letting a future fp change
    surface as an inscrutable bit mismatch."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.planned = []

    def plan(self, kl, d):
        out = super().plan(kl, d)
        if kl is not None:
            total = float(np.sum(kl)) + 1e-12
            target = self.target_ratio * math.log(self.n_is)
            requested = min(self._cap(d),
                            max(self.min_blocks, math.ceil(total / target)))
            assert requested == out[1], \
                "binning edges collapsed; exact-parity premise broken"
            self.planned.append(requested)
        return out


def _run_adaptive_pair(task, shards, make_alloc, *, rounds=3, seed=11,
                       variant="GR", **kw):
    """Host (exact plan) vs fused (bucketed plan) for an adaptive scheme."""
    n = int(shards.x.shape[0])
    host = FLEngine(task, registry.bicompfl_spec(
        variant, allocation=make_alloc(), n_is=16, n_dl=n, **kw)).run(
        shards, rounds=rounds, seed=seed, mode="host")
    fused = FLEngine(task, registry.bicompfl_spec(
        variant, allocation=make_alloc(), n_is=16, n_dl=n, **kw)).run(
        shards, rounds=rounds, seed=seed, mode="fused")
    return host, fused


def test_adaptive_fused_supported_no_fallback(mask_setup):
    """The PR 2 host auto-fallback is gone: adaptive allocations are fused-
    eligible, mode="fused" runs them, and mode="auto" picks the fused path."""
    task, shards = mask_setup
    spec = registry.bicompfl_spec("GR", allocation=AdaptiveAllocation(n_is=16),
                                  n_is=16, n_dl=3)
    engine = FLEngine(task, spec)
    assert engine.fused_supported()
    auto = engine.run(shards, rounds=2, seed=11, mode="auto")
    assert auto["mode"] == "fused"
    fused = engine.run(shards, rounds=2, seed=11, mode="fused")
    _assert_identical(fused, auto)


def test_non_functional_channel_still_host_only(mask_setup):
    """A channel without the explicit-state protocol is refused by ``run``
    in every mode, naming what it lacks; only an allocation exposing
    neither a static plan nor the bucket API forces the host loop."""
    task, shards = mask_setup

    class StatefulOnlyDownlink:  # no init_down_state/step_down/flush_step
        broadcast_shareable = True

        def distribute(self, ctx, update, theta, theta_hat):
            raise NotImplementedError

    spec = registry.bicompfl_spec("GR", allocation=FixedAllocation(64),
                                  n_is=16, n_dl=3)
    spec.downlink = StatefulOnlyDownlink()
    for mode in ("auto", "host", "fused"):
        with pytest.raises(ValueError, match=r"downlink\.step_down"):
            FLEngine(task, spec).run(shards, rounds=1, seed=1, mode=mode)

    class NoBucketAdaptive:  # data-dependent plan without the bucket API
        static_plan = False
        needs_kl = True

        def plan(self, kl, d):
            return 64, -(-d // 64), None, 0.0

    spec2 = registry.bicompfl_spec("GR", allocation=FixedAllocation(64),
                                   n_is=16, n_dl=3)
    spec2.allocation = NoBucketAdaptive()
    engine2 = FLEngine(task, spec2)
    assert not engine2.fused_supported()
    with pytest.raises(ValueError):
        engine2.run(shards, rounds=1, seed=1, mode="fused")


def test_fused_adaptive_avg_exact_parity(mask_setup):
    """AdaptiveAvg's bucket set IS its pow2 plan space, so the fused bucketed
    run reproduces the host exact-plan run bit-for-bit (bits included)."""
    task, shards = mask_setup
    host, fused = _run_adaptive_pair(
        task, shards,
        lambda: AdaptiveAvgAllocation(n_is=16, min_block=32, max_block=512))
    assert fused["mode"] == "fused" and host["mode"] == "host"
    _assert_identical(host, fused)


def test_fused_adaptive_exact_bucket_contains_plan(mask_setup):
    """Segment codec: when the bucket set contains every exact per-round
    block count, the fused run is bit-identical to the host oracle."""
    task, shards = mask_setup
    probe = _ProbedAdaptive(n_is=16, target_ratio=0.02)
    host = FLEngine(task, registry.bicompfl_spec(
        "GR", allocation=probe, n_is=16, n_dl=3)).run(
        shards, rounds=3, seed=11, mode="host")
    assert len(set(probe.planned)) > 1  # the plan really moves across rounds
    fused = FLEngine(task, registry.bicompfl_spec(
        "GR", allocation=AdaptiveAllocation(
            n_is=16, target_ratio=0.02, buckets=tuple(probe.planned)),
        n_is=16, n_dl=3)).run(shards, rounds=3, seed=11, mode="fused")
    _assert_identical(host, fused)


def test_fused_adaptive_bucketing_bound(mask_setup):
    """Default (geometric) buckets: accuracy stays within tolerance of the
    exact-plan host oracle.  Bits: the conservativeness guarantee is
    per-round-for-the-same-KL-profile (tests/test_allocation.py pins it),
    so only round 1 -- where both trajectories share the initial state --
    gets the strict inequality; after that the trajectories drift and the
    whole run is held to a band, exactly like the benchmark oracle."""
    task, shards = mask_setup
    make_alloc = lambda: AdaptiveAllocation(n_is=16, target_ratio=0.02)
    host, fused = _run_adaptive_pair(task, shards, make_alloc)
    accs_h = np.array([h["acc"] for h in host["history"]])
    accs_f = np.array([h["acc"] for h in fused["history"]])
    np.testing.assert_allclose(accs_f, accs_h, atol=0.2)
    bound = make_alloc().bucket_overhead_bits  # declared, per round
    assert fused["history"][0]["cum_bits"] <= \
        host["history"][0]["cum_bits"] + bound  # round 1: same KL profile
    ratio = fused["meter"]["total_bits"] / host["meter"]["total_bits"]
    assert 0.4 <= ratio <= 2.0


@pytest.mark.parametrize("cohort_rng", ["numpy", "jax"])
def test_fused_adaptive_partial_participation(mask_setup, cohort_rng):
    """PR + segment codec under partial participation: the KL profile and
    the bucketed plan are derived from the active cohort only, on device.
    With the probed exact bucket set the fused run must again be
    bit-identical to the host oracle -- under both cohort RNGs."""
    task, shards = mask_setup
    probe = _ProbedAdaptive(n_is=16, target_ratio=0.02)
    host = FLEngine(task, registry.bicompfl_spec(
        "PR", allocation=probe, n_is=16, n_dl=3,
        participation=0.67)).run(
        shards, rounds=3, seed=11, mode="host", cohort_rng=cohort_rng)
    fused = FLEngine(task, registry.bicompfl_spec(
        "PR", allocation=AdaptiveAllocation(
            n_is=16, target_ratio=0.02, buckets=tuple(probe.planned)),
        n_is=16, n_dl=3, participation=0.67)).run(
        shards, rounds=3, seed=11, mode="fused", cohort_rng=cohort_rng)
    assert fused["mode"] == "fused"
    assert fused["active_schedule"].shape == (3, 2)  # 0.67 of 3 -> 2 active
    _assert_identical(host, fused)


def test_fixed_allocation_auto_uses_fused(mask_setup):
    task, shards = mask_setup
    engine = FLEngine(task, registry.bicompfl_spec(
        "GR", allocation=FixedAllocation(64), n_is=16, n_dl=3))
    assert engine.fused_supported()


# -- no task array is compiled into the fused program -------------------------

class _Lowered(Exception):
    """Carries the fused program's StableHLO out of ``FLEngine.run``."""


def _largest_constant_bytes(text: str) -> int:
    """Bytes of the largest non-splat constant literal in StableHLO text."""
    best = 0
    for payload, ty in re.findall(
            r"stablehlo\.constant dense<(.)[^>]*> : tensor<([^>]*)>", text):
        if payload not in ('"', "["):  # a splat: one value, broadcast
            continue
        *dims, dtype = ty.split("x")
        bits = int(re.search(r"(\d+)$", dtype).group(1))
        best = max(best, math.prod(int(x) for x in dims) * max(bits // 8, 1))
    return best


def _fused_program_text(task, spec, shards, theta0=None) -> str:
    engine = FLEngine(task, spec)
    build = engine._build_fused

    def lower_only(**kw):
        fn, booked = build(**kw)

        def runner(*args):
            raise _Lowered(fn.lower(*args).as_text())
        return runner, booked

    engine._build_fused = lower_only
    with pytest.raises(_Lowered) as caught:
        engine.run(shards, theta0, rounds=2, seed=0, mode="fused")
    return str(caught.value)


MIB = 2 ** 20


@pytest.mark.parametrize("case", ["mlp-gr", "mlp-fedavg", "vit-gr"])
def test_fused_program_holds_no_large_constant(case):
    """The fixed weights and the test set reach ``fl_rounds`` as arguments:
    each exceeds 1 MiB here, and no constant of the lowered program does."""
    k = jax.random.PRNGKey(9)
    if case.startswith("vit"):
        hw, channels, n_test = 16, 3, 1200
        net = make_vit(**VIT_ARGS, signed_constant=True)
    else:
        hw, channels, n_test = 28, 1, 512
        net = make_mlp(in_dim=784, widths=(400,), signed_constant=True)
    train, test = make_synthetic(k, n_train=64, n_test=n_test, hw=hw,
                                 channels=channels)
    shards = partition_iid(jax.random.fold_in(k, 1), train, 3, 16)
    assert test.x.nbytes > MIB
    if case == "mlp-fedavg":
        task, theta0 = make_cfl_task(net, jax.random.fold_in(k, 2), test.x,
                                     test.y, local_epochs=1, batch_size=16)
        spec = registry.baseline_spec("fedavg", n=3, d=int(theta0.shape[0]))
    else:
        task, theta0 = make_mask_task(net, jax.random.fold_in(k, 2), test.x,
                                      test.y, local_epochs=1,
                                      batch_size=16), None
        spec = registry.bicompfl_spec("GR", allocation=FixedAllocation(64),
                                      n_is=16)
        if case == "mlp-gr":
            assert task.w0_flat.nbytes > MIB
    text = _fused_program_text(task, spec, shards, theta0)
    assert "fl_rounds" in text
    assert _largest_constant_bytes(text) <= MIB


def test_largest_constant_bytes_reads_literals():
    text = ('%0 = stablehlo.constant dense<"0x0000"> : tensor<512x1024xf32>\n'
            '%1 = stablehlo.constant dense<1.0> : tensor<4096x4096xf32>\n'
            '%2 = stablehlo.constant dense<[1, 2]> : tensor<2xi32>\n')
    assert _largest_constant_bytes(text) == 512 * 1024 * 4
