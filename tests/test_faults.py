"""Fault-injection suite: deterministic schedules, degraded-cohort math,
wire integrity under corruption, retransmit accounting, crash-safe resume.

Pins of DESIGN.md §8 ("Fault model"):

* same fault seed => the identical fault schedule, independent of the
  other rates, and the identical trajectory in ``mode="host"`` and
  ``mode="fused"``;
* a ``FaultPlan`` that draws no fault is **bit-identical** to
  ``faults=None`` for every registry scheme (the legacy code path);
* the CRC-32 trailer catches *every* single-bit flip of a frame;
* retransmitted bits booked by the engine reconcile exactly against the
  wasted bytes on the wire stream;
* a run killed at a checkpoint and resumed is bit-identical to the
  uninterrupted run (host and fused, clean and faulted);
* the staged host loop does not re-trace its round computation per round.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.blocks import FixedAllocation
from repro.fl import registry
from repro.fl.data import make_synthetic, partition_iid
from repro.fl.engine import FLEngine, _cohort_mean
from repro.fl.faults import FaultPlan, corrupt_copy
from repro.fl.nets import make_mlp
from repro.fl.tasks import make_cfl_task, make_mask_task
from repro.wire.frame import DIR_UP, Message, WireError

N, D = 4, 208
SCHEMES = registry.all_schemes(n=N, d=D, n_is=16, block=16, reset_period=2)
FAULT_MATRIX = registry.fault_matrix(n=N, d=D, n_is=16, block=16,
                                     reset_period=2)
PLAN = FaultPlan(drop_rate=0.3, straggler_rate=0.1, corrupt_rate=0.2, seed=5)


@pytest.fixture(scope="module")
def mask_setup():
    k = jax.random.PRNGKey(3)
    train, test = make_synthetic(k, n_train=120, n_test=60, hw=4, noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, N, 30)
    net = make_mlp(in_dim=16, widths=(8,), signed_constant=True)
    task = make_mask_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                          local_epochs=1, batch_size=30)
    return task, shards


@pytest.fixture(scope="module")
def cfl_setup():
    k = jax.random.PRNGKey(4)
    train, test = make_synthetic(k, n_train=120, n_test=60, hw=4, noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, N, 30)
    net = make_mlp(in_dim=16, widths=(8,))
    task, theta0 = make_cfl_task(net, jax.random.fold_in(k, 2), test.x,
                                 test.y, local_epochs=1, batch_size=30,
                                 local_lr=3e-3)
    assert int(theta0.shape[0]) == D
    return task, theta0, shards


def _setup_for(kind, mask_setup, cfl_setup):
    if kind == "mask":
        task, shards = mask_setup
        return task, shards, None
    task, theta0, shards = cfl_setup
    return task, shards, theta0


def _assert_identical(a, b):
    assert len(a["history"]) == len(b["history"])
    for ha, hb in zip(a["history"], b["history"]):
        assert set(ha) == set(hb)
        for key in ha:
            assert hb[key] == ha[key], (key, ha, hb)
    for key in a["meter"]:
        assert b["meter"][key] == a["meter"][key], key
    np.testing.assert_array_equal(np.asarray(a["theta"]),
                                  np.asarray(b["theta"]))
    np.testing.assert_array_equal(np.asarray(a["theta_hat"]),
                                  np.asarray(b["theta_hat"]))


# ---------------------------------------------------------------------------
# Schedule determinism (pure numpy, no engine).
# ---------------------------------------------------------------------------


class TestScheduleDeterminism:

    @settings(deadline=None, max_examples=8)
    @given(st.floats(min_value=0.0, max_value=0.95),
           st.floats(min_value=0.0, max_value=0.95),
           st.floats(min_value=0.0, max_value=0.9),
           st.integers(min_value=0, max_value=10_000))
    def test_same_seed_same_schedule(self, dr, sr, cr, seed):
        plan = FaultPlan(drop_rate=dr, straggler_rate=sr, corrupt_rate=cr,
                         seed=seed)
        a, b = plan.schedule(7, 5), plan.schedule(7, 5)
        np.testing.assert_array_equal(a.drop, b.drop)
        np.testing.assert_array_equal(a.straggle, b.straggle)
        np.testing.assert_array_equal(a.up_failures, b.up_failures)
        np.testing.assert_array_equal(a.dn_failures, b.dn_failures)
        np.testing.assert_array_equal(a.flip_u, b.flip_u)

    @settings(deadline=None, max_examples=8)
    @given(st.floats(min_value=0.0, max_value=0.9),
           st.floats(min_value=0.0, max_value=0.9))
    def test_rates_are_independent_dimensions(self, cr1, cr2):
        """Moving corrupt_rate must not perturb the dropout pattern."""
        base = dict(drop_rate=0.3, straggler_rate=0.2, seed=42)
        a = FaultPlan(corrupt_rate=cr1, **base).schedule(6, 5)
        b = FaultPlan(corrupt_rate=cr2, **base).schedule(6, 5)
        np.testing.assert_array_equal(a.drop, b.drop)
        np.testing.assert_array_equal(a.straggle, b.straggle)
        # and the corruption counts come from the same uniforms: the
        # higher rate dominates pointwise (monotone thresholding).
        lo, hi = (a, b) if cr1 <= cr2 else (b, a)
        assert (lo.up_failures <= hi.up_failures).all()
        assert (lo.dn_failures <= hi.dn_failures).all()

    def test_run_views_are_reproducible(self):
        sched = PLAN.schedule(5, N)
        cohort = np.stack([np.arange(N)] * 5)
        va = sched.run_views(cohort, "all")
        vb = sched.run_views(cohort, "all")
        for x, y in zip(va, vb):
            np.testing.assert_array_equal(x.contrib, y.contrib)
            np.testing.assert_array_equal(x.delivered_dn, y.delivered_dn)
            np.testing.assert_array_equal(x.up_wasted, y.up_wasted)
            assert x.all_failed == y.all_failed

    def test_trivial_plan_draws_nothing(self):
        s = FaultPlan(seed=123).schedule(10, 6)
        assert not s.drop.any() and not s.straggle.any()
        assert not s.up_failures.any() and not s.dn_failures.any()
        assert FaultPlan(seed=123).trivial


# ---------------------------------------------------------------------------
# Degraded aggregation math.
# ---------------------------------------------------------------------------


class _Ctx:
    def __init__(self, w):
        self.up_weight = w


def test_cohort_mean_full_mask_is_exact_mean():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 7)),
                    dtype=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(_cohort_mean(_Ctx(None), x)),
        np.asarray(jnp.mean(x, axis=0)))


def test_cohort_mean_renormalizes_over_survivors():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 3)),
                    dtype=jnp.float32)
    w = jnp.asarray([1.0, 0.0, 1.0, 0.0], dtype=jnp.float32)
    got = np.asarray(_cohort_mean(_Ctx(w), x))
    want = np.asarray((x[0] + x[2]) / 2.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # all-fail: denominator guard, finite output (the engine discards it)
    z = _cohort_mean(_Ctx(jnp.zeros(4)), x)
    assert np.isfinite(np.asarray(z)).all()


# ---------------------------------------------------------------------------
# CRC integrity: every single-bit flip of a frame must be caught.
# ---------------------------------------------------------------------------


def test_crc_catches_every_single_bit_flip():
    m = Message(direction=DIR_UP, sender=2, recipient=0xFFFF,
                payload=b"\xa5\x5a\xf0", payload_bits=20, round=9,
                scheme_id=0xBEEF)
    raw = m.to_bytes()
    assert Message.from_bytes(raw).payload_bits == 20  # clean parses
    for bitpos in range(8 * len(raw)):
        bad = corrupt_copy(raw, bitpos)
        assert bad != raw
        with pytest.raises(WireError):
            Message.from_bytes(bad)


# ---------------------------------------------------------------------------
# Trivial plan == no plan, for every registry scheme (both engine paths
# via mode="auto": fused where eligible, host otherwise).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["host", "fused"])
@pytest.mark.parametrize("name,kind,factory", SCHEMES,
                         ids=[s[0] for s in SCHEMES])
def test_trivial_plan_bit_identical(mask_setup, cfl_setup, name, kind,
                                    factory, mode):
    task, shards, theta0 = _setup_for(kind, mask_setup, cfl_setup)
    base = FLEngine(task, factory()).run(shards, theta0, rounds=2, seed=7,
                                         mode=mode)
    triv = FLEngine(task, factory()).run(shards, theta0, rounds=2, seed=7,
                                         mode=mode, faults=FaultPlan(seed=99))
    _assert_identical(base, triv)
    assert triv["faults"]["summary"]["faulty_rounds"] == 0
    assert triv["faults"]["events"] == []
    assert "faults" not in base


# ---------------------------------------------------------------------------
# Faulted host == faulted fused, one scheme per uplink family.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,kind,factory", FAULT_MATRIX,
                         ids=[s[0] for s in FAULT_MATRIX])
def test_faulted_host_fused_parity(mask_setup, cfl_setup, name, kind,
                                   factory):
    task, shards, theta0 = _setup_for(kind, mask_setup, cfl_setup)
    host = FLEngine(task, factory()).run(shards, theta0, rounds=3, seed=7,
                                         mode="host", faults=PLAN)
    fused = FLEngine(task, factory()).run(shards, theta0, rounds=3, seed=7,
                                          mode="fused", faults=PLAN)
    _assert_identical(host, fused)
    assert host["faults"] == fused["faults"]
    rep = host["faults"]
    assert rep["summary"]["faulty_rounds"] > 0  # the plan actually bites
    assert host["meter"]["retransmit_bits"] == pytest.approx(
        rep["summary"]["retransmit_bits_total"], abs=0.0)


def test_all_fail_round_falls_back(mask_setup):
    """Every client offline every round: the run aborts each round;
    the model never moves and no downlink bits are billed."""
    task, shards = mask_setup
    factory = FAULT_MATRIX[0][2]
    # rates live in [0, 1); pick (deterministically) a seed whose draw
    # at 0.95 drops every client in both rounds
    seed = next(s for s in range(1000)
                if FaultPlan(drop_rate=0.95, seed=s)
                .schedule(2, N).drop.all())
    out = FLEngine(task, factory()).run(
        shards, rounds=2, seed=7, mode="host",
        faults=FaultPlan(drop_rate=0.95, seed=seed))
    rep = out["faults"]
    assert rep["summary"]["all_failed_rounds"] == 2
    assert all(e["all_failed"] and e["survivors"] == 0
               for e in rep["events"])
    assert out["meter"]["downlink_bpp"] == 0.0
    accs = {h["acc"] for h in out["history"]}
    assert len(accs) == 1  # theta_hat frozen at its initial value


# ---------------------------------------------------------------------------
# Wire integrity under faults: retransmits reconcile against the stream.
# ---------------------------------------------------------------------------


def test_wire_faulted_audit_reconciles_and_matches_booking(mask_setup):
    task, shards = mask_setup
    factory = FAULT_MATRIX[0][2]
    wired = FLEngine(task, factory()).run(shards, rounds=3, seed=7,
                                          mode="host", wire="audit",
                                          faults=PLAN)
    rep = wired["wire"]  # reconcile raises on any divergence
    assert rep["retransmit_err_bits"] == 0.0
    assert rep["retransmit_stream_bits"] > 0
    session = wired["wire_session"]
    assert wired["meter"]["retransmit_bits"] == pytest.approx(
        session.retransmit_payload_bits)
    # the non-wire host path books the identical retransmit total (the
    # booking formula is shared, the schedule is the same seed)
    plain = FLEngine(task, factory()).run(shards, rounds=3, seed=7,
                                          mode="host", faults=PLAN)
    assert plain["meter"]["retransmit_bits"] == pytest.approx(
        wired["meter"]["retransmit_bits"])
    assert wired["faults"]["summary"]["retransmits_total"] \
        == plain["faults"]["summary"]["retransmits_total"]


@pytest.mark.parametrize("name,kind,factory", FAULT_MATRIX,
                         ids=[s[0] for s in FAULT_MATRIX])
def test_wire_faulted_audit_matches_staged_trajectory(mask_setup, cfl_setup,
                                                      name, kind, factory):
    """The faulted wire round carries EF rows, theta_hat rows and all-fail
    discards exactly as the staged host round: same trajectory, bit for
    bit, with the decoded frames driving it."""
    task, shards, theta0 = _setup_for(kind, mask_setup, cfl_setup)
    plain = FLEngine(task, factory()).run(shards, theta0, rounds=3, seed=7,
                                          mode="host", faults=PLAN)
    wired = FLEngine(task, factory()).run(shards, theta0, rounds=3, seed=7,
                                          mode="host", wire="audit",
                                          faults=PLAN)
    assert plain["faults"]["summary"]["faulty_rounds"] > 0
    np.testing.assert_array_equal(np.asarray(wired["theta"]),
                                  np.asarray(plain["theta"]))
    np.testing.assert_array_equal(np.asarray(wired["theta_hat"]),
                                  np.asarray(plain["theta_hat"]))
    assert wired["history"] == plain["history"], name


# ---------------------------------------------------------------------------
# Crash-safe resume: killed at a checkpoint == uninterrupted.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["host", "fused"])
@pytest.mark.parametrize("faults", [None, PLAN],
                         ids=["clean", "faulted"])
def test_resume_matches_uninterrupted(mask_setup, tmp_path, mode, faults):
    task, shards = mask_setup
    factory = FAULT_MATRIX[0][2]
    kw = dict(rounds=4, seed=7, mode=mode, faults=faults)
    full = FLEngine(task, factory()).run(shards, **kw)

    ckdir = str(tmp_path / "ck")
    FLEngine(task, factory()).run(shards, checkpoint_dir=ckdir,
                                  checkpoint_every=2, **kw)
    # "kill" the run after round 2: drop every later checkpoint so the
    # resume genuinely restarts mid-run rather than loading the final one
    for p in glob.glob(os.path.join(ckdir, "ckpt_*.repro")):
        if not p.endswith("00000002.repro"):
            os.remove(p)
    resumed = FLEngine(task, factory()).run(shards, resume_from=ckdir, **kw)
    _assert_identical(full, resumed)
    if faults is not None:
        assert resumed["faults"] == full["faults"]


def test_resume_refuses_mismatched_config(mask_setup, tmp_path):
    task, shards = mask_setup
    factory = FAULT_MATRIX[0][2]
    ckdir = str(tmp_path / "ck")
    FLEngine(task, factory()).run(shards, rounds=2, seed=7, mode="host",
                                  checkpoint_dir=ckdir, checkpoint_every=1)
    with pytest.raises(Exception, match="config"):
        FLEngine(task, factory()).run(shards, rounds=2, seed=8, mode="host",
                                      resume_from=ckdir)


# ---------------------------------------------------------------------------
# Host-loop staging: no per-round re-trace (ROADMAP item).
# ---------------------------------------------------------------------------


def test_host_round_jit_is_cached_across_rounds(mask_setup):
    task, shards = mask_setup
    factory = FAULT_MATRIX[0][2]
    eng3 = FLEngine(task, factory())
    eng3.run(shards, rounds=3, seed=7, mode="host")
    eng6 = FLEngine(task, factory())
    eng6.run(shards, rounds=6, seed=7, mode="host")
    assert eng3.host_trace_count >= 1
    # doubling the rounds must not add traces: the staged round jit is
    # keyed by plan shape, not by round index
    assert eng6.host_trace_count == eng3.host_trace_count
