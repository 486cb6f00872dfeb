"""Unit/property tests for the block-allocation control plane
(``repro.core.blocks``): host exact plans and the fused bucket API.

Pinned properties:

* ``plan()`` is deterministic -- a fixed KL profile always yields the
  identical plan (sizes, segment ids, overhead);
* bucket rounding is *monotone* -- more KL never selects a bucket with
  fewer blocks (bigger blocks);
* bucket rounding is *conservative* -- the bucketed plan never allocates
  more bits than the exact plan's budget plus the allocation's declared
  ``bucket_overhead_bits`` (zero for both: AdaptiveAvg's buckets are the
  exact pow2 plan space, AdaptiveAllocation floors onto its grid);
* the traced bucket selection agrees with the host ``plan()`` on the same
  profile (AdaptiveAvg: identical size; Adaptive: the largest grid point
  at or below the exact block count).
"""
import math

from hypothesis import given, settings, strategies as st

import jax.numpy as jnp
import numpy as np

from repro.core.blocks import (AdaptiveAllocation, AdaptiveAvgAllocation,
                               BlockPlan, FixedAllocation)


def _profile(seed: int, d: int, scale: float) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (np.abs(rng.standard_normal(d)) * scale).astype(np.float32)


def _stats(klp: np.ndarray):
    klp = jnp.asarray(klp)
    return {"profile": klp, "total": jnp.sum(klp)}


def _exact_bits(alloc, klp, d, n_is):
    """Exact host plan's uplink budget: blocks * log2(n_is) + overhead."""
    _, nb, _, oh = alloc.plan(klp, d)
    return nb * math.log2(n_is), oh


class TestDeterminism:
    @settings(max_examples=8)
    @given(st.integers(min_value=64, max_value=2048),
           st.floats(min_value=1e-4, max_value=0.5))
    def test_adaptive_plan_deterministic(self, d, scale):
        alloc = AdaptiveAllocation(n_is=16)
        klp = _profile(0, d, scale)
        a = alloc.plan(klp, d)
        b = alloc.plan(klp.copy(), d)
        assert a[0] == b[0] and a[1] == b[1] and a[3] == b[3]
        np.testing.assert_array_equal(a[2], b[2])

    @settings(max_examples=8)
    @given(st.floats(min_value=1e-4, max_value=0.5))
    def test_adaptive_avg_plan_deterministic(self, scale):
        alloc = AdaptiveAvgAllocation(n_is=16)
        klp = _profile(1, 512, scale)
        assert alloc.plan(klp, 512) == alloc.plan(klp.copy(), 512)

    def test_finalize_plan_deterministic(self):
        alloc = AdaptiveAllocation(n_is=16)
        klp = _profile(2, 512, 0.05)
        tmpl = alloc.bucket_plans(512)[2]
        a = alloc.finalize_plan(tmpl, _stats(klp), 512)
        b = alloc.finalize_plan(tmpl, _stats(klp), 512)
        np.testing.assert_array_equal(np.asarray(a.seg_ids),
                                      np.asarray(b.seg_ids))
        assert int(a.billable) == int(b.billable)


class TestMonotone:
    @settings(max_examples=8)
    @given(st.floats(min_value=1.2, max_value=8.0))
    def test_avg_bucket_monotone_in_kl(self, ratio):
        """Scaling the KL profile up never selects *fewer* blocks."""
        alloc = AdaptiveAvgAllocation(n_is=16, min_block=32, max_block=4096)
        d = 4096
        klp = _profile(3, d, 0.01)
        lo = int(alloc.select_bucket(_stats(klp), d))
        hi = int(alloc.select_bucket(_stats(klp * ratio), d))
        # bucket index orders by *size*; more KL -> smaller-or-equal size
        assert hi <= lo
        sizes = alloc.bucket_sizes()
        assert sizes[hi] <= sizes[lo]

    @settings(max_examples=8)
    @given(st.floats(min_value=1.2, max_value=8.0))
    def test_adaptive_bucket_monotone_in_kl(self, ratio):
        alloc = AdaptiveAllocation(n_is=16)
        d = 2048
        klp = _profile(4, d, 0.01)
        lo = int(alloc.select_bucket(_stats(klp), d))
        hi = int(alloc.select_bucket(_stats(klp * ratio), d))
        grid = alloc.bucket_grid(d)
        assert grid[hi] >= grid[lo]  # more KL -> at least as many blocks

    def test_grid_sorted_and_capped(self):
        alloc = AdaptiveAllocation(min_blocks=4)
        grid = alloc.bucket_grid(2048)
        assert list(grid) == sorted(set(grid))
        assert grid[0] == 4 and grid[-1] == 2048 // 8


class TestConservative:
    @settings(max_examples=8)
    @given(st.floats(min_value=1e-3, max_value=0.5))
    def test_avg_bucket_is_exact_plan(self, scale):
        """AdaptiveAvg: the selected bucket IS the host plan (same pow2
        size), so bucketing adds zero overhead by construction."""
        alloc = AdaptiveAvgAllocation(n_is=16, min_block=32, max_block=4096)
        d = 4096
        klp = _profile(5, d, scale)
        size_exact, nb_exact, _, _ = alloc.plan(klp, d)
        idx = int(alloc.select_bucket(_stats(klp), d))
        plan = alloc.bucket_plans(d)[idx]
        assert plan.size == size_exact and plan.n_blocks == nb_exact
        assert alloc.bucket_overhead_bits == 0.0

    @settings(max_examples=8)
    @given(st.integers(min_value=256, max_value=4096),
           st.floats(min_value=1e-3, max_value=0.3))
    def test_adaptive_bucket_never_exceeds_exact_budget(self, d, scale):
        """Floor rounding: bucketed bits <= exact bits + declared overhead."""
        n_is = 16
        alloc = AdaptiveAllocation(n_is=n_is)
        klp = _profile(6, d, scale)
        exact_bits, exact_oh = _exact_bits(alloc, klp, d, n_is)
        idx = int(alloc.select_bucket(_stats(klp), d))
        plan = alloc.finalize_plan(alloc.bucket_plans(d)[idx], _stats(klp), d)
        bucket_bits = int(plan.billable) * math.log2(n_is)
        assert bucket_bits <= exact_bits + alloc.bucket_overhead_bits
        assert float(plan.overhead_bits) <= exact_oh + alloc.bucket_overhead_bits
        # ... and the static capacity really is the grid's floor:
        grid = alloc.bucket_grid(d)
        _, nb_exact, _, _ = alloc.plan(klp, d)
        assert plan.n_blocks == max(g for g in grid if g <= nb_exact)

    def test_explicit_buckets_respected(self):
        # min_blocks is always in the grid: the conservative floor anchor
        alloc = AdaptiveAllocation(n_is=16, buckets=(40, 10, 20, 10))
        assert alloc.bucket_grid(2048) == (4, 10, 20, 40)
        # out-of-range buckets clamp into [min_blocks, d // 8]
        alloc2 = AdaptiveAllocation(n_is=16, min_blocks=4, buckets=(1, 9999))
        assert alloc2.bucket_grid(256) == (4, 32)

    def test_explicit_buckets_above_exact_stay_conservative(self):
        """A bucket set entirely above the exact block count must floor to
        the min_blocks anchor, never round up onto the grid."""
        n_is = 16
        alloc = AdaptiveAllocation(n_is=n_is, buckets=(64, 128))
        d = 2048
        klp = _profile(8, d, 1e-4)  # tiny KL -> exact plan wants min_blocks
        _, nb_exact, _, _ = alloc.plan(klp, d)
        assert nb_exact < 64
        idx = int(alloc.select_bucket(_stats(klp), d))
        plan = alloc.finalize_plan(alloc.bucket_plans(d)[idx], _stats(klp), d)
        assert plan.n_blocks == alloc.min_blocks
        assert int(plan.billable) * math.log2(n_is) <= \
            nb_exact * math.log2(n_is) + alloc.bucket_overhead_bits


class TestFinalizeMatchesHostPlan:
    def test_seg_ids_match_exact_plan_at_same_count(self):
        """With the bucket capacity equal to the exact block count, the
        traced binning reproduces the host plan's segment ids."""
        d = 1024
        alloc = AdaptiveAllocation(n_is=16)
        klp = _profile(7, d, 0.05)
        _, nb, seg_host, oh_host = alloc.plan(klp, d)
        tmpl = BlockPlan(size=None, n_blocks=nb, seg_ids=None,
                         overhead_bits=0.0)
        plan = alloc.finalize_plan(tmpl, _stats(klp), d)
        np.testing.assert_array_equal(np.asarray(plan.seg_ids), seg_host)
        assert int(plan.billable) == int(seg_host.max()) + 1
        assert float(plan.overhead_bits) == oh_host

    def test_billable_defaults_to_capacity(self):
        plan = BlockPlan(size=64, n_blocks=8, seg_ids=None, overhead_bits=0.0)
        assert plan.billable == 8 and not plan.adaptive
