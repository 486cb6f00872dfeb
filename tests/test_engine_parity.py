"""Fixed-seed parity: the composable Channel/Engine API reproduces the
seed's monolithic loops bit-for-bit.

Each case runs the vendored legacy loop (tests/legacy_seed_impl.py) and the
engine, with the scheme built from the same run config by
``repro.fl.registry``, on the same tiny synthetic task and asserts equal
histories (accuracy floats, cumulative bits), meters, and final model /
client-estimate arrays -- exact equality, no tolerances."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blocks import AdaptiveAllocation, FixedAllocation
from repro.fl import registry
from repro.fl.data import make_synthetic, partition_iid
from repro.fl.engine import FLEngine
from repro.fl.nets import make_mlp
from repro.fl.tasks import make_cfl_task, make_mask_task

from legacy_seed_impl import (BaselineConfig, BiCompFLConfig, CFLConfig,
                              run_baseline_legacy, run_bicompfl_cfl_legacy,
                              run_bicompfl_legacy)


def run_bicompfl(task, shards, cfg):
    """The engine on a legacy BiCompFL config (paper n_dl = n * n_ul)."""
    n = int(shards.x.shape[0])
    n_dl = cfg.n_dl if cfg.n_dl is not None else n * cfg.n_ul
    spec = registry.bicompfl_spec(
        cfg.variant, allocation=cfg.allocation, n_is=cfg.n_is, n_ul=cfg.n_ul,
        n_dl=n_dl, chunk=cfg.chunk, logw_fn=cfg.logw_fn,
        participation=cfg.participation)
    return FLEngine(task, spec).run(shards, rounds=cfg.rounds, seed=cfg.seed,
                                    eval_every=cfg.eval_every)


def run_bicompfl_cfl(task, theta0, shards, cfg):
    spec = registry.cfl_spec(n_is=cfg.n_is, n_ul=cfg.n_ul,
                             block_size=cfg.block_size,
                             server_lr=cfg.server_lr, chunk=cfg.chunk,
                             logw_fn=cfg.logw_fn)
    return FLEngine(task, spec).run(shards, theta0, rounds=cfg.rounds,
                                    seed=cfg.seed, eval_every=cfg.eval_every)


def run_baseline(task, theta0, shards, cfg):
    spec = registry.baseline_spec(cfg.scheme, n=int(shards.x.shape[0]),
                                  d=int(theta0.shape[0]),
                                  server_lr=cfg.server_lr,
                                  reset_period=cfg.reset_period)
    return FLEngine(task, spec).run(shards, theta0, rounds=cfg.rounds,
                                    seed=cfg.seed, eval_every=cfg.eval_every)


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
def cfl_setup():
    k = jax.random.PRNGKey(4)
    train, test = make_synthetic(k, n_train=240, n_test=120, hw=6, noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, 3, 80)
    net = make_mlp(in_dim=36, widths=(32,))
    task, theta0 = make_cfl_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                                 local_epochs=2, batch_size=40, local_lr=3e-3)
    return task, theta0, shards


def _assert_same(old, new, *, check_theta_hat=True):
    assert len(old["history"]) == len(new["history"])
    for ho, hn in zip(old["history"], new["history"]):
        for key in ho:
            assert hn[key] == ho[key], (key, ho, hn)
    for key in old["meter"]:
        assert new["meter"][key] == old["meter"][key], key
    np.testing.assert_array_equal(np.asarray(old["theta"]),
                                  np.asarray(new["theta"]))
    if check_theta_hat and "theta_hat" in old:
        np.testing.assert_array_equal(np.asarray(old["theta_hat"]),
                                      np.asarray(new["theta_hat"]))
    assert new["final_acc"] == old["final_acc"]
    assert new["max_acc"] == old["max_acc"]


@pytest.mark.parametrize("variant", ["GR", "GR-Reconst", "PR", "PR-SplitDL"])
def test_bicompfl_variant_parity(mask_setup, variant):
    task, shards = mask_setup
    cfg = BiCompFLConfig(variant=variant, rounds=2, n_is=16,
                         allocation=FixedAllocation(64), seed=11)
    _assert_same(run_bicompfl_legacy(task, shards, cfg),
                 run_bicompfl(task, shards, cfg))


def test_bicompfl_adaptive_parity(mask_setup):
    """Segment-codec path (AdaptiveAllocation) through the engine."""
    task, shards = mask_setup
    cfg = BiCompFLConfig(variant="GR", rounds=2, n_is=16,
                         allocation=AdaptiveAllocation(n_is=16), seed=11)
    _assert_same(run_bicompfl_legacy(task, shards, cfg),
                 run_bicompfl(task, shards, cfg))


def test_bicompfl_pr_partial_parity(mask_setup):
    """Partial participation: the engine skips training inactive clients but
    must reproduce the legacy loop (which trained everyone) exactly."""
    task, shards = mask_setup
    cfg = BiCompFLConfig(variant="PR", rounds=3, n_is=16, participation=0.67,
                         allocation=FixedAllocation(64), seed=13)
    _assert_same(run_bicompfl_legacy(task, shards, cfg),
                 run_bicompfl(task, shards, cfg))


@pytest.mark.parametrize("scheme", ["fedavg", "memsgd", "doublesqueeze",
                                    "neolithic", "cser", "liec", "m3"])
def test_baseline_parity(cfl_setup, scheme):
    task, theta0, shards = cfl_setup
    # reset_period=2 exercises the CSER/LIEC flush path inside 3 rounds
    cfg = BaselineConfig(scheme=scheme, rounds=3, server_lr=1.0, seed=5,
                         reset_period=2)
    _assert_same(run_baseline_legacy(task, theta0, shards, cfg),
                 run_baseline(task, theta0, shards, cfg))


def test_cfl_parity(cfl_setup):
    task, theta0, shards = cfl_setup
    cfg = CFLConfig(rounds=2, n_is=16, block_size=16, server_lr=1.0, seed=7)
    _assert_same(run_bicompfl_cfl_legacy(task, theta0, shards, cfg),
                 run_bicompfl_cfl(task, theta0, shards, cfg),
                 check_theta_hat=False)
