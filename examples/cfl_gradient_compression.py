"""BiCompFL-GR-CFL: the paper's technique in *conventional* FL.

    PYTHONPATH=src python examples/cfl_gradient_compression.py

Clients compute weight deltas, quantize them with stochastic SignSGD
(Q_s of paper Sec. 4), and convey samples through MRC against the
uninformative Ber(1/2) prior; the federator relays indices on the downlink
(global shared randomness).  Compared side by side with DoubleSqueeze and
dense FedAvg at equal round counts.
"""
import time

import jax

from repro.fl.baselines import BaselineConfig, run_baseline
from repro.fl.data import make_synthetic, partition_iid
from repro.fl.federator import CFLConfig, run_bicompfl_cfl
from repro.fl.nets import make_mlp
from repro.fl.tasks import make_cfl_task
from repro import compile_cache


def main():
    compile_cache.enable()
    key = jax.random.PRNGKey(0)
    train, test = make_synthetic(key, n_train=2000, n_test=500, hw=10, noise=0.4)
    shards = partition_iid(jax.random.fold_in(key, 1), train, 10, 200)
    net = make_mlp(in_dim=100, widths=(256,))
    task, theta0 = make_cfl_task(net, jax.random.fold_in(key, 2),
                                 test.x, test.y, local_epochs=5,
                                 batch_size=32, local_lr=3e-3)

    rounds = 12
    t0 = time.time()
    out = run_bicompfl_cfl(task, theta0, shards,
                           CFLConfig(rounds=rounds, server_lr=1.0))
    print(f"BiCompFL-GR-CFL : acc {out['max_acc']:.3f}  "
          f"bpp {out['meter']['bpp']:.3f}  [{time.time()-t0:.0f}s]")

    for scheme in ("doublesqueeze", "fedavg"):
        t0 = time.time()
        res = run_baseline(task, theta0, shards,
                           BaselineConfig(scheme=scheme, rounds=rounds,
                                          server_lr=1.0))
        print(f"{scheme:15s} : acc {res['max_acc']:.3f}  "
              f"bpp {res['meter']['bpp']:.3f}  [{time.time()-t0:.0f}s]")


if __name__ == "__main__":
    main()
