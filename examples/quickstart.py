"""Quickstart: BiCompFL-GR on a synthetic federated task in ~a minute.

    PYTHONPATH=src python examples/quickstart.py

Ten clients collaboratively train a probabilistic mask over a frozen
signed-constant MLP; all communication runs through bi-directional MRC.
Prints per-round accuracy and the communication bill (bits per parameter),
which lands orders of magnitude below dense FedAvg's 64 bpp.
"""
import time

import jax

from repro.core.blocks import FixedAllocation
from repro.fl.data import make_synthetic, partition_iid
from repro.fl.engine import FLEngine
from repro.fl.registry import bicompfl_spec
from repro.fl.nets import make_mlp
from repro.fl.tasks import make_mask_task
from repro import compile_cache


def main():
    compile_cache.enable()
    key = jax.random.PRNGKey(0)
    train, test = make_synthetic(key, n_train=2000, n_test=500, hw=10, noise=0.4)
    n_clients = 10
    shards = partition_iid(jax.random.fold_in(key, 1), train, n_clients,
                           2000 // n_clients)

    net = make_mlp(in_dim=100, widths=(256,), signed_constant=True)
    task = make_mask_task(net, jax.random.fold_in(key, 2), test.x, test.y,
                          local_epochs=3, lr=0.1)
    print(f"model dimension d = {task.d} Bernoulli parameters")

    # A scheme is (uplink channel, downlink channel, aggregator): the GR
    # variant is an MRC uplink over shared candidates + an index-relay
    # downlink.  Swap either channel to explore new scenarios (DESIGN.md).
    spec = bicompfl_spec("GR", allocation=FixedAllocation(128), n_is=64,
                         n_dl=n_clients)
    t0 = time.time()
    out = FLEngine(task, spec).run(shards, rounds=15, seed=0, eval_every=3)
    for h in out["history"]:
        print(f"round {h['round']:3d}  acc {h['acc']:.3f}  "
              f"cumulative bpp {h['bpp_so_far']:.4f}")
    m = out["meter"]
    print(f"\nfinal acc {out['final_acc']:.3f}   max acc {out['max_acc']:.3f}")
    print(f"bitrate: {m['bpp']:.4f} bpp (vs 64 bpp dense FedAvg -> "
          f"{64 / m['bpp']:.0f}x reduction)   [{time.time()-t0:.0f}s]")


if __name__ == "__main__":
    main()
