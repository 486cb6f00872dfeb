"""CI fault-matrix smoke: every uplink channel family under a hostile
``FaultPlan``, with a kill-and-resume leg, emitting the per-run fault
event logs as a CI artifact.

One scheme per family (``registry.fault_matrix``: MRC index streams,
quantized-MRC deltas, sign-EF, top-k EF, dense) runs three legs:

1. **faulted run** -- dropouts + stragglers + frame corruption at the
   DESIGN.md §8 smoke rates (drop 0.3); the plan must actually bite
   (``faulty_rounds > 0``) and the booked ``retransmit_bits`` must equal
   the fault report's total;
2. **host/fused agreement** -- the same seed's faulted run on the other
   engine path must produce the identical fault report and final model;
3. **kill + resume** -- the run is checkpointed, every checkpoint after
   round ``rounds//2`` is deleted (the "crash"), and the resumed run
   must be bit-identical to the uninterrupted one.

The collected ``out["faults"]`` reports land in ``fault_events.json``
(uploaded by CI), so a fault-semantics regression shows up as an artifact
diff as well as a red line.

Run:  PYTHONPATH=src python -m benchmarks.fault_smoke [--rounds N]
      [--out fault_events.json]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import tempfile

import jax
import numpy as np

from repro.fl import registry
from repro.fl.data import make_synthetic, partition_iid
from repro.fl.engine import FLEngine
from repro.fl.faults import FaultPlan
from repro.fl.nets import make_mlp
from repro.fl.tasks import make_cfl_task, make_mask_task
from repro import compile_cache

N_CLIENTS = 4
PLAN = FaultPlan(drop_rate=0.3, straggler_rate=0.1, corrupt_rate=0.2,
                 seed=1)


def build_setup():
    k = jax.random.PRNGKey(0)
    train, test = make_synthetic(k, n_train=240, n_test=120, hw=6, noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, N_CLIENTS, 60)
    net = make_mlp(in_dim=36, widths=(32,), signed_constant=True)
    task = make_mask_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                          local_epochs=1, batch_size=40)
    cnet = make_mlp(in_dim=36, widths=(32,))
    ctask, theta0 = make_cfl_task(cnet, jax.random.fold_in(k, 3), test.x,
                                  test.y, local_epochs=1, batch_size=40,
                                  local_lr=3e-3)
    return task, ctask, theta0, shards


def assert_identical(a, b, label):
    assert len(a["history"]) == len(b["history"]), label
    for ha, hb in zip(a["history"], b["history"]):
        assert ha == hb, (label, ha, hb)
    assert a["meter"] == b["meter"], label
    np.testing.assert_array_equal(np.asarray(a["theta"]),
                                  np.asarray(b["theta"]), err_msg=label)
    np.testing.assert_array_equal(np.asarray(a["theta_hat"]),
                                  np.asarray(b["theta_hat"]), err_msg=label)


def smoke_scheme(name, task, factory, shards, theta0, *, rounds):
    kw = dict(rounds=rounds, seed=7, eval_every=max(rounds // 4, 1),
              faults=PLAN)

    host = FLEngine(task, factory()).run(shards, theta0, mode="host", **kw)
    rep = host["faults"]
    assert rep["summary"]["faulty_rounds"] > 0, \
        f"{name}: the fault plan never bit -- smoke proves nothing"
    assert host["meter"]["retransmit_bits"] == \
        rep["summary"]["retransmit_bits_total"], name

    fused = FLEngine(task, factory()).run(shards, theta0, mode="fused", **kw)
    assert_identical(host, fused, f"{name}: host vs fused under faults")
    assert fused["faults"] == rep, name

    # kill + resume: drop every checkpoint after the midpoint, resume, and
    # demand the bit-identical trajectory
    with tempfile.TemporaryDirectory() as ckdir:
        FLEngine(task, factory()).run(shards, theta0, mode="host",
                                      checkpoint_dir=ckdir,
                                      checkpoint_every=max(rounds // 2, 1),
                                      **kw)
        keep = max(rounds // 2, 1)
        for p in glob.glob(os.path.join(ckdir, "ckpt_*.repro")):
            if int(os.path.basename(p)[5:13]) > keep:
                os.remove(p)
        resumed = FLEngine(task, factory()).run(shards, theta0, mode="host",
                                                resume_from=ckdir, **kw)
    assert_identical(host, resumed, f"{name}: killed-at-{keep} resume")

    s = rep["summary"]
    print(f"{name:16s} faulty_rounds={s['faulty_rounds']}/{rounds}  "
          f"dropped={s['dropped_total']} stragglers={s['stragglers_total']} "
          f"lost={s['lost_uplink_total']}+{s['lost_downlink_total']}  "
          f"retransmits={s['retransmits_total']} "
          f"({s['retransmit_bits_total']:,.0f} bits)  "
          f"resume@{keep} ok", flush=True)
    return rep


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--out", default="fault_events.json")
    args = ap.parse_args()

    task, ctask, theta0, shards = build_setup()
    d = int(theta0.shape[0])
    matrix = registry.fault_matrix(n=N_CLIENTS, d=d, n_is=16, block=16,
                                   reset_period=2)
    print(f"== fault_smoke: {args.rounds} rounds, {N_CLIENTS} clients, "
          f"d={d}, plan={PLAN} ==")

    reports = {}
    for name, kind, factory in matrix:
        t, th0 = (task, None) if kind == "mask" else (ctask, theta0)
        reports[name] = smoke_scheme(name, t, factory, shards, th0,
                                     rounds=args.rounds)
        jax.clear_caches()

    with open(args.out, "w") as f:
        json.dump({"plan": reports[matrix[0][0]]["plan"],
                   "schemes": reports}, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
