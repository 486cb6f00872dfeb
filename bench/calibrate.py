#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \
        [--faulted 3] [--rehearse]

For every seed: the program's first ``REF_STEPS`` calls against the plain
reference ("sound").  For the first ``--faulted`` seeds also: the reference
computed in bfloat16 put in the program's place ("control"), the program
with half of the cohort left out of the aggregation ("half_cohort"), and
calls that return their state unchanged ("unchanged": 1 on ``update_gap``
by construction; run for the other numbers).  One JSON line per reading,
with both sides' test losses and leaf norms, then the largest sound and
the smallest faulted reading of each number.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run as bench_run  # noqa: E402


def leaf_norms(prog, ref, cell):
    """Each side's leaf norms of the change of theta and of the clients'
    estimates after every call, for numbers worked out after the run."""
    import numpy as np
    from harness.cell import load_module
    from harness.correct import _leaf_norms
    slices = load_module("reference", "common.py").leaf_slices(
        [shape for shape, _ in cell.layer_shapes()])
    out = {}
    for side, rec in (("prog", prog), ("ref", ref)):
        t0 = np.asarray(rec.theta0, np.float64)
        out[side] = [_leaf_norms(s.theta - t0, slices).tolist()
                     for s in rec.steps]
        out[side + "_hat"] = [_leaf_norms(s.theta_hat - t0, slices).tolist()
                              for s in rec.steps]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faulted", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    jax = bench_run.setup_jax(args.rehearse)
    import jax.numpy as jnp
    from harness import faults
    from harness.cell import Cell

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("calibrate: not a TPU", file=sys.stderr)
        return 1
    cell = Cell.load(args.workload, rehearse=args.rehearse)
    seeds = [int(s) for s in args.seeds.split(",")]
    plans = {
        "sound": {},
        "control": {"call_hook": faults.reference_in_place(jnp.bfloat16)},
        "half_cohort": {"spec_transform": faults.half_cohort},
        "unchanged": {"call_hook": faults.unchanged},
    }
    rows = []
    for i, seed in enumerate(seeds):
        for kind in plans:
            if kind != "sound" and i >= args.faulted:
                continue
            t0 = time.perf_counter()
            data, net_key, program, call, prog, _ = bench_run.first_steps(
                cell, seed, **plans[kind])
            t1 = time.perf_counter()
            del program, call
            values, ref = bench_run.compare(cell, data, net_key, seed, prog,
                                            with_reference=True)
            row = {"seed": seed, "kind": kind, "readings": values,
                   "program_s": t1 - t0,
                   "reference_s": time.perf_counter() - t1,
                   "loss": [s.loss for s in prog.steps],
                   "ref_loss": [s.loss for s in ref.steps],
                   "ref_loss0": ref.loss0,
                   "acc": [s.acc for s in prog.steps],
                   "ref_acc": [s.acc for s in ref.steps],
                   "norms": leaf_norms(prog, ref, cell)}
            rows.append(row)
            print(json.dumps(row), flush=True)
            jax.clear_caches()
    summary = {}
    for kind in plans:
        vals = [r["readings"] for r in rows if r["kind"] == kind]
        if not vals:
            continue
        pick = max if kind == "sound" else min
        summary[kind] = {k: pick(v[k] for v in vals) for k in vals[0]}
    print(json.dumps({"summary": summary, "seeds": seeds,
                      "device": dev.device_kind}), flush=True)
    out = os.environ.get("CALIBRATE_OUT")
    if out:
        with open(out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
