"""Benchmark harness -- one benchmark per paper table/figure.

  table_main     Tables 5-12 / Figs 1-2: max accuracy + bpp (total, BC,
                 uplink, downlink) per scheme, iid and non-iid, for the
                 BiCompFL variants and the non-stochastic baselines.
  table_cfl      Section 4 (BiCompFL-GR-CFL): conventional FL with
                 stochastic sign + MRC vs the sign-EF baselines.
  ablation_ndl   Appendix J.3: downlink sample count n_DL.
  ablation_nis   Appendix J.5: importance samples n_IS.
  ablation_block Appendix J.4: block size d/B.
  ablation_nclients  Appendix J.1: number of clients.
  kernel_micro   Pallas kernel vs jnp oracle timing + allclose (interpret
                 mode on the CPU, compiled elsewhere).
  wire_audit     bytes on the wire per scheme: short wire-audited host runs
                 over the full registry matrix (stream bytes per round,
                 payload vs framing split; reconcile runs inside).
  roofline       reads dryrun_*.json -> the per-(arch x shape x mesh) table.

Run:  PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocks import AdaptiveAllocation, AdaptiveAvgAllocation, FixedAllocation
from repro.fl import registry
from repro.fl.data import make_synthetic, partition_dirichlet, partition_iid
from repro.fl.engine import FLEngine
from repro.fl.nets import make_cnn, make_mlp
from repro.fl.tasks import make_cfl_task, make_mask_task
from repro import compile_cache

SEP = "-" * 100


def _setup(seed=0, *, iid=True, n_clients=4, hw=10, noise=0.5,
           n_train=1600, n_test=400):
    k = jax.random.PRNGKey(seed)
    train, test = make_synthetic(k, n_train=n_train, n_test=n_test, hw=hw,
                                 noise=noise)
    shard = n_train // n_clients
    if iid:
        shards = partition_iid(jax.random.fold_in(k, 1), train, n_clients, shard)
    else:
        shards = partition_dirichlet(jax.random.fold_in(k, 1), train,
                                     n_clients, shard, alpha=0.1)
    return k, shards, test


def _mask_task(k, test, hw=10, width=256, local_epochs=3, lr=0.1):
    net = make_mlp(in_dim=hw * hw, widths=(width,), signed_constant=True)
    return make_mask_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                          local_epochs=local_epochs, lr=lr)


def _fmt_row(name, out):
    m = out["meter"]
    return (f"{name:34s} acc={out['max_acc']:.3f}  bpp={m['bpp']:8.4f} "
            f"bpp(BC)={m['bpp_bc']:8.4f}  up={m['uplink_bpp']:7.4f} "
            f"down={m['downlink_bpp']:7.4f}")


def table_main(fast: bool):
    """Main accuracy-vs-bitrate table (paper Tables 5-12)."""
    rounds = 6 if fast else 10
    for iid in (True, False):
        print(f"\n== table_main ({'iid' if iid else 'non-iid Dir(0.1)'}), "
              f"{rounds} rounds, 4 clients, synthetic-10class ==")
        k, shards, test = _setup(iid=iid)
        task = _mask_task(k, test)

        n = int(shards.x.shape[0])  # n_dl paper default = n_clients * n_ul
        variants = [
            ("BiCompFL-GR-Fixed",
             registry.bicompfl_spec("GR", allocation=FixedAllocation(128),
                                    n_is=64, n_dl=n)),
            ("BiCompFL-GR-Adaptive",
             registry.bicompfl_spec("GR", allocation=AdaptiveAllocation(n_is=64),
                                    n_is=64, n_dl=n)),
            ("BiCompFL-GR-Adaptive-Avg",
             registry.bicompfl_spec("GR", allocation=AdaptiveAvgAllocation(n_is=64),
                                    n_is=64, n_dl=n)),
            ("BiCompFL-GR-Reconst-Fixed",
             registry.bicompfl_spec("GR-Reconst", allocation=FixedAllocation(128),
                                    n_is=64, n_dl=n)),
            ("BiCompFL-PR-Fixed",
             registry.bicompfl_spec("PR", allocation=FixedAllocation(128),
                                    n_is=64, n_dl=n)),
            ("BiCompFL-PR-Fixed-SplitDL",
             registry.bicompfl_spec("PR-SplitDL", allocation=FixedAllocation(128),
                                    n_is=64, n_dl=n)),
        ]
        for name, spec in variants:
            t0 = time.time()
            out = FLEngine(task, spec).run(shards, rounds=rounds, seed=0)
            print(_fmt_row(name, out) + f"  [{time.time()-t0:.0f}s]", flush=True)
            jax.clear_caches()  # the CPU JIT otherwise exhausts memory
                                # across variants (LLVM 'Cannot allocate')

        # conventional baselines need a CFL task (deterministic weights)
        net = make_mlp(in_dim=100, widths=(256,))
        ctask, theta0 = make_cfl_task(net, jax.random.fold_in(k, 3),
                                      test.x, test.y, local_epochs=5,
                                      batch_size=32, local_lr=3e-3)
        for scheme in registry.ALL_BASELINES:
            t0 = time.time()
            spec = registry.baseline_spec(scheme, n=n, d=int(theta0.shape[0]),
                                          server_lr=1.0)
            out = FLEngine(ctask, spec).run(shards, theta0, rounds=rounds, seed=0)
            print(_fmt_row(scheme, out) + f"  [{time.time()-t0:.0f}s]", flush=True)
            jax.clear_caches()


def table_cfl(fast: bool):
    """BiCompFL-GR-CFL vs sign-EF baselines (paper Section 4)."""
    rounds = 6 if fast else 10
    print(f"\n== table_cfl (conventional FL, stochastic sign + MRC) ==")
    k, shards, test = _setup(iid=True)
    net = make_mlp(in_dim=100, widths=(256,))
    task, theta0 = make_cfl_task(net, jax.random.fold_in(k, 3), test.x, test.y,
                                 local_epochs=5, batch_size=32, local_lr=3e-3)
    out = FLEngine(task, registry.cfl_spec(server_lr=1.0)).run(
        shards, theta0, rounds=rounds, seed=0)
    print(_fmt_row("BiCompFL-GR-CFL", out))
    n, d = int(shards.x.shape[0]), int(theta0.shape[0])
    for scheme in ("doublesqueeze", "memsgd", "fedavg"):
        spec = registry.baseline_spec(scheme, n=n, d=d, server_lr=1.0)
        out = FLEngine(task, spec).run(shards, theta0, rounds=rounds, seed=0)
        print(_fmt_row(scheme, out))


def ablation_ndl(fast: bool):
    rounds = 4 if fast else 6
    print("\n== ablation: n_DL (paper J.3, BiCompFL-PR) ==")
    k, shards, test = _setup(iid=True)
    task = _mask_task(k, test)
    for n_dl in (2, 5, 10):
        spec = registry.bicompfl_spec("PR", allocation=FixedAllocation(128),
                                      n_is=64, n_dl=n_dl)
        out = FLEngine(task, spec).run(shards, rounds=rounds, seed=0)
        print(_fmt_row(f"PR n_DL={n_dl}", out), flush=True)
        jax.clear_caches()


def ablation_nis(fast: bool):
    rounds = 4 if fast else 6
    print("\n== ablation: n_IS (paper J.5, BiCompFL-GR) ==")
    k, shards, test = _setup(iid=True)
    task = _mask_task(k, test)
    for n_is in (16, 64, 256):
        spec = registry.bicompfl_spec("GR", allocation=FixedAllocation(128),
                                      n_is=n_is, n_dl=int(shards.x.shape[0]))
        out = FLEngine(task, spec).run(shards, rounds=rounds, seed=0)
        print(_fmt_row(f"GR n_IS={n_is}", out), flush=True)
        jax.clear_caches()


def ablation_block(fast: bool):
    rounds = 4 if fast else 6
    print("\n== ablation: block size d/B (paper J.4, BiCompFL-GR) ==")
    k, shards, test = _setup(iid=True)
    task = _mask_task(k, test)
    for bs in (64, 128, 256):
        spec = registry.bicompfl_spec("GR", allocation=FixedAllocation(bs),
                                      n_is=64, n_dl=int(shards.x.shape[0]))
        out = FLEngine(task, spec).run(shards, rounds=rounds, seed=0)
        print(_fmt_row(f"GR block={bs}", out), flush=True)
        jax.clear_caches()


def ablation_nclients(fast: bool):
    rounds = 4 if fast else 6
    print("\n== ablation: number of clients (paper J.1) ==")
    for n in (4, 8) if fast else (4, 8, 16):
        k, shards, test = _setup(iid=True, n_clients=n)
        task = _mask_task(k, test)
        spec = registry.bicompfl_spec("GR", allocation=FixedAllocation(128),
                                      n_is=64, n_dl=n)
        out = FLEngine(task, spec).run(shards, rounds=rounds, seed=0)
        print(_fmt_row(f"GR n={n}", out), flush=True)
        jax.clear_caches()


def kernel_micro(fast: bool):
    from repro.kernels import ops, ref
    mode = "interpret" if ops.resolve_interpret(None) else "compiled"
    print(f"\n== kernel microbench: mrc_logw / bernoulli_kl ({mode}) vs jnp ==")
    key = jax.random.PRNGKey(0)
    nb, nis, s = (8, 256, 256)
    x = (jax.random.uniform(key, (nb, nis, s)) < 0.5).astype(jnp.float32)
    a = jax.random.normal(jax.random.fold_in(key, 1), (nb, s))
    b = jax.random.normal(jax.random.fold_in(key, 2), (nb, s))

    def bench(f, *args, reps=5):
        out = f(*args)
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(reps):
            out = f(*args)
        jax.block_until_ready(out)
        return (time.time() - t0) / reps * 1e6, out

    t_ref, o_ref = bench(jax.jit(ref.mrc_logw_ref), x, a, b)
    t_pal, o_pal = bench(lambda *z: ops.mrc_logw(*z), x, a, b)
    err = float(jnp.max(jnp.abs(o_ref - o_pal)))
    print(f"mrc_logw ({nb}x{nis}x{s}):  jnp={t_ref:9.1f}us  "
          f"pallas({mode})={t_pal:9.1f}us  max_err={err:.2e}")
    q = jax.random.uniform(key, (64, 256), minval=0.05, maxval=0.95)
    p = jax.random.uniform(jax.random.fold_in(key, 3), (64, 256),
                           minval=0.05, maxval=0.95)
    t_ref, o_ref = bench(jax.jit(ref.bernoulli_kl_ref), q, p)
    t_pal, o_pal = bench(lambda *z: ops.bernoulli_kl(*z), q, p)
    err = float(jnp.max(jnp.abs(o_ref - o_pal)))
    print(f"bernoulli_kl (64x256):  jnp={t_ref:9.1f}us  "
          f"pallas({mode})={t_pal:9.1f}us  max_err={err:.2e}")
    if mode == "interpret":
        print("(interpret mode runs the kernel body in Python -- correctness "
              "check; TPU timing requires hardware)")


def wire_audit(fast: bool):
    """Bytes on the wire per scheme (repro.wire bitstream layer).

    Every scheme in the registry matrix runs a short ``wire="audit"`` host
    run: each payload is serialized through the codecs, the decoded values
    drive the trajectory, and the BitMeter is reconciled against the
    stream -- a booked-vs-serialized divergence raises inside ``run``.
    The table's bytes column is the *actual* stream length, not a formula.
    """
    rounds = 2 if fast else 3
    print(f"\n== wire_audit: {rounds} wire-audited host rounds, 4 clients, "
          f"reset_period=2 ==")
    k, shards, test = _setup(iid=True, n_train=240, n_test=120, hw=6)
    task = _mask_task(k, test, hw=6, width=32, local_epochs=1)
    net = make_mlp(in_dim=36, widths=(32,))
    ctask, theta0 = make_cfl_task(net, jax.random.fold_in(k, 3), test.x,
                                  test.y, local_epochs=1, batch_size=40,
                                  local_lr=3e-3)
    n, d = int(shards.x.shape[0]), int(theta0.shape[0])
    print(f"{'scheme':26s} {'bytes':>10s} {'B/round':>9s} {'payload_b':>11s} "
          f"{'framing_b':>10s} {'msgs':>5s} {'bpp':>9s}")
    for name, kind, factory in registry.all_schemes(
            n=n, d=d, n_is=16, block=64, reset_period=2,
            include_adaptive=True):
        t = task if kind == "mask" else ctask
        th0 = None if kind == "mask" else theta0
        out = FLEngine(t, factory()).run(shards, th0, rounds=rounds, seed=0,
                                         eval_every=rounds, mode="host",
                                         wire="audit")
        ws = out["wire"]
        print(f"{name:26s} {ws['stream_bytes']:>10,} "
              f"{ws['stream_bytes'] / rounds:>9,.0f} "
              f"{ws['payload_bits']:>11,} {ws['framing_bits']:>10,} "
              f"{ws['messages']:>5} {out['meter']['bpp']:>9.4f}", flush=True)
        jax.clear_caches()


def roofline(fast: bool):
    print("\n== roofline table (from dry-run artifacts) ==")
    found = False
    for path in ("dryrun_1pod.json", "dryrun_2pod.json"):
        if not os.path.exists(path):
            continue
        found = True
        with open(path) as f:
            rows = json.load(f)
        print(f"\n-- {path} --")
        hdr = (f"{'arch':26s} {'shape':12s} {'stat':5s} {'compute_s':>10s} "
               f"{'memory_s':>10s} {'collect_s':>10s} {'dominant':>10s} "
               f"{'args/dev':>10s} {'MF/HLO':>7s}")
        print(hdr)
        for r in rows:
            if r["status"] == "skip":
                print(f"{r['arch']:26s} {r['shape']:12s} skip   ({r['reason']})")
                continue
            if r["status"] != "ok":
                print(f"{r['arch']:26s} {r['shape']:12s} FAIL   {r.get('error','')[:60]}")
                continue
            rl = r["roofline"]
            chips = 512 if r["multi_pod"] else 256
            mf = r["model_flops_6nd"] / chips / max(rl["flops_per_dev"], 1)
            print(f"{r['arch']:26s} {r['shape']:12s} ok    "
                  f"{rl['compute_s']:10.4f} {rl['memory_s']:10.4f} "
                  f"{rl['collective_s']:10.4f} {rl['dominant']:>10s} "
                  f"{r['memory']['argument_bytes']/2**30:9.2f}G "
                  f"{mf:7.2f}")
    if not found:
        print("(no dryrun_*.json found -- run python -m repro.launch.dryrun --all)")


BENCHES = {
    "table_main": table_main,
    "table_cfl": table_cfl,
    "ablation_ndl": ablation_ndl,
    "ablation_nis": ablation_nis,
    "ablation_block": ablation_block,
    "ablation_nclients": ablation_nclients,
    "kernel_micro": kernel_micro,
    "wire_audit": wire_audit,
    "roofline": roofline,
}


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    t0 = time.time()
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        print(SEP)
        fn(args.fast)
    print(SEP)
    print(f"total {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
