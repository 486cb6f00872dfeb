#!/usr/bin/env python3
"""Bring-up smoke test of the BiCompFL training path on one TPU chip.

    python3 chip_smoke.py

One process drives the FL engine through its user entry points
(``registry.*_spec`` -> ``FLEngine(task, spec).run``) with compiled Pallas
kernels, and checks what comes out.  Phases, in order, each printing one
line per check with its own numbers:

1. device     -- platform, device kind, device count, compile-cache
                 directory; anything but a TPU exits non-zero.
2. reference  -- the MNIST-MLP analog of ``benchmarks/fl_round_bench.py``
                 (10 clients x 200 synthetic 10x10 samples, MLP 100-256-10,
                 d = 28,160 mask parameters, n_is = 64), 15 fused rounds of
                 bicompfl-gr, bicompfl-pr, bicompfl-gr-adaptive-avg,
                 bicompfl-gr-adaptive and fedavg; each held to an accuracy
                 floor from a CPU run of the same seed.
3. oracle     -- bicompfl-gr host loop vs fused scan (meters equal, theta
                 bit for bit), and a 2-round wire audit that must reconcile.
4. kernels    -- bicompfl-gr with ``pallas_logw=True`` and
                 bicompfl-gr-adaptive with ``segment_logw_pallas=True`` end
                 to end; each FL kernel lowered at the run's shapes must
                 hold a ``tpu_custom_call`` and match its jnp route.
5. wide       -- ``make_cnn(hw=28)`` at its default widths (d = 421,408),
                 3 fused rounds of bicompfl-gr and bicompfl-gr-adaptive
                 (segment kernel), with the device's peak memory.

Any failure exits non-zero before the last line.  The last line of
standard output is one JSON object: ``{"ok": true, "device": {...}}``.
Wall seconds printed per run (cold = first run, compile included; warm =
the same engine run again) are informational, not benchmark metrics.

``tests/test_chip_smoke.py`` rehearses every phase on the CPU at a tiny
size, with the kernels in interpret mode.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0

# Final-accuracy floors for the reference phase.  A CPU run of this seed
# (kernels in interpret mode) reaches 1.0 test accuracy by round 5 and
# holds it to round 15 in every scheme below.  The chip's f32 matmuls
# default to one bf16 pass, so its trajectories differ from the CPU's;
# each floor is the CPU accuracy less 0.1.
FLOORS: Dict[str, float] = {
    name: 0.9 for name in (
        "bicompfl-gr", "bicompfl-pr", "bicompfl-gr-adaptive-avg",
        "bicompfl-gr-adaptive", "fedavg", "bicompfl-gr+pallas_logw",
        "bicompfl-gr-adaptive+segment_logw")
}

# Kernel vs jnp route on the same inputs: |out - ref| <= RTOL * max|ref| +
# ATOL.  The routes differ only in f32 summation order (the kernels' MXU
# products run at Precision.HIGHEST; the fixed-block reference einsum is
# evaluated at the same precision).
KERNEL_RTOL = 1e-5
KERNEL_ATOL = 1e-6


@dataclass(frozen=True)
class Sizes:
    n_clients: int = 10
    hw: int = 10
    per_client: int = 200
    n_test: int = 400
    width: int = 256
    n_is: int = 64
    block: int = 128
    rounds: int = 15
    eval_every: int = 5
    local_epochs: int = 3
    batch_size: int = 128
    wide_hw: int = 28
    wide_conv: Tuple[int, ...] = (32, 64)
    wide_dense: Tuple[int, ...] = (128,)
    wide_rounds: int = 3
    expect_d: Tuple[int, int] = (28160, 421408)


REFERENCE = Sizes()


class SmokeFailure(AssertionError):
    """A check of the smoke test failed."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@dataclass
class Setup:
    sizes: Sizes
    task: object
    ctask: object
    theta0: object
    shards: object
    factories: Dict[str, Tuple[str, object]] = field(default_factory=dict)


def build_reference(sizes: Sizes) -> Setup:
    """The fl_round_bench full setup (same keys, noise and optimizers)."""
    import jax
    from repro.core.blocks import (AdaptiveAllocation, AdaptiveAvgAllocation,
                                   FixedAllocation)
    from repro.fl import registry
    from repro.fl.data import make_synthetic, partition_iid
    from repro.fl.nets import make_mlp
    from repro.fl.tasks import make_cfl_task, make_mask_task

    s = sizes
    k = jax.random.PRNGKey(SEED)
    n, hw = s.n_clients, s.hw
    train, test = make_synthetic(k, n_train=n * s.per_client,
                                 n_test=s.n_test, hw=hw, noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, n, s.per_client)
    net = make_mlp(in_dim=hw * hw, widths=(s.width,), signed_constant=True)
    task = make_mask_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                          local_epochs=s.local_epochs,
                          batch_size=s.batch_size)
    cnet = make_mlp(in_dim=hw * hw, widths=(s.width,))
    ctask, theta0 = make_cfl_task(cnet, jax.random.fold_in(k, 3), test.x,
                                  test.y, local_epochs=s.local_epochs,
                                  batch_size=s.batch_size, local_lr=3e-3)
    check(task.d == s.expect_d[0], f"reference d={task.d}, "
          f"expected {s.expect_d[0]}")

    def mrc(variant, alloc):
        return lambda **kw: registry.bicompfl_spec(
            variant, allocation=alloc(), n_is=s.n_is, n_dl=n, **kw)

    factories = {
        "bicompfl-gr": ("mask", mrc("GR", lambda: FixedAllocation(s.block))),
        "bicompfl-pr": ("mask", mrc("PR", lambda: FixedAllocation(s.block))),
        "bicompfl-gr-adaptive-avg": (
            "mask", mrc("GR", lambda: AdaptiveAvgAllocation(n_is=s.n_is))),
        "bicompfl-gr-adaptive": (
            "mask", mrc("GR", lambda: AdaptiveAllocation(n_is=s.n_is))),
        "fedavg": ("cfl", lambda: registry.baseline_spec(
            "fedavg", n=n, d=int(theta0.shape[0]))),
    }
    return Setup(s, task, ctask, theta0, shards, factories)


def run_scheme(setup: Setup, name: str, factory, *, kind: str = "mask",
               rounds: int, mode: str = "fused", **run_kw):
    """Run one scheme twice on one engine; return (out, cold_s, warm_s).

    The second run reuses the engine's compiled program and must repeat
    the first bit for bit.
    """
    import jax
    import numpy as np
    from repro.fl.engine import FLEngine

    task = setup.task if kind == "mask" else setup.ctask
    theta0 = None if kind == "mask" else setup.theta0
    engine = FLEngine(task, factory())
    times, outs = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        out = engine.run(setup.shards, theta0, rounds=rounds, seed=SEED,
                         eval_every=min(setup.sizes.eval_every, rounds),
                         mode=mode, **run_kw)
        jax.block_until_ready(out["theta"])
        times.append(time.perf_counter() - t0)
        outs.append(out)
    out = outs[0]
    check(out["mode"] == mode, f"{name}: ran mode={out['mode']!r}, "
          f"expected {mode!r}")
    check(np.array_equal(np.asarray(out["theta"]),
                         np.asarray(outs[1]["theta"])),
          f"{name}: a repeated run of the same engine changed theta")
    theta = np.asarray(out["theta"])
    check(np.all(np.isfinite(theta)), f"{name}: non-finite theta")
    check(np.isfinite(out["final_acc"]), f"{name}: non-finite accuracy")
    return out, times[0], times[1]


def report_run(phase: str, name: str, out, cold: float, warm: float,
               floor: float) -> None:
    acc = out["final_acc"]
    print(f"[{phase}] {name}: mode={out['mode']} final_acc={acc!r} "
          f"floor={floor!r} bpp={out['meter']['bpp']!r} "
          f"cold_s={cold:.3f} warm_s={warm:.3f}", flush=True)
    check(acc >= floor, f"{name}: final accuracy {acc} below floor {floor}")


def phase_reference(setup: Setup, floors: Dict[str, float]):
    """Every reference scheme, fused; returns {name: out}."""
    outs = {}
    for name, (kind, factory) in setup.factories.items():
        out, cold, warm = run_scheme(setup, name, factory, kind=kind,
                                     rounds=setup.sizes.rounds)
        report_run("reference", name, out, cold, warm, floors.get(name, 0.0))
        outs[name] = out
    return outs


def phase_oracle(setup: Setup, fused_gr) -> None:
    """Host loop vs fused scan on bicompfl-gr; 2-round wire audit."""
    import numpy as np
    from repro.fl.engine import FLEngine

    factory = setup.factories["bicompfl-gr"][1]
    host, cold, _ = run_scheme(setup, "bicompfl-gr/host", factory,
                               rounds=setup.sizes.rounds, mode="host")
    a, b = np.asarray(host["theta"]), np.asarray(fused_gr["theta"])
    diff = float(np.max(np.abs(a - b)))
    same = np.array_equal(a, b)
    print(f"[oracle] bicompfl-gr host vs fused: meters_equal="
          f"{host['meter'] == fused_gr['meter']} theta_bitwise={same} "
          f"max_abs_dtheta={diff!r} differing={int(np.sum(a != b))}/{a.size} "
          f"host_acc={host['final_acc']!r} fused_acc="
          f"{fused_gr['final_acc']!r} host_cold_s={cold:.3f}", flush=True)
    check(host["meter"] == fused_gr["meter"], "oracle: meters differ")
    check(same, f"oracle: theta differs between host and fused "
          f"(max |dtheta| = {diff})")

    out = FLEngine(setup.task, factory()).run(
        setup.shards, rounds=2, seed=SEED, eval_every=2, mode="host",
        wire="audit")
    ws = out["wire"]  # reconcile raised if booked bits != stream bits
    print(f"[oracle] bicompfl-gr wire audit (2 rounds): reconciled "
          f"stream_bytes={ws['stream_bytes']} payload_bits="
          f"{ws['payload_bits']} framing_bits={ws['framing_bits']} "
          f"messages={ws['messages']}", flush=True)


def _compare(name: str, fn, ref_fn, args, expect_kernel: bool) -> None:
    """Lower ``fn`` at these shapes, look for the kernel, compare values."""
    import jax
    import numpy as np

    text = jax.jit(fn).lower(*args).as_text()
    has_kernel = "tpu_custom_call" in text
    out = np.asarray(jax.jit(fn)(*args), np.float64)
    ref = np.asarray(jax.jit(ref_fn)(*args), np.float64)
    check(out.shape == ref.shape, f"{name}: shape {out.shape} != {ref.shape}")
    err = float(np.max(np.abs(out - ref)))
    tol = KERNEL_RTOL * float(np.max(np.abs(ref))) + KERNEL_ATOL
    print(f"[kernels] {name} shape={tuple(out.shape)}: tpu_custom_call="
          f"{has_kernel} max_abs_err={err!r} tol={tol!r}", flush=True)
    check(has_kernel == expect_kernel,
          f"{name}: tpu_custom_call present={has_kernel}, expected "
          f"{expect_kernel}")
    check(err <= tol, f"{name}: |kernel - jnp| = {err} > {tol}")


def phase_kernels(setup: Setup, floors: Dict[str, float]) -> None:
    """The registry's kernel hooks end to end, then kernel vs jnp route."""
    import jax
    import jax.numpy as jnp
    from repro.core import mrc
    from repro.core.bernoulli import bern_kl, clip01, log_ratio_coeffs
    from repro.core.blocks import AdaptiveAllocation
    from repro.fl import engine
    from repro.kernels import ops

    s = setup.sizes
    gr = setup.factories["bicompfl-gr"][1]
    ada = setup.factories["bicompfl-gr-adaptive"][1]
    runs = {"bicompfl-gr+pallas_logw": (gr, {"pallas_logw": True}),
            "bicompfl-gr-adaptive+segment_logw":
                (ada, {"segment_logw_pallas": True})}
    for name, (factory, kw) in runs.items():
        out, cold, warm = run_scheme(
            setup, name, lambda f=factory, kw=kw: f(**kw), rounds=s.rounds)
        report_run("kernels", name, out, cold, warm, floors.get(name, 0.0))

    on_chip = not ops.resolve_interpret(None)
    d, n, n_is = setup.task.d, s.n_clients, s.n_is
    k = jax.random.fold_in(jax.random.PRNGKey(SEED), 7)
    kq, kp, ku, kx = jax.random.split(k, 4)
    q = jax.random.uniform(kq, (n, d), minval=0.02, maxval=0.98)
    p = jax.random.uniform(kp, (n, d), minval=0.02, maxval=0.98)

    # Fixed-block MRC weights, at the hook the registry hands the channels,
    # on one encoder chunk of blocks.
    logw_fn = gr(pallas_logw=True).uplink.logw_fn
    nb = min(gr().uplink.chunk, d // s.block)
    blk_q = q[0, :nb * s.block].reshape(nb, s.block)
    blk_p = p[0, :nb * s.block].reshape(nb, s.block)
    a, b = log_ratio_coeffs(blk_q, blk_p)
    x = (jax.random.uniform(kx, (nb, n_is, s.block))
         < blk_p[:, None, :]).astype(jnp.float32)

    def logw_ref(x, a, b):
        with jax.default_matmul_precision("highest"):
            return mrc.default_logw(x, a, b)

    _compare("mrc_logw", logw_fn, logw_ref, (x, a, b), on_chip)

    # Segment weights at the largest bucket, on a plan the fused engine
    # would build from this KL profile.
    alloc = AdaptiveAllocation(n_is=n_is)
    klp = jnp.mean(jax.vmap(bern_kl)(q, clip01(p)), axis=0)
    stats = {"profile": klp, "total": jnp.sum(klp)}
    template = alloc.bucket_plans(d)[-1]
    plan = alloc.finalize_plan(template, stats, d)
    n_seg = template.n_blocks
    seg_fn = ada(segment_logw_pallas=True).uplink.seg_logw_fn
    u = mrc._segment_candidates(ku, n_is, d)
    pc = clip01(p[0])
    a1, b1 = log_ratio_coeffs(q[0], p[0])
    _compare(f"segment_logw(n_seg={n_seg})",
             lambda *z: seg_fn(*z, n_seg),
             lambda *z: mrc.default_segment_logw(*z, n_seg),
             (u, pc, a1, b1, plan.seg_ids), on_chip)

    # KL statistics, through the engine's own route selection.
    def kl_ref(q, p, profile):
        klp = jnp.mean(jax.vmap(bern_kl)(q, clip01(p)), axis=0)
        return klp if profile else jnp.sum(klp)

    for profile, what in ((True, "profile"), (False, "total")):
        _compare(f"bernoulli_kl_{what}",
                 lambda q, p, pr=profile: engine._kl_stats(
                     q, p, needs_profile=pr)["profile" if pr else "total"],
                 lambda q, p, pr=profile: kl_ref(q, p, pr),
                 (q, p), on_chip)


def phase_wide(sizes: Sizes) -> None:
    """The widest net fl/nets.py builds, a few fused rounds."""
    import jax
    from repro.core.blocks import AdaptiveAllocation, FixedAllocation
    from repro.fl import registry
    from repro.fl.data import make_synthetic, partition_iid
    from repro.fl.nets import make_cnn
    from repro.fl.tasks import make_mask_task

    s = sizes
    k = jax.random.fold_in(jax.random.PRNGKey(SEED), 11)
    n = s.n_clients
    train, test = make_synthetic(k, n_train=n * s.per_client,
                                 n_test=s.n_test, hw=s.wide_hw, noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, n, s.per_client)
    net = make_cnn(hw=s.wide_hw, channels=1, conv_widths=s.wide_conv,
                   dense_widths=s.wide_dense, signed_constant=True)
    task = make_mask_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                          local_epochs=s.local_epochs,
                          batch_size=s.batch_size)
    check(task.d == s.expect_d[1], f"wide d={task.d}, "
          f"expected {s.expect_d[1]}")
    setup = Setup(s, task, None, None, shards)
    runs = {
        "bicompfl-gr": lambda: registry.bicompfl_spec(
            "GR", allocation=FixedAllocation(s.block), n_is=s.n_is, n_dl=n),
        "bicompfl-gr-adaptive+segment_logw": lambda: registry.bicompfl_spec(
            "GR", allocation=AdaptiveAllocation(n_is=s.n_is), n_is=s.n_is,
            n_dl=n, segment_logw_pallas=True),
    }
    dev = jax.devices()[0]
    for name, factory in runs.items():
        out, cold, warm = run_scheme(setup, f"wide/{name}", factory,
                                     rounds=s.wide_rounds)
        stats = dev.memory_stats() or {}
        print(f"[wide] {name} d={task.d}: mode={out['mode']} final_acc="
              f"{out['final_acc']!r} bpp={out['meter']['bpp']!r} "
              f"cold_s={cold:.3f} warm_s={warm:.3f} peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use', 'not reported')}",
              flush=True)


class CompileMeter:
    """Counts persistent-cache hits/misses and backend compile seconds."""

    def __init__(self):
        self.hits = self.misses = 0
        self.compile_s = 0.0

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_listener(self.on_event)
        mon.register_event_duration_secs_listener(self.on_duration)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_listener(self.on_event)
        mon.unregister_event_duration_listener(self.on_duration)


def run_phases(sizes: Sizes, floors: Dict[str, float]) -> None:
    """Phases 2-5 (everything after the device check)."""
    setup = build_reference(sizes)
    outs = phase_reference(setup, floors)
    phase_oracle(setup, outs["bicompfl-gr"])
    phase_kernels(setup, floors)
    phase_wide(sizes)


def main(argv=None) -> int:
    argparse.ArgumentParser(description=__doc__.split("\n\n")[0]).parse_args(
        argv)
    src = os.path.join(ROOT, "src")
    sys.path.insert(0, src)
    try:
        from repro import compile_cache
        where = os.path.abspath(compile_cache.__file__)
    except ImportError as e:
        where = str(e)
    if not where.startswith(src + os.sep):
        print(f"chip_smoke: the repro package is not in {src} ({where})",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if dev.platform != "tpu":
        print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
              f"count={len(devices)}: no TPU, refusing to run",
              file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable()
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)
    t0 = time.perf_counter()
    try:
        with CompileMeter() as cm:
            run_phases(REFERENCE, FLOORS)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[compile] persistent_cache_hits={cm.hits} "
          f"persistent_cache_misses={cm.misses} backend_compile_s="
          f"{cm.compile_s:.3f} total_s={time.perf_counter() - t0:.3f}",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
