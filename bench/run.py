#!/usr/bin/env python3
"""The on-chip FL-round benchmark: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``bench/workloads/<cell>.json``: a configuration (net and data,
``bench/configs/``) under a traffic mix (scheme and deployment,
``bench/traffic/``).  The run builds the data on the device from the seed,
hands it to the program (``FLEngine.run(mode="fused")`` on the scheme the
registry builds), and drives it in a closed loop: each call runs
``rounds_per_call`` rounds from the previous call's model with a fresh seed
derived from ``--seed`` and the call's number.

Set-up (counted in ``setup_s``): import, data, engine, and the first
``REF_STEPS`` calls, which compile every program and are the calls the
correctness check follows.  The window then repeats calls until
``--seconds`` have passed; nothing compiles inside it.  With ``--trace 0``
the result carries the end-to-end metrics (``round_s``, ``peak_hbm_mib``,
``setup_s``); with ``--trace 1`` a few calls of the window are traced and
each reader under ``bench/metrics/`` reduces the trace to one per-layer
metric.  After the window the plain reference (``bench/reference/``) reruns
the first calls, and ``correct`` says whether the program agreed with it
within the cell's limits.

Anything but a TPU exits non-zero before any result, unless ``--rehearse``
asks for a CPU rehearsal at each file's own tiny sizes, which prints no
device metric.  The last line of standard output is one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
N_TRACED_MIN, TRACED_S = 2, 3.0  # traced calls: at least 2, about 3 s
MIB = 2 ** 20


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; prints no metric")
    return ap.parse_args(argv)


def log(*a):
    print(*a, flush=True)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 1


def setup_jax(rehearse: bool):
    """Persistent compile cache: the checkout's fixed ``.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; small programs cached too.
    A CPU rehearsal keeps no cache."""
    import jax
    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
        return jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def record(out):
    import numpy as np
    from harness.correct import Step
    return Step(theta=np.asarray(out["theta"], np.float32),
                theta_hat=np.asarray(out["theta_hat"], np.float32),
                bits=float(out["meter"]["total_bits"]),
                acc=[h["acc"] for h in out["history"]])


def first_steps(cell, seed: int, *, spec_transform=None, call_hook=None):
    """Data, program and the program's first ``REF_STEPS`` calls.

    ``spec_transform`` and ``call_hook`` let a test or the calibration
    break the timed path underneath: a spec with a faulty part, or a call
    put in the program's place (``call_hook(program) -> call``)."""
    import numpy as np
    from harness.cell import REF_STEPS
    from harness.correct import Record
    from harness.program import Program

    data = cell.make_data(seed)
    net_key = cell.net_key(seed)
    program = Program(cell, data, net_key, spec_transform=spec_transform)
    call = program.call if call_hook is None else call_hook(program)
    theta = program.theta0
    prog = Record(theta0=np.asarray(theta, np.float32))
    for k in range(REF_STEPS):
        out = call(theta, cell.call_seed(seed, k))
        prog.steps.append(record(out))
        theta = out["theta"]
    return data, net_key, program, call, prog, theta


def compare(cell, data, net_key, seed: int, prog, dtype=None,
            with_reference=False):
    """Readings of ``prog`` against the reference's run of the same seed
    (and that run, with ``with_reference``)."""
    import jax.numpy as jnp
    from harness import correct, refrun
    loss, slices = refrun.loss_fn(cell, data, net_key)
    for s in prog.steps:
        s.loss = loss(s.theta)
    ref = refrun.run(cell, data, net_key, seed, dtype or jnp.float32)
    values = correct.readings(prog, ref, slices)
    return (values, ref) if with_reference else values


def run_cell(args, *, spec_transform=None, call_hook=None):
    """Everything after the device check; returns (result, checks)."""
    import jax
    import numpy as np
    from harness import correct
    from harness.cell import REF_STEPS, Cell
    from harness.compile_meter import CompileMeter

    cell = Cell.load(args.workload, rehearse=args.rehearse)
    if cell.d != int(cell.config["d"]):
        raise ValueError(f"layer shapes give d={cell.d}, the configuration "
                         f"says {cell.config['d']}")
    seed = args.seed
    dev = jax.devices()[0]

    with CompileMeter() as cm:
        data, net_key, program, call, prog, theta = first_steps(
            cell, seed, spec_transform=spec_transform, call_hook=call_hook)
    setup_s = time.perf_counter() - T_START
    log(f"[setup] setup_s={setup_s!r} calls={REF_STEPS} {cm.line()}")

    k = REF_STEPS
    rounds = calls = 0
    traced, call_s = None, []
    with CompileMeter() as wm:
        if args.trace:
            tdir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(tdir)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    t0 = time.perf_counter()
                    while (calls < N_TRACED_MIN
                           or time.perf_counter() - t0 < TRACED_S):
                        with jax.profiler.TraceAnnotation("bench.call"):
                            out = call(theta, cell.call_seed(seed, k))
                        theta, k = out["theta"], k + 1
                        calls, rounds = calls + 1, rounds + cell.rounds_per_call
                    window_s = time.perf_counter() - t0
            finally:
                jax.profiler.stop_trace()
            traced = tdir
        else:
            t0 = t_prev = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.call"):
                    out = call(theta, cell.call_seed(seed, k))
                theta, k = out["theta"], k + 1
                calls, rounds = calls + 1, rounds + cell.rounds_per_call
                t_now = time.perf_counter()
                call_s.append(t_now - t_prev)
                t_prev = t_now
                if t_now - t0 >= args.seconds:
                    break
            window_s = t_prev - t0
    finite = bool(np.all(np.isfinite(np.asarray(theta))))
    log(f"[window] calls={calls} rounds={rounds} window_s={window_s!r} "
        f"compiles_in_window={wm.compiles} {wm.line()} "
        f"call_s={json.dumps(call_s[:40])}")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    del program, call, out, theta

    result = {"correct": False, "attempted": rounds,
              "failed": 0 if finite else rounds, "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": peak}}
    if traced is not None:
        try:
            per_layer, dev_times, breakdown = reduce_trace(
                traced, cell, rounds, window_s, args)
            result["device"].update(dev_times)
            result["metrics"] = per_layer
            result["breakdown"] = breakdown
        except Exception:  # the run still reports; its metrics are missing
            traceback.print_exc()
        finally:
            shutil.rmtree(traced, ignore_errors=True)
    elif not args.rehearse:
        result["metrics"] = {
            "round_s": {"value": window_s / rounds, "unit": "s"},
            "peak_hbm_mib": {"value": peak / MIB, "unit": "MiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    if args.rehearse:
        result["metrics"] = {}
        result["rehearsal"] = True

    t_ref = time.perf_counter()
    try:
        values = compare(cell, data, net_key, seed, prog)
    except Exception:  # a comparison that gives no number has failed
        traceback.print_exc()
        values = {}
    ok, rows = correct.judge(values, cell.workload["limits"])
    log(f"[reference] steps={REF_STEPS} reference_s="
        f"{time.perf_counter() - t_ref!r} readings={json.dumps(values)}")
    result["correct"] = ok and finite
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    return result, rows


def reduce_trace(tdir, cell, rounds, window_s, args):
    """Per-layer metrics, device busy time and the breakdown of a trace."""
    import jax
    from harness import trace as tr
    from harness.cell import load_json, load_module
    t = tr.load(tdir)
    kind = jax.devices()[0].device_kind
    peaks = None if args.rehearse else load_json("peaks.json")[kind]
    ctx = tr.MetricContext(trace=t, cell=cell, rounds=rounds,
                           window_s=window_s, peaks=peaks)
    metrics = {}
    for path in sorted((BENCH / "metrics").glob("*.py")):
        mod = load_module("metrics", path.name)
        value = mod.read(ctx)
        if value is not None:
            metrics[path.name[:-3]] = {"value": value, "unit": mod.UNIT}
    busy_s, span_s = t.busy_s(), t.window_s()
    return metrics, {"busy_s": busy_s, "window_s": span_s}, t.breakdown()


def main(argv=None) -> int:
    args = parse(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        return fail(f"no program under {src}")
    sys.path.insert(0, str(src))
    sys.path.insert(0, str(BENCH))
    jax = setup_jax(args.rehearse)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return fail(f"JAX finds no device: {e}")
    dev = devices[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)} rehearse={args.rehearse}")
    from harness.cell import load_json
    try:
        chips = int(load_json("workloads", f"{args.workload}.json")["chips"])
    except FileNotFoundError:
        return fail(f"no cell {args.workload!r} under bench/workloads")
    if not args.rehearse:
        if dev.platform != "tpu":
            return fail(f"platform {dev.platform!r} is not a TPU; "
                        "--rehearse runs the CPU rehearsal")
        if len(devices) < chips:
            return fail(f"the cell needs {chips} chips, JAX finds "
                        f"{len(devices)}")
        if dev.device_kind not in load_json("peaks.json"):
            return fail(f"device kind {dev.device_kind!r} is not in "
                        "bench/peaks.json")
    result, rows = run_cell(args)
    for name, value, limit in rows:
        print(f"check {name}={value!r} limit={limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
