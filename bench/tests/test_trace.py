"""The trace-to-metric reduction, on a hand-built trace whose answers are
known, and on a small trace recorded on a TPU v5e (where present)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from harness import trace as tr

DATA = Path(__file__).parent / "data"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def fake():
    """Window 0..1000 ns.  Device ops: 100-300 (local_train), 250-400
    (overlaps it; encode_fixed), 600-700 (the KL kernel), 950-1100 (runs
    past the window).  Busy = [100, 400] + [600, 700] + [950, 1100] =
    550 ns; idle gaps 0-100, 400-600 (under bench.wait) and 700-950."""
    dev = plane("/device:TPU:0", {
        "XLA Ops": [
            ev("fusion.1", 100, 200),
            ev("fusion.2", 250, 150),
            ev("%kl.1 = f32[8,4096] custom-call(...)", 600, 100),
            ev("copy.3", 950, 150),
        ],
        "XLA Modules": [ev("jit_run_fn", 0, 1100)],
    })
    host = plane("/host:CPU", {"python": [
        ev("bench.window", 0, 1000),
        ev("bench.call", 0, 1000),
        ev("bench.wait", 400, 250),
        ev("$engine.py:1227 _run_fused", 380, 300),
        ev("$other.py:1 f", 0, 60),
    ]})
    return NS(planes=[host, dev])


PATHS = {"fusion.1": "jit(run_fn)/vmap(jit(local_train))/dot",
         "fusion.2": "jit(run_fn)/jit(encode_fixed)/argmax",
         "%kl.1 = f32[8,4096] custom-call(...)":
             "jit(run_fn)/jit(bernoulli_kl_pallas)/pallas_call"}


def test_busy_and_window():
    t = tr.parse(fake(), PATHS)
    assert t.window() == (0, 1000)
    assert t.window_s() == pytest.approx(1e-6)
    assert t.busy_s() == pytest.approx(550e-9)


def test_time_under_name_stack():
    t = tr.parse(fake(), PATHS)
    assert t.time_under(["local_train"]) == (pytest.approx(200e-9), 1)
    assert t.time_under(["encode_fixed", "_encode_segments"]) == \
        (pytest.approx(150e-9), 1)
    assert t.time_under(["bernoulli_kl_pallas)/pallas_call"]) == \
        (pytest.approx(100e-9), 1)
    assert t.time_under(["nothing"]) == (0.0, 0)


def test_breakdown_names_gaps_by_host_span_and_frame():
    b = tr.parse(fake(), PATHS).breakdown()
    assert b["device_ops"][0][0].endswith("local_train))/dot")
    (l1, s1), (l2, s2), (l3, s3) = b["idle_gaps"]  # 700-950, 400-600, 0-100
    assert (l1, l2, l3) == ("bench.call", "bench.wait | $engine.py:1227 "
                            "_run_fused", "bench.call | $other.py:1 f")
    assert (s1, s2, s3) == (pytest.approx(250e-9), pytest.approx(200e-9),
                            pytest.approx(100e-9))


def test_idle_share_metric():
    from harness.cell import load_module
    mod = load_module("metrics", "device_idle_pct.py")
    ctx = tr.MetricContext(trace=tr.parse(fake(), PATHS), cell=None, rounds=1,
                           window_s=1e-6, peaks=None)
    assert mod.read(ctx) == pytest.approx(45.0)


def test_recorded_v5e_trace():
    """Three calls of a small jitted step (a ``local_train`` jit and the
    ``bernoulli_kl`` kernel under one name scope), traced on a TPU v5e."""
    t = tr.load(str(DATA))
    assert t.n_devices == 1
    assert t.window_s() == pytest.approx(3001790e-9)
    assert [s.name for s in t.spans].count("bench.call") == 3
    secs, n = t.time_under(["jit(local_train)"])
    assert n == 3 and secs == pytest.approx(3 * 1.75e-6, rel=0.01)
    ksecs, kn = t.time_under(["bernoulli_kl_pallas)/pallas_call"])
    assert kn == 3 and 0 < ksecs < secs
    assert 0 < t.busy_s() < t.window_s()
    b = t.breakdown()
    assert b["device_ops"][0][0].endswith("jit(local_train)/dot_general:")
    assert all(g > 0 for _, g in b["idle_gaps"])
