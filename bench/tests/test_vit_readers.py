"""The ViT readers (``vit_attn_ms``, ``vit_mlp_ms``,
``vit_attn_roofline_pct``) on a hand-built trace whose answers are known,
and their silence on a trace of a net without ``vit.*`` scopes."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from harness import trace as tr
from harness.cell import Cell, load_module

DATA = Path(__file__).parent / "data"
CELL = "vitb16-cifar10.gr-silo"
LT = "jit(fl_rounds)/while/body/fl.train/vmap(jit(local_train))/while/body/"
EV = "jit(fl_rounds)/while/body/fl.eval/cond/jit(evaluate)/"
# One call's device ops, ns: (start, end, name stack).  The attention's
# ops overlap once (100-300 and 250-400), so its union is 300, not 350.
CALL = [
    (0, 2000, "jit(fl_rounds)/while"),
    (0, 1500, "jit(fl_rounds)/while/body/fl.train/"
              "vmap(jit(local_train))/while"),
    (50, 100, LT + "jvp(vit.patch)/dot_general"),
    (100, 300, LT + "jvp(vit.attn)/dot_general"),
    (250, 400, LT + "jvp(vit.attn)/softmax"),
    (400, 700, LT + "jvp(vit.mlp)/dot_general"),
    (700, 900, LT + "transpose(jvp(vit.mlp))/dot_general"),
    (900, 1000, LT + "transpose(jvp(vit.attn))/dot_general"),
    (1000, 1100, LT + "transpose(jvp(vit.head))/dot_general"),
    (1500, 1800, "jit(fl_rounds)/while/body/fl.uplink/jit(encode_fixed)/"
                 "while"),
    (1800, 1850, EV + "vit.attn/dot_general"),
    (1850, 1900, EV + "vit.mlp/dot_general"),
]
SECOND = 5000


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def fake():
    ops, paths = [], {}
    for shift in (0, SECOND):
        for i, (s, e, path) in enumerate(CALL):
            name = f"op{shift}.{i}"
            ops.append(ev(name, s + shift, e - s))
            paths[name] = path
    pd = NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=[
            ev("bench.window", 0, 10000)])]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])])
    return tr.parse(pd, paths)


@pytest.fixture(scope="module")
def cell():
    return Cell.load(CELL)


def ctx(trace, cell, rounds=2):
    return tr.MetricContext(trace=trace, cell=cell, rounds=rounds,
                            window_s=1e-5, peaks=None)


def read(metric, c):
    return load_module("metrics", f"{metric}.py").read(c)


PEAK = 197e12


def test_scope_readers_count_forward_transpose_and_eval(cell):
    c = ctx(fake(), cell)
    # attention: 100-400 (union), 900-1000, 1800-1850 -> 450 ns a call
    assert read("vit_attn_ms", c) == pytest.approx(450e-6)
    # mlp: 400-900, 1850-1900 -> 550 ns a call
    assert read("vit_mlp_ms", c) == pytest.approx(550e-6)


def test_roofline_is_attention_flops_over_its_time(cell):
    c = ctx(fake(), cell)
    assert read("vit_attn_roofline_pct", c) is None  # no peaks: rehearsal
    c.peaks = {"bf16_flops_per_s": PEAK}
    flops = load_module("flops", "make_vit.py")
    f = flops.attn_flops(**cell.net["args"])
    per_round = 4 * 15 * 32 * 3 * f + 500 * f / 2  # 4 silos, 15 steps
    want = 100 * per_round * 2 / (2 * 450e-9) / PEAK
    assert read("vit_attn_roofline_pct", c) == pytest.approx(want)


def test_attention_flops_at_published_widths(cell):
    """ViT-B/16 at 4 layers: 1.049 GFLOP of attention a layer and sample
    (projections 929.6 M, Q K^T and P V 119.2 M), 11.86 GFLOP in all."""
    flops = load_module("flops", "make_vit.py")
    args = cell.net["args"]
    assert flops.attn_flops(**args) == 4 * (2 * 197 * 768 * 3072
                                            + 4 * 197 * 197 * 768)
    assert flops.forward_flops(**args) == pytest.approx(11.863e9, rel=1e-3)


@pytest.mark.parametrize("metric", ["vit_attn_ms", "vit_mlp_ms",
                                    "vit_attn_roofline_pct"])
def test_silent_on_a_net_without_vit_scopes(metric):
    """The recorded v5e trace of the MLP cell's program has no ``vit.*``
    scope: each reader returns None, not 0."""
    t = tr.load(str(DATA / "scoped"))
    assert t.ops
    c = tr.MetricContext(trace=t, cell=Cell.load("mlp2nn-mnist.pr"),
                         rounds=2, window_s=1e-3,
                         peaks={"bf16_flops_per_s": PEAK})
    assert read(metric, c) is None
