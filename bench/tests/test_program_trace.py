"""The program's marks in a trace (``harness/program_trace.py``) and the
readers built on them, on a hand-built trace whose answers are known."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from harness import program_trace as pt
from harness import trace as tr
from harness.cell import load_module

DATA = Path(__file__).parent / "data"
RUN = "jit(fl_rounds)/while/body/"
ENC = "jit(encode_fixed)/while/body/"
# One call's device ops on the host's clock, ns: (start, end, name stack).
# The scan's while spans the round; the uplink's encode is a while that
# spans its body; one bookkeeping op lies outside every stage scope.
CALL = [
    (300, 2000, "jit(fl_rounds)/while"),
    (300, 400, RUN + "fl.train/vmap(jit(local_train))/while/body/"
               "local.batch/gather"),
    (400, 700, RUN + "fl.train/vmap(jit(local_train))/while/body/dot"),
    (700, 1200, RUN + "fl.uplink/jit(encode_fixed)/while"),
    (720, 900, RUN + "fl.uplink/" + ENC + "mrc.draw/threefry2x32"),
    (900, 1100, RUN + "fl.uplink/" + ENC + "mrc.logw/dot_general"),
    (1100, 1180, RUN + "fl.uplink/" + ENC + "argmax"),
    (1200, 1250, RUN + "fl.aggregate/reduce_sum"),
    (1250, 1500, RUN + "fl.downlink/vmap(" + ENC + "mrc.draw/convert)"),
    (1500, 1800, RUN + "fl.downlink/vmap(" + ENC + "mrc.logw/dot_general)"),
    (1800, 1900, RUN + "fl.downlink/vmap(" + ENC + "take_along_axis)"),
    (1900, 1910, RUN + "dynamic_update_slice"),
    (1910, 2000, RUN + "fl.eval/cond/dot_general"),
]
TILE = (50, 60, "jit(tile)/broadcast_in_dim")  # fl.prepare's device work
SECOND = 5000  # the second call: the first shifted by this


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return NS(name=name,
              lines=[NS(name=k, events=v) for k, v in lines.items()])


def fake(off=0, dispatch2=5150, program=True):
    """Two calls of a scoped program, the device's clock ``off`` ns behind
    the host's (ahead when negative).  Call 1: prepare 0-100, dispatch
    100-200, fetch 200-2100, book 2100-2300; call 2 the same 5000 ns
    later, but dispatched at ``dispatch2`` for 50 ns and fetched until
    7050.  The scoped ops start 150 to 200 ns after their dispatch and end
    50 to 100 ns before their fetch does: the offset lies in
    [off - 150, off + 50]."""
    ops, paths = [], {}
    for shift in (0, SECOND):
        for i, (s, e, path) in enumerate([TILE] + CALL):
            name = f"op{shift}.{i}"
            ops.append(ev(name, s + shift - off, e - s))
            paths[name] = path
    host = [ev("bench.window", 0, 10000), ev("bench.call", 0, 2400),
            ev("bench.call", SECOND, 2400),
            ev("$engine.py:1 _run_fused", SECOND, 2300)]
    if program:
        host += [ev("fl.prepare", 0, 100), ev("fl.dispatch", 100, 100),
                 ev("fl.fetch", 200, 1900), ev("fl.book", 2100, 200),
                 ev("fl.prepare", SECOND, 150),
                 ev("fl.dispatch", dispatch2, 50),
                 ev("fl.fetch", dispatch2 + 50, SECOND + 2000 - dispatch2),
                 ev("fl.book", SECOND + 2050, 250)]
    pd = NS(planes=[plane("/host:CPU", {"python": host}),
                    plane("/device:TPU:0", {"XLA Ops": ops})])
    return tr.parse(pd, paths), pt.parse_spans(pd)


def ctx(trace, rounds=2):
    return tr.MetricContext(trace=trace, cell=None, rounds=rounds,
                            window_s=1e-5, peaks=None)


def read(metric, trace, rounds=2):
    return load_module("metrics", f"{metric}.py").read(ctx(trace, rounds))


def test_merge_and_union():
    assert pt.merge([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    assert pt.union_length([(0, 4), (1, 2), (6, 7)]) == 5
    assert pt.merge([]) == []


@pytest.mark.parametrize("metric,ns", [
    ("uplink_ms", 500),       # the encode's while, not 500+180+200+80
    ("downlink_ms", 650),
    ("eval_ms", 90),
    ("mrc_draw_ms", 180 + 250),
    ("mrc_logw_ms", 200 + 300),
    ("local_batch_ms", 100),
    ("unattributed_ms", 10 + 10),  # the bookkeeping op and the tile
])
def test_scope_readers(metric, ns):
    t, _ = fake()
    # two calls over two rounds: one call's ns a round, in ms
    assert read(metric, t) == pytest.approx(ns * 1e-6)


def test_while_spanning_scoped_children_counted_once():
    t, _ = fake()
    secs, n = pt.time_union(t, ["fl.uplink"])
    assert n == 8 and secs == pytest.approx(2 * 500e-9)
    assert t.time_under(["fl.uplink"])[0] == pytest.approx(2 * 960e-9)
    scoped, _ = pt.time_union(t, pt.STAGES)
    assert t.busy_s() - scoped == pytest.approx(2 * 20e-9)


def test_readers_silent_without_scopes():
    t, _ = fake(program=False)
    for op in t.ops:
        op.path = op.path.replace("fl.", "x_").replace("mrc.", "y_") \
            .replace("local.batch", "z")
    for metric in ("uplink_ms", "downlink_ms", "eval_ms", "mrc_draw_ms",
                   "mrc_logw_ms", "local_batch_ms", "unattributed_ms"):
        assert read(metric, t) is None


def test_program_spans_kept_apart_from_bench_spans():
    t, spans = fake()
    assert {s.name for s in spans} == {"fl.prepare", "fl.dispatch",
                                       "fl.fetch", "fl.book"}
    assert all(s.name.startswith("bench.") for s in t.spans)
    assert pt.program_calls(spans) == [(100, 2100), (5150, 7050)]


@pytest.mark.parametrize("off", [800.0, -800.0, 0.0])
def test_clock_aligned_by_program_spans(off):
    """A device clock behind (800) or ahead (-800) of the host's."""
    t, spans = fake(off)
    got, width = pt.clock_alignment(t, spans)
    assert width == pytest.approx(200)
    assert got == pytest.approx(off - 50)
    groups = pt.call_ops(t, 2)
    for (d0, f1), g in zip(pt.program_calls(spans), groups):
        assert all(d0 <= o.start + got and o.end + got <= f1 for o in g)


def test_clock_without_program_spans_is_the_first_call_offset():
    t, _ = fake(-800.0)
    assert pt.clock_alignment(t, []) == (t.device_offset(), None)
    assert t.device_offset() == 0.0  # the old rule leaves it uncorrected


def test_infeasible_bounds_give_a_negative_width():
    t, spans = fake(dispatch2=5400)  # dispatched after its first op ran
    assert pt.clock_alignment(t, spans)[1] < 0


def test_gap_labels_carry_the_program_span():
    t, spans = fake()
    gaps = pt.idle_gaps(t, spans)
    # on the aligned clock: 1950-5000 and 6950-10000 after each call's
    # program, 10-250 and 5010-5250 between a tile and its program
    assert [(k, pytest.approx(v)) for k, v in gaps] == [
        ("bench.window | fl.fetch>fl.book", 3050e-9),
        ("bench.window | fl.fetch>fl.book", 3050e-9),
        ("bench.call | fl.prepare>fl.dispatch>fl.fetch", 240e-9),
        ("bench.call | fl.prepare>fl.dispatch>fl.fetch | "
         "$engine.py:1 _run_fused", 240e-9)]


def test_gap_labels_unchanged_without_program_spans():
    t, _ = fake(300.0)
    assert [list(g) for g in pt.idle_gaps(t, [])] == \
        t.breakdown()["idle_gaps"]


def test_recorded_v5e_scoped_trace():
    """Two one-round calls of the PR cell's fused program at its rehearsal
    sizes, traced on a TPU v5e (fields no reader reads removed)."""
    directory = str(DATA / "scoped")
    t, spans = tr.load(directory), pt.load_spans(directory)
    assert t.n_devices == 1 and len(pt.program_calls(spans)) == 2
    c = ctx(t, rounds=2)
    got = {m: read(m, t) for m in ("uplink_ms", "downlink_ms", "eval_ms",
                                   "mrc_draw_ms", "mrc_logw_ms",
                                   "local_batch_ms", "unattributed_ms")}
    assert all(v is not None and v > 0 for v in got.values()), got
    mrc = load_module("metrics", "mrc_ms.py").read(c)
    local = load_module("metrics", "local_train_ms.py").read(c)
    assert got["mrc_draw_ms"] + got["mrc_logw_ms"] <= mrc
    assert mrc <= got["uplink_ms"] + got["downlink_ms"]
    assert got["local_batch_ms"] <= local
    off, width = pt.clock_alignment(t, spans)
    assert width > 0
    groups = pt.call_ops(t, 2)
    assert sum(map(len, groups)) == sum(
        1 for o in t.ops if any(s in o.path for s in pt.STAGES))
    for (d0, f1), g in zip(pt.program_calls(spans), groups):
        assert all(d0 <= o.start + off and o.end + off <= f1 for o in g)
    assert all(" | fl." in label for label, s in pt.idle_gaps(t, spans)
               if s >= 5e-4)


def test_command_line_on_a_kept_trace(capsys):
    import json
    assert pt.main([str(DATA / "scoped")]) == 0
    out = json.loads(capsys.readouterr().out)
    t = tr.load(str(DATA / "scoped"))
    assert (out["clock_offset_ns"], out["clock_interval_ns"]) == \
        pt.clock_alignment(t, pt.load_spans(str(DATA / "scoped")))
    assert out["idle_gaps"] and all(" | fl." in g for g, _ in
                                    out["idle_gaps"])
