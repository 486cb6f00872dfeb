"""The program's own marks in a profiler trace, read beside ``trace.py``.

The program names its work in two ways:

* on the device, by ``jax.named_scope``: every op of a round lies under
  one round-stage scope (``STAGES``, ``repro/fl/engine.py``), and the
  layers that hold most of the time carry sub-scopes (``mrc.draw`` and
  ``mrc.logw`` in ``repro/core/mrc.py``, ``local.batch`` in
  ``repro/fl/tasks.py``).  A scope lands in each op's name stack
  (``Op.path``).  A fusion carries its root op's name stack, so a scope's
  boundary is only as sharp as XLA's fusions.
* on the host, by ``jax.profiler.TraceAnnotation`` spans on the clock of
  the harness's ``bench.*`` spans (``FLEngine.run``): ``fl.prepare``,
  ``fl.dispatch`` (the compiled program's call), ``fl.fetch`` (the first
  read of its outputs, which waits for the device), ``fl.book`` and
  ``fl.checkpoint``.

``trace.Trace`` keeps only the harness's spans.  This module reads the
program's from the same file, aligns the device clock by them and names
each idle gap by what the program was doing.  On a program without these
marks nothing is under a scope, there is no program span, and the
alignment is ``Trace.device_offset``'s.

    cd bench && python3 -m harness.program_trace <trace directory>

prints the alignment and the longest idle gaps of a trace kept on disk.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Iterable, List, Optional, Sequence, Tuple

from harness.trace import HOST_PLANE, Op, Span, Trace

STAGES = ("fl.train", "fl.control", "fl.uplink", "fl.aggregate",
          "fl.downlink", "fl.flush", "fl.faults", "fl.eval")
PROGRAM_SPAN = "fl."
DISPATCH_SPAN, FETCH_SPAN = "fl.dispatch", "fl.fetch"


# -- device time under a scope -----------------------------------------------

def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """The union of intervals as sorted, disjoint intervals.  A ``while``
    op's event spans the ops of its body, so a sum of durations would
    count the body twice; the union counts each instant once."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merge(intervals))


def time_union(trace: Trace, keys: Iterable[str]) -> Tuple[float, int]:
    """(seconds, op count): the union of the intervals of the ops whose
    name stack holds any of ``keys``, per chip, averaged over the chips."""
    keys = tuple(keys)
    hit = [o for o in trace.ops if any(k in o.path for k in keys)]
    devs = max(1, len({o.device for o in trace.ops}))
    ns = sum(union_length((o.start, o.end) for o in hit if o.device == d)
             for d in {o.device for o in hit})
    return ns * 1e-9 / devs, len(hit)


def ms_per_round(ctx, keys: Iterable[str]) -> Optional[float]:
    """Device milliseconds a round under ``keys``; None when no op ran
    there."""
    secs, n = time_union(ctx.trace, keys)
    return secs * 1e3 / ctx.rounds if n else None


def unattributed(trace: Trace) -> Tuple[float, int]:
    """(seconds, count of scoped ops): busy time outside every round-stage
    scope, per chip; (0, 0) when no op carries one."""
    scoped, n = time_union(trace, STAGES)
    return (trace.busy_s() - scoped, n) if n else (0.0, 0)


# -- the program's host spans ------------------------------------------------

def parse_spans(pd) -> List[Span]:
    """The ``fl.*`` events of the host plane of a ``ProfileData``."""
    return [Span(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in pd.planes if plane.name == HOST_PLANE
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM_SPAN)]


def load_spans(directory: str) -> List[Span]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return parse_spans(ProfileData.from_file(paths[0]))


def program_calls(spans: Sequence[Span]) -> List[Tuple[float, float]]:
    """(``fl.dispatch`` start, end of the first ``fl.fetch`` after it) of
    each call of a compiled program, in order."""
    fetches = sorted((s for s in spans if s.name == FETCH_SPAN),
                     key=lambda s: s.start)
    calls = []
    for d in sorted((s for s in spans if s.name == DISPATCH_SPAN),
                    key=lambda s: s.start):
        f = next((f for f in fetches if f.start >= d.start), None)
        if f is not None:
            calls.append((d.start, f.end))
    return calls


def call_ops(trace: Trace, n_calls: int) -> List[List[Op]]:
    """The ops under a round-stage scope, one group per program call:
    split at the ``n_calls - 1`` longest gaps between them (a call's own
    ops run back to back; the host's booking and preparation lie between
    calls).  Empty when there are fewer such ops than calls."""
    ops = sorted((o for o in trace.ops if any(s in o.path for s in STAGES)),
                 key=lambda o: o.start)
    if not n_calls or len(ops) < n_calls:
        return []
    gaps, end = [], ops[0].end
    for i in range(1, len(ops)):
        gaps.append((ops[i].start - end, i))
        end = max(end, ops[i].end)
    cuts = sorted(i for _, i in sorted(gaps, reverse=True)[:n_calls - 1])
    return [ops[a:b] for a, b in zip([0] + cuts, cuts + [len(ops)])]


def clock_alignment(trace: Trace, spans: Sequence[Span]
                    ) -> Tuple[float, Optional[float]]:
    """(offset, width), in ns: host time minus device time, and the width
    of the interval it was taken from.

    Each call's scoped ops must begin after its ``fl.dispatch`` starts and
    end before its ``fl.fetch`` ends.  Those bounds leave an interval of
    offsets; its middle is taken.  A negative width means no offset meets
    every bound.  Without program spans: ``Trace.device_offset()``, width
    None."""
    calls = program_calls(spans)
    groups = call_ops(trace, len(calls))
    if not groups:
        return trace.device_offset(), None
    lo = max(d0 - min(o.start for o in g) for (d0, _), g in zip(calls, groups))
    hi = min(f1 - max(o.end for o in g) for (_, f1), g in zip(calls, groups))
    return (lo + hi) / 2, hi - lo


def idle_gaps(trace: Trace, spans: Sequence[Span], top: int = 10
              ) -> List[Tuple[str, float]]:
    """The longest idle gaps of the first chip in the window, on the
    aligned clock, as (label, seconds).  A label is ``bench span | program
    spans | frame``: the innermost harness span and Python frame that
    cover the gap's middle, and the program spans the gap overlaps, in
    order (``fl.fetch>fl.book>fl.prepare`` for a gap between two calls).
    With no program span it reads as ``Trace.breakdown``'s."""
    a, b = trace.window()
    off = clock_alignment(trace, spans)[0]
    gaps = []
    for d in sorted({o.device for o in trace.ops})[:1]:
        prev = a
        busy = merge((o.start + off, o.end + off)
                     for o in trace.ops if o.device == d)
        for s, e in busy + [(b, b)]:
            if s > prev and prev < b:
                gaps.append((prev, min(s, b)))
            prev = max(prev, e)
    out = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (g0 + g1) / 2
        names: List[str] = []
        for p in sorted(spans, key=lambda p: p.start):
            if p.start < g1 and p.end > g0 and names[-1:] != [p.name]:
                names.append(p.name)
        bench = trace._innermost(trace.spans, mid)
        frame = trace._innermost(trace.frames, mid)
        label = " | ".join(x for x in (bench and bench.name, ">".join(names),
                                       frame and frame.name) if x)
        out.append((label or "untraced host", (g1 - g0) * 1e-9))
    return out


def main(argv=None) -> int:
    from harness import trace as tr
    (directory,) = argv if argv is not None else sys.argv[1:]
    t, spans = tr.load(directory), load_spans(directory)
    off, width = clock_alignment(t, spans)
    print(json.dumps({"clock_offset_ns": off, "clock_interval_ns": width,
                      "idle_gaps": idle_gaps(t, spans)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
