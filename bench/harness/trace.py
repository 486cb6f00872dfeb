"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: the device's ops with their name stacks, the harness's own
host spans, and the traced window.

Layout (TPU v5e, JAX 0.9, seen in a recorded trace): each chip is a plane
``/device:TPU:<i>``, whose line ``XLA Ops`` holds one event per executed HLO
op, named by the op's HLO text; the op's name stack
(``jit(run_fn)/.../jit(local_train)/dot_general``) is the ``tf_op`` stat of
the event's metadata, read by ``xplane.op_paths``.  The host is the plane
``/host:CPU``; the harness's ``TraceAnnotation`` spans (``bench.*``) and the
Python tracer's frames (``$file.py:line name``) are events on its thread
lines.  Times are in nanoseconds; the device's clock may lead or lag the
host's by about a millisecond.  The trace is started just before the
``bench.window`` span and stopped just after it, so every device op in it
belongs to the window; the window's length is the host span's.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
BENCH_SPAN = "bench."
WINDOW_SPAN = "bench.window"


@dataclass
class Op:
    start: float
    end: float
    name: str
    path: str
    device: int


@dataclass
class Span:
    start: float
    end: float
    name: str


@dataclass
class Trace:
    ops: List[Op] = field(default_factory=list)
    spans: List[Span] = field(default_factory=list)    # bench.* spans
    frames: List[Span] = field(default_factory=list)   # Python frames
    n_devices: int = 0

    # -- the window ----------------------------------------------------------

    def window(self) -> Tuple[float, float]:
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if not w:
            raise ValueError(f"the trace has no {WINDOW_SPAN} span")
        return w[0].start, w[0].end

    def window_s(self) -> float:
        a, b = self.window()
        return (b - a) * 1e-9

    def device_offset(self) -> float:
        """Host time minus device time, from the first call's start and
        the first op's (0 when the device's clock does not lag)."""
        calls = [s.start for s in self.spans if s.name == "bench.call"]
        if not calls or not self.ops:
            return 0.0
        return max(0.0, min(calls) - min(o.start for o in self.ops))

    # -- busy time -----------------------------------------------------------

    def busy_intervals(self, device: int) -> List[Tuple[float, float]]:
        """Merged op intervals of one chip, on the host's clock."""
        off = self.device_offset()
        iv = sorted((o.start + off, o.end + off)
                    for o in self.ops if o.device == device)
        merged: List[List[float]] = []
        for s, e in iv:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the traced chips."""
        devs = sorted({o.device for o in self.ops})
        if not devs:
            return 0.0
        tot = sum(e - s for d in devs for s, e in self.busy_intervals(d))
        return tot * 1e-9 / len(devs)

    # -- attribution ---------------------------------------------------------

    def time_under(self, keys: Iterable[str]) -> Tuple[float, int]:
        """(seconds, op count) of window ops whose name stack or name holds
        any of ``keys``, summed over chips and averaged per chip."""
        keys = tuple(keys)
        hit = [o for o in self.ops
               if any(k in o.path or k in o.name for k in keys)]
        devs = max(1, len({o.device for o in self.ops}))
        secs = sum(o.end - o.start for o in hit) * 1e-9
        return secs / devs, len(hit)

    def _innermost(self, spans: Sequence[Span], t: float) -> Optional[Span]:
        """The shortest span covering t; of equal ones, the last opened."""
        best = None
        for s in spans:
            if s.start <= t < s.end and (best is None or
                                         s.end - s.start <= best.end - best.start):
                best = s
        return best

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (by name stack), and the
        longest idle gaps, each named by the harness span and the deepest
        Python frame that covered it."""
        a, b = self.window()
        by: dict = {}
        for o in self.ops:
            label = (o.path or o.name.split(" = ")[0])[-160:]
            by[label] = by.get(label, 0.0) + (o.end - o.start)
        ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for d in sorted({o.device for o in self.ops})[:1]:
            prev = a
            for s, e in self.busy_intervals(d) + [(b, b)]:
                if s > prev and prev < b:
                    gaps.append((prev, min(s, b)))
                prev = max(prev, e)
        named = []
        for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
            mid = (g0 + g1) / 2
            span = self._innermost(self.spans, mid)
            frame = self._innermost(self.frames, mid)
            label = " | ".join(x.name for x in (span, frame) if x)
            named.append((label or "untraced host", g1 - g0))
        gaps = named
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}


def load(directory: str) -> Trace:
    from jax.profiler import ProfileData
    from .xplane import op_paths
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    with open(paths[0], "rb") as f:
        data = f.read()
    return parse(ProfileData.from_file(paths[0]), op_paths(data))


def parse(pd, paths: dict) -> Trace:
    """``pd``: a ``ProfileData``; ``paths``: an op's event name -> its name
    stack."""
    tr = Trace()
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX):
            dev = int(name[len(DEVICE_PREFIX):].split()[0])
            tr.n_devices += 1
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    tr.ops.append(Op(e.start_ns, e.start_ns + e.duration_ns,
                                     e.name, paths.get(e.name, ""), dev))
        elif name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    s = Span(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name.startswith(BENCH_SPAN):
                        tr.spans.append(s)
                    elif e.name.startswith("$"):
                        tr.frames.append(s)
    return tr


@dataclass
class MetricContext:
    """What a per-layer metric reader gets."""
    trace: Trace
    cell: object
    rounds: int
    window_s: float
    peaks: Optional[dict]
