"""The name stacks of a trace's device ops, read from the ``.xplane.pb``.

``jax.profiler.ProfileData`` gives each event's name and times but not the
stats of its metadata, where the TPU profiler keeps an op's name stack
(``tf_op``: ``jit(run_fn)/.../jit(local_train)/dot_general``).  This reads
just that from the protobuf wire format (XSpace > XPlane > event and stat
metadata; field numbers as in TSL's ``xplane.proto``), with no dependency.
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, v


def _map_values(entry: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for num, _, v in _fields(entry):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def op_paths(data: bytes, prefix: str = "/device:") -> Dict[str, str]:
    """{event name: name stack} over the planes whose name starts with
    ``prefix``; an event is known by its metadata's name and display name."""
    out: Dict[str, str] = {}
    for num, _, plane in _fields(data):
        if num != 1:  # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for f, _, v in _fields(plane):
            if f == 2:
                name = v.decode("utf-8", "replace")
            elif f == 4:  # event_metadata map
                events.append(_map_values(v)[1])
            elif f == 5:  # stat_metadata map
                _, sm = _map_values(v)
                sid, sname = 0, ""
                for g, _, w in _fields(sm):
                    if g == 1:
                        sid = w
                    elif g == 2:
                        sname = w.decode("utf-8", "replace")
                stat_names[sid] = sname
        if not name.startswith(prefix):
            continue
        for em in events:
            names, path = [], None
            for g, _, w in _fields(em):
                if g in (2, 4):  # name, display_name
                    names.append(w.decode("utf-8", "replace"))
                elif g == 5:  # stats
                    sid, sval = 0, None
                    for h, _, x in _fields(w):
                        if h == 1:
                            sid = x
                        elif h == 5:
                            sval = x.decode("utf-8", "replace")
                    if stat_names.get(sid) == "tf_op" and sval is not None:
                        path = sval
            if path is not None:
                for nm in names:
                    out[nm] = path
    return out
