"""A reader of the XPlane protobuf (``.xplane.pb``) wire format, for what
``jax.profiler.ProfileData`` leaves out: the stats of each event's
metadata, among them ``tf_op`` (the op's name stack, e.g.
``jit(step)/jvp(attn)/dot_general:``), ``program_id``, ``flops`` and
``bytes_accessed``.  It needs no TensorFlow and no generated code.

The messages read (field numbers of ``tsl/profiler/protobuf/xplane.proto``)::

    XSpace         { XPlane planes = 1; }
    XPlane         { int64 id = 1; string name = 2; XLine lines = 3;
                     map<int64, XEventMetadata> event_metadata = 4;
                     map<int64, XStatMetadata> stat_metadata = 5; }
    XLine          { string name = 2; int64 timestamp_ns = 3;
                     XEvent events = 4; }
    XEvent         { int64 metadata_id = 1; int64 offset_ps = 2;
                     int64 duration_ps = 3; }
    XEventMetadata { int64 id = 1; string name = 2; XStat stats = 5; }
    XStatMetadata  { int64 id = 1; string name = 2; }
    XStat          { int64 metadata_id = 1; double double_value = 2;
                     uint64 uint64_value = 3; int64 int64_value = 4;
                     string str_value = 5; bytes bytes_value = 6;
                     uint64 ref_value = 7; }

A map entry is a message with the key in field 1 and the value in field 2.
A ``ref_value`` names a stat metadata entry whose name is the string.
"""

from __future__ import annotations

import dataclasses
import struct
from typing import Callable, Dict, Iterator, List, Optional, Tuple, Union

Stat = Union[int, float, str, bytes]


@dataclasses.dataclass
class Line:
    name: str
    timestamp_ns: int
    events: List[Tuple[int, int, int]]      # (metadata_id, offset_ps, dur_ps)


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]
    event_metadata: Dict[int, Tuple[str, Dict[str, Stat]]]  # id -> name, stats


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, i: int, end: int
            ) -> Iterator[Tuple[int, int, Union[int, Tuple[int, int]]]]:
    """(field number, wire type, value) of one message in ``buf[i:end]``:
    an int for varints and fixed widths (raw bits), (start, end) for
    length-delimited fields."""
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wire == 5:
            value = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield field, wire, value


def _signed(x: int) -> int:
    return x - (1 << 64) if x >= 1 << 63 else x


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", errors="replace")


def _map_entry(buf: bytes, span: Tuple[int, int]):
    key, value = 0, (span[1], span[1])
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat(buf, span, stat_names: Dict[int, str]) -> Tuple[int, Stat]:
    mid, value = 0, None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _text(buf, v)
        elif f == 6:
            value = buf[v[0]:v[1]]
        elif f == 7:
            value = stat_names.get(v, "")
    return mid, value


def _line(buf: bytes, span: Tuple[int, int]) -> Line:
    name, ts, events = "", 0, []
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            ts = _signed(v)
        elif f == 4:
            mid = off = dur = 0
            for ef, _, ev in _fields(buf, *v):
                if ef == 1:
                    mid = ev
                elif ef == 2:
                    off = ev
                elif ef == 3:
                    dur = ev
            events.append((mid, off, dur))
    return Line(name, ts, events)


def _plane(buf: bytes, span: Tuple[int, int]) -> Plane:
    name, line_spans, meta_spans, stat_names = "", [], [], {}
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            line_spans.append(v)
        elif f == 4:
            meta_spans.append(v)
        elif f == 5:
            key, value = _map_entry(buf, v)
            for sf, _, sv in _fields(buf, *value):
                if sf == 2:
                    stat_names[key] = _text(buf, sv)
    metadata = {}
    for entry in meta_spans:
        key, value = _map_entry(buf, entry)
        ev_name, stats = "", {}
        for f, _, v in _fields(buf, *value):
            if f == 2:
                ev_name = _text(buf, v)
            elif f == 5:
                mid, stat = _stat(buf, v, stat_names)
                stats[stat_names.get(mid, str(mid))] = stat
        metadata[key] = (ev_name, stats)
    return Plane(name, [_line(buf, s) for s in line_spans], metadata)


def read(path: str, want: Optional[Callable[[str], bool]] = None
         ) -> List[Plane]:
    """The planes of the trace at ``path`` whose names ``want`` accepts
    (all without it), in the file's order; the others are skipped
    undecoded."""
    with open(path, "rb") as f:
        buf = f.read()
    out = []
    for field, wire, span in _fields(buf, 0, len(buf)):
        if field != 1 or wire != 2:
            continue
        if want is not None:
            name = next((_text(buf, v) for f, _, v in _fields(buf, *span)
                         if f == 2), "")
            if not want(name):
                continue
        out.append(_plane(buf, span))
    return out
