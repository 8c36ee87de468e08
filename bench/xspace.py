"""A reader of the profiler's ``.xplane.pb`` (an ``XSpace`` message) that
needs no protobuf library.

``jax.profiler.ProfileData`` gives each event's name, times and its own
stats, but not the stats of its *event metadata*: there a device op's
``tf_op`` stat holds the JAX scope path it was emitted under (for example
``jit(evolve_chunk)/while/body/vmap(active_mask)/while/body/or``).  This
module decodes the protobuf wire format of the messages of TSL's
``xplane.proto`` that carry them.  Field numbers:

* ``XSpace``: planes 1;
* ``XPlane``: id 1, name 2, lines 3, event_metadata 4 (map), stat_metadata
  5 (map); a map entry is key 1, value 2;
* ``XLine``: id 1 (a host line's thread id), name 2, timestamp_ns 3,
  events 4;
* ``XEvent``: metadata_id 1, offset_ps 2, duration_ps 3;
* ``XEventMetadata``: id 1, name 2, stats 5;
* ``XStatMetadata``: id 1, name 2;
* ``XStat``: metadata_id 1, then one value: double 2, uint64 3, int64 4,
  str 5, bytes 6, ref 7 (the id of a stat metadata whose name is the
  string).

Lines are decoded only when asked for (``Plane.lines``): a traced window
holds millions of device events, its metadata a few thousand entries.
"""
from __future__ import annotations

import struct
from dataclasses import dataclass, field

VARINT, I64, LEN, I32 = 0, 1, 2, 5


def _varint(buf: bytes, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def fields(buf: bytes, start: int = 0, end: int | None = None):
    """(field number, wire type, value) of each field of one message; a
    length-delimited value is the ``(start, end)`` of its bytes in
    ``buf``."""
    pos, end = start, len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == VARINT:
            value, pos = _varint(buf, pos)
        elif wire == LEN:
            n, pos = _varint(buf, pos)
            value = (pos, pos + n)
            pos += n
        elif wire == I64:
            value = buf[pos:pos + 8]
            pos += 8
        elif wire == I32:
            value = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield num, wire, value


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat(buf: bytes, span, stat_names: dict[int, str]):
    """(metadata id, value) of one ``XStat``."""
    mid, value = 0, None
    for num, wire, v in fields(buf, *span):
        if num == 1:
            mid = v
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = _text(buf, v)
        elif num == 6:
            value = buf[v[0]:v[1]]
        elif num == 7:
            value = stat_names.get(v, "")
    return mid, value


@dataclass(frozen=True)
class RawEvent:
    metadata_id: int
    start_ps: int      # the line's timestamp plus the event's offset
    duration_ps: int


@dataclass
class Plane:
    name: str = ""
    #: stat metadata id -> stat name
    stat_names: dict[int, str] = field(default_factory=dict)
    #: event metadata id -> (name, {stat name: value})
    event_metadata: dict[int, tuple[str, dict]] = field(default_factory=dict)
    _buf: bytes = b""
    _lines: list[tuple[int, int]] = field(default_factory=list)

    def lines(self):
        """(id, name, [RawEvent]) of each line, decoded now."""
        for span in self._lines:
            line_id, name, ts_ns, events = 0, "", 0, []
            for num, _, v in fields(self._buf, *span):
                if num == 1:
                    line_id = _signed(v)
                elif num == 2:
                    name = _text(self._buf, v)
                elif num == 3:
                    ts_ns = _signed(v)
                elif num == 4:
                    events.append(v)
            yield line_id, name, [self._event(s, ts_ns * 1000)
                                  for s in events]

    def _event(self, span, base_ps: int) -> RawEvent:
        mid = offset = duration = 0
        for num, _, v in fields(self._buf, *span):
            if num == 1:
                mid = v
            elif num == 2:
                offset = _signed(v)
            elif num == 3:
                duration = _signed(v)
        return RawEvent(mid, base_ps + offset, duration)


def _map_entries(buf: bytes, span) -> tuple[int, tuple[int, int] | None]:
    key, value = 0, None
    for num, _, v in fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane(buf: bytes, span) -> Plane:
    plane = Plane(_buf=buf)
    metadata = []
    for num, _, v in fields(buf, *span):
        if num == 2:
            plane.name = _text(buf, v)
        elif num == 3:
            plane._lines.append(v)
        elif num == 4:
            metadata.append(v)
        elif num == 5:
            key, value = _map_entries(buf, v)
            if value is not None:
                plane.stat_names[key] = next(
                    (_text(buf, s) for n, _, s in fields(buf, *value)
                     if n == 2), "")
    # stat names first: an event metadata's stats name themselves by id
    for entry in metadata:
        key, value = _map_entries(buf, entry)
        if value is None:
            continue
        name, stats = "", {}
        for num, _, v in fields(buf, *value):
            if num == 2:
                name = _text(buf, v)
            elif num == 5:
                mid, stat = _stat(buf, v, plane.stat_names)
                stats[plane.stat_names.get(mid, str(mid))] = stat
        plane.event_metadata[key] = (name, stats)
    return plane


def read(path: str) -> list[Plane]:
    """Every plane of the ``XSpace`` stored at ``path``."""
    with open(path, "rb") as f:
        buf = f.read()
    return [_plane(buf, v) for num, wire, v in fields(buf)
            if num == 1 and wire == LEN]
