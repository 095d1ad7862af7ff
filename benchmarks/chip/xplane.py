"""Event metadata of a profiler trace (`*.xplane.pb`), read without a
protobuf schema.

`jax.profiler.ProfileData` gives every event with its start and length,
but not the stats that the TPU runtime attaches to an op's metadata:
its HLO category (`convolution fusion`, `all-gather`, ...) and the JAX
op name it came from. This reads those from the protobuf wire format,
field numbers as in `tsl/profiler/protobuf/xplane.proto`:

    XSpace        planes=1
    XPlane        name=2, event_metadata=4 (map), stat_metadata=5 (map)
    XEventMetadata  id=1, name=2, display_name=4, stats=5
    XStatMetadata   id=1, name=2
    XStat         metadata_id=1, double=2, uint64=3, int64=4, str=5,
                  bytes=6, ref=7
"""
from __future__ import annotations


def _varint(buf: bytes, i: int):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one message; a length-delimited
    value is returned as bytes, a fixed-width one as raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, wire, val


def _map_entry(buf: bytes):
    """(key, value bytes) of a map<int64, message> entry."""
    key, value = 0, b""
    for field, _, val in _fields(buf):
        if field == 1:
            key = val
        elif field == 2:
            value = val
    return key, value


def _stat_value(buf: bytes, stat_names: dict):
    """(name, value) of an XStat; a ref_value names another stat
    metadata entry, whose name is the value."""
    name, value = None, None
    for field, _, val in _fields(buf):
        if field == 1:
            name = stat_names.get(val, str(val))
        elif field in (3, 4):
            value = val
        elif field == 5:
            value = val.decode("utf-8", "replace")
        elif field == 7:
            value = stat_names.get(val, val)
    return name, value


def event_metadata(path: str) -> dict:
    """{plane name: {event name: {stat name: value}}} for every plane
    of the trace at `path` whose event metadata carries stats."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        name, events, stats = "", [], {}
        for pf, _, val in _fields(plane):
            if pf == 2:
                name = val.decode("utf-8", "replace")
            elif pf == 4:
                events.append(_map_entry(val)[1])
            elif pf == 5:
                _, meta = _map_entry(val)
                sid, sname = 0, ""
                for mf, _, mval in _fields(meta):
                    if mf == 1:
                        sid = mval
                    elif mf == 2:
                        sname = mval.decode("utf-8", "replace")
                stats[sid] = sname
        per_event = {}
        for meta in events:
            ename, estats = "", []
            for ef, _, eval_ in _fields(meta):
                if ef == 2:
                    ename = eval_.decode("utf-8", "replace")
                elif ef == 5:
                    estats.append(eval_)
            if estats:
                per_event[ename] = dict(_stat_value(s, stats)
                                        for s in estats)
        if per_event:
            out[name] = per_event
    return out
