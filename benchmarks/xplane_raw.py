"""A reader for the profiler's `.xplane.pb` that keeps what
`jax.profiler.ProfileData` leaves out: the statistics of an event's
*metadata*. On the TPU plane an operation's `tf_op` (its `op_name`),
`program_id`, `hlo_category`, `flops` and `bytes_accessed` are stored once per
distinct operation, on the XEventMetadata, not on each event, and
ProfileData shows an event's own statistics only.

The file is one protobuf message (tsl/profiler/protobuf/xplane.proto); this
reads the wire format directly — varints, length-delimited fields — for the
few messages and fields it needs, with nothing but the standard library:

    XSpace        planes=1
    XPlane        name=2 lines=3 event_metadata=4 stat_metadata=5 (maps)
    XLine         name=2 timestamp_ns=3 events=4
    XEvent        metadata_id=1 offset_ps=2 duration_ps=3 stats=4
    XEventMetadata id=1 name=2 display_name=4 stats=5
    XStatMetadata  id=1 name=2
    XStat         metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7
"""

from __future__ import annotations

import struct


def _varint(buf, i):
    """The varint at byte `i` → (value, the byte after it)."""
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def fields(buf):
    """One message's fields → (number, wire type, value): an int for
    varints and fixed widths, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 1:
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 5:
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names: dict):
    """One XStat → (name, value)."""
    name = value = None
    for num, _wt, val in fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", val.to_bytes(8, "little"))[0]
        elif num == 3:
            value = val
        elif num == 4:
            value = _signed(val)
        elif num == 5:
            value = bytes(val).decode("utf-8", "replace")
        elif num == 6:
            value = bytes(val)
        elif num == 7:
            value = stat_names.get(val, str(val))
    return name, value


def _map_entry(buf):
    key = value = None
    for num, _wt, val in fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def read_planes(path: str, want_plane, want_line):
    """→ [{"name", "lines": [{"name", "timestamp_ns", "events": [(metadata
    id, start s, end s, {stat: value})]}], "metadata": {id: {"name",
    "display_name", "stats": {...}}}}] for the planes `want_plane(name)`
    accepts, with the events of the lines `want_line(name)` accepts. Times
    are seconds from the line's own timestamp."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for num, _wt, plane in fields(space):
        if num != 1:
            continue
        name, lines, emeta, smeta = "", [], [], []
        for pnum, _pwt, val in fields(plane):
            if pnum == 2:
                name = bytes(val).decode("utf-8", "replace")
            elif pnum == 3:
                lines.append(val)
            elif pnum == 4:
                emeta.append(val)
            elif pnum == 5:
                smeta.append(val)
        if not want_plane(name):
            continue
        stat_names = {}
        for entry in smeta:
            key, val = _map_entry(entry)
            for snum, _swt, sval in fields(val):
                if snum == 2:
                    stat_names[key] = bytes(sval).decode("utf-8", "replace")
        metadata = {}
        for entry in emeta:
            key, val = _map_entry(entry)
            md = {"name": "", "display_name": "", "stats": {}}
            for mnum, _mwt, mval in fields(val):
                if mnum == 2:
                    md["name"] = bytes(mval).decode("utf-8", "replace")
                elif mnum == 4:
                    md["display_name"] = bytes(mval).decode("utf-8",
                                                            "replace")
                elif mnum == 5:
                    k, v = _stat(mval, stat_names)
                    md["stats"][k] = v
            metadata[key] = md
        got_lines = []
        for line in lines:
            lname, ts_ns, events = "", 0, []
            for lnum, _lwt, lval in fields(line):
                if lnum == 2:
                    lname = bytes(lval).decode("utf-8", "replace")
                elif lnum == 3:
                    ts_ns = lval
                elif lnum == 4:
                    events.append(lval)
            if not want_line(lname):
                continue
            evs = []
            for ev in events:
                mid = off = dur = 0
                stats = {}
                for enum_, _ewt, evval in fields(ev):
                    if enum_ == 1:
                        mid = evval
                    elif enum_ == 2:
                        off = evval
                    elif enum_ == 3:
                        dur = evval
                    elif enum_ == 4:
                        k, v = _stat(evval, stat_names)
                        stats[k] = v
                evs.append((mid, off * 1e-12, (off + dur) * 1e-12, stats))
            got_lines.append({"name": lname, "timestamp_ns": ts_ns,
                              "events": evs})
        out.append({"name": name, "lines": got_lines, "metadata": metadata})
    return out
