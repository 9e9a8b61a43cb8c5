"""The raw `.xplane.pb`, walked on the wire: what `jax.profiler.ProfileData`
leaves out.

`ProfileData` gives every event its name and times; the name stack that
put a device operation there (`jit(f)/while/body/lgbm.route/select_n:`) is
a stat (`tf_op`) of the operation's *event metadata*, which `ProfileData`
does not surface, and no generated `xplane_pb2` is installed. The schema is
public (tsl/profiler/protobuf/xplane.proto) and small, so this module reads
the protobuf wire format itself, only the fields it needs:

    XSpace          1 planes
    XPlane          2 name, 3 lines, 4 event_metadata (map), 5 stat_metadata (map)
    XLine           2 name, 3 timestamp_ns, 4 events
    XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps
    XEventMetadata  1 id, 2 name, 5 stats
    XStatMetadata   1 id, 2 name
    XStat           1 metadata_id, 5 str_value, 6 bytes_value, 7 ref_value

An event's times come out as `ProfileData`'s do (line timestamp plus the
event's offset), so both readings of one file share a clock.
"""
from __future__ import annotations

from trace import DEVICE_PREFIX, HOST_PREFIX, OPS_LINE, op_name

NAME_STACK_STAT = "tf_op"


def _varint(buf: bytes, at: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def fields(buf: bytes):
    """(field number, value) of every field of one message: an int for a
    varint, the bytes for a length-delimited or fixed-width field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"wire type {wire} at byte {at}: not an xplane")
        yield key >> 3, value


def _map_value(entry: bytes) -> bytes:
    """The value (field 2) of one protobuf map entry."""
    for number, value in fields(entry):
        if number == 2:
            return value
    return b""


def _plane(buf: bytes) -> dict:
    name, lines, event_meta, stat_names = "", [], {}, {}
    for number, value in fields(buf):
        if number == 2:
            name = value.decode()
        elif number == 3:
            lines.append(value)
        elif number == 4:
            meta = {"name": "", "stats": []}
            for n, v in fields(_map_value(value)):
                if n == 1:
                    meta["id"] = v
                elif n == 2:
                    meta["name"] = v.decode(errors="replace")
                elif n == 5:
                    meta["stats"].append(v)
            event_meta[meta.get("id", 0)] = meta
        elif number == 5:
            ident, text = 0, ""
            for n, v in fields(_map_value(value)):
                if n == 1:
                    ident = v
                elif n == 2:
                    text = v.decode(errors="replace")
            stat_names[ident] = text
    return {"name": name, "lines": lines, "event_meta": event_meta,
            "stat_names": stat_names}


def _name_stack(meta: dict, stat_names: dict) -> str:
    """The `tf_op` stat of one event metadata ("" where it has none)."""
    for stat in meta["stats"]:
        ident, text = None, ""
        for n, v in fields(stat):
            if n == 1:
                ident = v
            elif n in (5, 6):
                text = v.decode(errors="replace")
            elif n == 7:  # a string kept once, as a stat metadata's name
                text = stat_names.get(v, "")
        if stat_names.get(ident) == NAME_STACK_STAT:
            return text
    return ""


def _events(line: bytes) -> tuple:
    """(line name, [(metadata id, start_ns, duration_ns)])"""
    name, stamp_ns, raw = "", 0, []
    for number, value in fields(line):
        if number == 2:
            name = value.decode()
        elif number == 3:
            stamp_ns = value
        elif number == 4:
            raw.append(value)
    out = []
    for event in raw:
        ident = offset_ps = duration_ps = 0
        for n, v in fields(event):
            if n == 1:
                ident = v
            elif n == 2:
                offset_ps = v
            elif n == 3:
                duration_ps = v
        out.append((ident, stamp_ns + offset_ps / 1000.0,
                    duration_ps / 1000.0))
    return name, out


def load(path: str) -> dict:
    """{"devices": {plane: [(name, start_ns, dur_ns, name stack)]},
        "host": [(name, start_ns, dur_ns)]}
    The first three of a device event are `trace.load`'s; the fourth is the
    operation's name stack as the compiler recorded it, "" where the
    operation's metadata has none."""
    with open(path, "rb") as f:
        space = f.read()
    devices, host = {}, []
    for number, value in fields(space):
        if number != 1:
            continue
        plane = _plane(value)
        if plane["name"].startswith(DEVICE_PREFIX):
            stacks = {}
            for line in plane["lines"]:
                line_name, events = _events(line)
                if line_name != OPS_LINE:
                    continue
                for ident, start, dur in events:
                    meta = plane["event_meta"].get(ident)
                    if meta is None:
                        devices.setdefault(plane["name"], []).append(
                            (f"event_{ident}", start, dur, ""))
                        continue
                    if ident not in stacks:
                        stacks[ident] = _name_stack(meta,
                                                    plane["stat_names"])
                    devices.setdefault(plane["name"], []).append(
                        (op_name(meta["name"]), start, dur, stacks[ident]))
        elif plane["name"].startswith(HOST_PREFIX):
            for line in plane["lines"]:
                for ident, start, dur in _events(line)[1]:
                    meta = plane["event_meta"].get(ident)
                    if dur > 0 and meta is not None:
                        host.append((meta["name"], start, dur))
    return {"devices": devices, "host": host}


def of(ctx) -> dict:
    """The traced run's file, walked once for all the readers of a run."""
    import trace as trace_mod

    if getattr(ctx, "_xplane", None) is None:
        ctx._xplane = load(trace_mod.find_xplane(ctx.trace_dir))
    return ctx._xplane
