"""A minimal reader of the XSpace protobuf the profiler writes.

``jax.profiler.ProfileData`` gives each event's name, start and
duration, but not the stats of its event metadata, where a TPU op's
named-scope path (``tf_op``) and HLO category (``hlo_category``) live.
This module reads just those, from the wire format, for the planes
asked for: ``{plane name: {event name: {stat name: value}}}``.

Fields read (``tsl/profiler/protobuf/xplane.proto``): XSpace.planes = 1;
XPlane.name = 2, event_metadata = 4 (map<int64, XEventMetadata>),
stat_metadata = 5 (map<int64, XStatMetadata>); XEventMetadata.name = 2,
stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1, and its value
in fields 2-7.
"""

from __future__ import annotations

import struct


def _varint(b: bytes, i: int):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes, i: int, end: int):
    """(field number, wire type, value or (start, end)) of one message."""
    while i < end:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
            yield f, wt, v
        elif wt == 2:
            n, i = _varint(b, i)
            yield f, wt, (i, i + n)
            i += n
        elif wt == 1:
            yield f, wt, b[i:i + 8]
            i += 8
        elif wt == 5:
            yield f, wt, b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", errors="replace")


def _map_value(b: bytes, span):
    """(key, value span) of one map entry."""
    key, value = None, None
    for f, _, v in _fields(b, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat(b: bytes, span, names: dict):
    mid, value = None, None
    for f, wt, v in _fields(b, *span):
        if f == 1:
            mid = v
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4, 7):
            value = v
        elif f in (5, 6):
            value = _text(b, v)
    return names.get(mid, str(mid)), value


def event_metadata(xspace: bytes, planes) -> dict:
    """Stats of every event's metadata on the named planes of a
    serialized XSpace."""
    b = xspace
    out = {}
    for f, _, span in _fields(b, 0, len(b)):
        if f != 1:
            continue
        name, metas, stat_names = None, [], {}
        for pf, _, pv in _fields(b, *span):
            if pf == 2:
                name = _text(b, pv)
            elif pf == 4:
                metas.append(pv)
            elif pf == 5:
                k, v = _map_value(b, pv)
                for sf, _, sv in _fields(b, *v):
                    if sf == 2:
                        stat_names[k] = _text(b, sv)
        if name not in planes:
            continue
        table = {}
        for pv in metas:
            _, v = _map_value(b, pv)
            ev_name, stats = None, {}
            for ef, _, ev in _fields(b, *v):
                if ef == 2:
                    ev_name = _text(b, ev)
                elif ef == 5:
                    k, val = _stat(b, ev, stat_names)
                    stats[k] = val
            table[ev_name] = stats
        out[name] = table
    return out
