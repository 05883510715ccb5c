"""From a profiler trace (``.xplane.pb``) to per-chip and per-layer time.

Read with ``jax.profiler.ProfileData``:

- Device planes are the ``/device:TPU:<n>`` planes.  Their op line
  (``XLA Ops``) holds one event per executed operation.  An
  operation's named-scope path (``repro/schedule``,
  ``repro/local_train``, ``repro/aggregate``, entered by the program's
  ``telemetry.phase_scope``) is the ``tf_op`` stat of its event
  metadata, read by ``feelbench/xplane.py``.
- A ``while``, ``conditional`` or ``call`` op's event spans the events
  of its body, so per-layer and per-op time sum the other ops only.
- Busy time of a chip is the union of its op intervals; idle is the
  rest of the traced window.
- The window runs from the first to the last of the harness's own host
  spans (``chunk.enqueue``, ``chunk.fold``, ``chunk.wait``), written by
  ``jax.profiler.TraceAnnotation``.
- Each gap in a chip's busy time is attributed to the host span that
  covers most of it, or to ``host`` where none does.

``reduce(profile, meta, chips)`` returns a plain dict that the per-layer
readers in ``feelbench/metrics`` take their numbers from.
"""

from __future__ import annotations

import collections
import glob
import os
import re

_PHASE = re.compile(r"repro/([A-Za-z0-9_]+)")
HOST_SPANS = ("chunk.enqueue", "chunk.fold", "chunk.wait")
OP_LINE = "XLA Ops"
# Operations that hold other operations: their events span their body's
# events, so only the leaves are summed.
CONTAINERS = ("while", "conditional", "call")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def phase_of(scope: str) -> str | None:
    """The innermost ``repro/<phase>`` scope in an op's named-scope
    path, if any."""
    found = _PHASE.findall(scope)
    return found[-1] if found else None


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce(profile, meta: dict, chips: int) -> dict:
    """Per-chip busy time, per-phase device time, idle gaps, top ops.

    ``meta``: ``{plane: {event name: stats}}`` from
    :func:`feelbench.xplane.event_metadata`.
    """
    spans = []
    device_ops = {}
    for plane in profile.planes:
        m = _DEVICE.match(plane.name)
        if m:
            if int(m.group(1)) >= chips:
                continue
            table = meta.get(plane.name, {})
            ops = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    st = table.get(ev.name, {})
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, st.get("hlo_category", ""),
                                str(st.get("tf_op", ""))))
            device_ops[int(m.group(1))] = ops
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    if not spans:
        raise ValueError("the trace holds none of the harness's host spans")
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    window_ns = hi - lo
    phase_ns = collections.Counter()
    op_ns = collections.Counter()
    busy, gaps = {}, collections.Counter()
    for chip, ops in device_ops.items():
        iv = _union([(max(s, lo), min(e, hi)) for s, e, *_ in ops
                     if e > lo and s < hi])
        busy[chip] = sum(e - s for s, e in iv)
        for s, e, name, cat, scope in ops:
            d = min(e, hi) - max(s, lo)
            if d <= 0 or cat in CONTAINERS:
                continue
            op_ns[f"{short_name(name)} ({cat})"] += d
            p = phase_of(scope)
            if p:
                phase_ns[p] += d
        edges = [lo] + [x for pair in iv for x in pair] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            cover = collections.Counter()
            for s, e, n in spans:
                o = min(e, g1) - max(s, g0)
                if o > 0:
                    cover[n] += o
            owner = cover.most_common(1)[0][0] if cover else "host"
            gaps[owner] += g1 - g0
    n = max(len(busy), 1)
    return {
        "chips_seen": len(device_ops),
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy.values()) / n * 1e-9,
        "busy_s_per_chip": {str(c): b * 1e-9 for c, b in busy.items()},
        "idle_share_worst": (max(1.0 - b / window_ns for b in busy.values())
                             if busy and window_ns > 0 else 1.0),
        "phase_s": {p: d * 1e-9 for p, d in phase_ns.items()},
        "leaf_s": sum(op_ns.values()) * 1e-9,
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in op_ns.most_common(10)],
            "idle_gaps": [[k, v * 1e-9 / n] for k, v in gaps.most_common(10)],
        },
    }


def reduce_xspace(xspace: bytes, chips: int) -> dict:
    """:func:`reduce` of a serialized XSpace."""
    from jax.profiler import ProfileData
    from feelbench import xplane
    planes = {f"/device:TPU:{i}" for i in range(chips)}
    return reduce(ProfileData.from_serialized_xspace(xspace),
                  xplane.event_metadata(xspace, planes), chips)


def reduce_dir(log_dir: str, chips: int) -> dict:
    with open(find_xplane(log_dir), "rb") as f:
        return reduce_xspace(f.read(), chips)

