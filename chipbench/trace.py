"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

The harness wraps the traced stretch in a host annotation named
:data:`WINDOW`; everything is measured inside it, on the trace's own
clock.  On each device plane the ``XLA Ops`` line holds one event per
executed HLO operation, nested where an operation (a ``while`` loop)
contains others:

- busy time is the union of those events' intervals, averaged over the
  devices;
- an operation's self time is its duration less the part its nested
  operations cover;
- an idle gap is a stretch of the window that no operation covers; one
  of 10 us or more is labelled by the shortest host event that covers at
  least half of it (a Python frame such as ``$pipeline.py:59
  stack_chunk_batches``, or a runtime event such as ``Transpose``), or by
  ``unattributed``; shorter gaps are summed under one label.

Host lines with more than :data:`MAX_HOST_EVENTS` events (per-chunk
workers of the runtime's transposes) are skipped for labels: their
parent events on other lines already cover the same time.
"""

from __future__ import annotations

import collections
import gzip
import pathlib
from typing import Dict, List, Tuple

import numpy as np

WINDOW = "chipbench.traced_window"
OPS_LINE = "XLA Ops"
MAX_HOST_EVENTS = 200_000
MIN_GAP_NS = 10_000  # shorter gaps are summed, not labelled
SHORT_GAPS = "gaps under 10 us"
Interval = Tuple[int, int]


def load(path):
    """``jax.profiler.ProfileData`` of an ``.xplane.pb`` (or ``.pb.gz``)."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    return ProfileData.from_file(str(path))


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _window(pd) -> Interval:
    host = pd.find_plane_with_name("/host:CPU")
    spans = [(int(e.start_ns), int(e.end_ns)) for line in (host.lines if host else [])
             for e in line.events if e.name == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no host event {WINDOW!r}")
    return max(spans, key=lambda s: s[1] - s[0])


def _device_ops(plane, w0: int, w1: int):
    """``(start, end, event_name)`` of the plane's operations, clipped to
    the window."""
    for line in plane.lines:
        if line.name != OPS_LINE:
            continue
        for e in line.events:
            s, t = max(int(e.start_ns), w0), min(int(e.end_ns), w1)
            if t > s:
                yield s, t, e.name


def _self_times(ops) -> Dict[str, List[float]]:
    """``op name -> [self seconds, count, custom-call target or '']``."""
    out: Dict[str, List] = {}
    stack: List[list] = []  # [end, name, child_cover]

    def close(frame):
        end, name, start, cover = frame
        rec = out.setdefault(op_name(name), [0.0, 0, _target(name)])
        rec[0] += (end - start - cover) * 1e-9
        rec[1] += 1

    for s, t, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(t, stack[-1][0]) - s
        stack.append([t, name, s, 0])
    while stack:
        close(stack.pop())
    return out


def _target(event_name: str) -> str:
    key = 'custom_call_target="'
    i = event_name.find(key)
    return event_name[i + len(key):event_name.find('"', i + len(key))] if i >= 0 else ""


def _host_events(pd, w0: int, w1: int):
    """Host events that overlap the window, as ``(starts, ends, names)``."""
    host = pd.find_plane_with_name("/host:CPU")
    starts, ends, names = [], [], []
    for line in host.lines:
        events = list(line.events)
        if len(events) > MAX_HOST_EVENTS:
            continue
        for e in events:
            s, t = int(e.start_ns), int(e.end_ns)
            if t > w0 and s < w1 and e.name != WINDOW:
                starts.append(s)
                ends.append(t)
                names.append(e.name)
    return np.asarray(starts, np.int64), np.asarray(ends, np.int64), names


def _label(gap: Interval, host) -> str:
    starts, ends, names = host
    g0, g1 = gap
    covers = (np.minimum(ends, g1) - np.maximum(starts, g0)) >= 0.5 * (g1 - g0)
    if not covers.any():
        return "unattributed"
    idx = np.flatnonzero(covers)
    return names[idx[np.argmin((ends - starts)[idx])]]


def reduce_trace(pd, top: int = 10) -> dict:
    """Busy and window seconds, per-operation self time, and the idle
    seconds by the label of their gaps."""
    w0, w1 = _window(pd)
    planes = [p for p in pd.planes if p.name.startswith("/device:")
              and any(line.name == OPS_LINE for line in p.lines)]
    if not planes:
        raise ValueError("the trace holds no device operations")
    busy, ops, gaps = [], collections.defaultdict(lambda: [0.0, 0, ""]), []
    for plane in planes:
        events = list(_device_ops(plane, w0, w1))
        cover = union([(s, t) for s, t, _ in events])
        busy.append(sum(t - s for s, t in cover) * 1e-9)
        for name, (sec, cnt, target) in _self_times(events).items():
            rec = ops[name]
            rec[0] += sec / len(planes)
            rec[1] += cnt
            rec[2] = target
        edges = [w0] + [x for iv in cover for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    host = _host_events(pd, w0, w1)
    idle_by_label = collections.Counter()
    for g in gaps:
        name = _label(g, host) if g[1] - g[0] >= MIN_GAP_NS else SHORT_GAPS
        idle_by_label[name] += (g[1] - g[0]) * 1e-9 / len(planes)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "devices": len(planes),
        "ops": {k: {"self_s": v[0], "count": v[1], "target": v[2]}
                for k, v in ops.items()},
        "top_ops": sorted(((k, v[0]) for k, v in ops.items()),
                          key=lambda x: -x[1])[:top],
        "idle_by_label": idle_by_label.most_common(top),
    }
