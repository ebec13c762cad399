"""Reduction of a profiler trace by the names the program puts on its
work (``repro.telemetry.spans``): device self time by scope, and idle
time by host span.

- Device scopes.  A trace names each executed operation by its HLO
  instruction alone; ``FLTrainer.op_scopes(k)`` maps the instructions of
  the program ``run(chunk=k)`` executes to the innermost ``fl.*`` scope
  of their metadata.  The operations' self seconds
  (:func:`chipbench.trace.reduce_trace`'s ``ops``) summed by scope, with
  any instruction the map lacks under ``unscoped``, partition the busy
  time.
- Host spans.  The trainer's ``fl.*`` spans are host annotations on the
  trace's clock.  Each idle gap of a device (a stretch of the window
  that no operation covers) is cut at the spans' edges, and each piece
  goes to the innermost span over it (the shortest: spans nest), or to
  ``unspanned``.  The pieces add up to the idle time.

A trace from a program without these names reduces to ``unscoped`` and
``unspanned`` alone.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from chipbench import trace

UNSCOPED = "unscoped"
UNSPANNED = "unspanned"
SPAN_PREFIX = "fl."
Interval = Tuple[int, int]


def scope_seconds(ops: Mapping[str, dict], op_scopes: Mapping[str, str]) -> Dict[str, float]:
    """Self seconds by scope of reduced operations (``{name: {"self_s"}}``)."""
    out: Dict[str, float] = collections.defaultdict(float)
    for name, op in ops.items():
        out[op_scopes.get(name, UNSCOPED)] += op["self_s"]
    return dict(out)


def pieces(spans: List[Tuple[int, int, str]], w0: int, w1: int) -> List[Tuple[int, int, str]]:
    """The window ``[w0, w1)`` cut at every span edge, each piece named
    by the shortest span that covers it, or ``unspanned``."""
    edges = sorted({w0, w1} | {min(max(x, w0), w1) for s, e, _ in spans for x in (s, e)})
    starts = np.asarray([s for s, _, _ in spans], np.int64)
    ends = np.asarray([e for _, e, _ in spans], np.int64)
    out = []
    for a, b in zip(edges[:-1], edges[1:]):
        over = np.flatnonzero((starts <= a) & (ends >= b))
        name = (spans[over[np.argmin((ends - starts)[over])]][2] if over.size
                else UNSPANNED)
        out.append((a, b, name))
    return out


def split_gaps(gaps: List[Interval], named: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """Nanoseconds of sorted disjoint ``gaps`` that fall in each named
    piece (:func:`pieces`, sorted and disjoint)."""
    out: Dict[str, int] = collections.defaultdict(int)
    i = 0
    for g0, g1 in gaps:
        while i < len(named) and named[i][1] <= g0:
            i += 1
        j = i
        while j < len(named) and named[j][0] < g1:
            a, b, name = named[j]
            out[name] += min(b, g1) - max(a, g0)
            j += 1
    return dict(out)


def _host_spans(pd, w0: int, w1: int) -> List[Tuple[int, int, str]]:
    host = pd.find_plane_with_name("/host:CPU")
    return [(int(e.start_ns), int(e.end_ns), e.name)
            for line in (host.lines if host else []) for e in line.events
            if e.name.startswith(SPAN_PREFIX)
            and int(e.end_ns) > w0 and int(e.start_ns) < w1]


def _device_gaps(plane, w0: int, w1: int) -> List[Interval]:
    cover = trace.union([(s, t) for s, t, _ in trace._device_ops(plane, w0, w1)])
    edges = [w0] + [x for iv in cover for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def idle_by_span(pd) -> Dict[str, float]:
    """Idle seconds of the traced window by innermost host span, averaged
    over the devices."""
    w0, w1 = trace._window(pd)
    planes = [p for p in pd.planes if p.name.startswith("/device:")
              and any(line.name == trace.OPS_LINE for line in p.lines)]
    if not planes:
        raise ValueError("the trace holds no device operations")
    named = pieces(_host_spans(pd, w0, w1), w0, w1)
    out: Dict[str, float] = collections.defaultdict(float)
    for plane in planes:
        for name, ns in split_gaps(_device_gaps(plane, w0, w1), named).items():
            out[name] += ns * 1e-9 / len(planes)
    return dict(out)


def reduce(pd, op_scopes: Mapping[str, str]) -> dict:
    """:func:`chipbench.trace.reduce_trace` with two more keys: ``scopes``
    (self seconds by device scope) and ``idle_by_span`` (idle seconds by
    host span)."""
    out = trace.reduce_trace(pd)
    out["scopes"] = scope_seconds(out["ops"], op_scopes)
    out["idle_by_span"] = idle_by_span(pd)
    return out


def scope_ms(record: dict, names: Sequence[str]) -> Optional[float]:
    """Device milliseconds per traced round under the scopes ``names``
    together, from a run record's reduced trace; ``None`` untraced, or
    where the trace holds none of them."""
    trace = record.get("trace")
    if not trace or not record.get("traced_rounds"):
        return None
    found = [trace["scopes"][s] for s in names if s in trace["scopes"]]
    if not found:
        return None
    return 1e3 * sum(found) / record["traced_rounds"]
