"""Names for the program's own work: device scopes and host spans.

Every name the program puts on its work is listed here, once.

* **Device scopes** wrap the parts of the compiled round in
  ``jax.named_scope``.  A scope is metadata only: it lands in the
  ``op_name`` of each HLO instruction the part lowers to and never
  changes the compiled arithmetic.  A profiler trace names an executed
  operation by its HLO instruction alone, so a scope reaches a trace by
  joining the two: :func:`op_scopes` maps each instruction of a compiled
  program's text to the innermost scope in its ``op_name``
  (``FLTrainer.op_scopes`` applies it to the trainer's own program).
* **Host spans** time the steps of the trainer's host loop.  Each
  :meth:`Spans.span` opens a ``jax.profiler.TraceAnnotation`` of the
  same name, so it lands on the profiler's clock beside the device
  operations, and adds its ``perf_counter`` seconds and its count to the
  recorder's in-memory totals.  Spans are always on; with no profiler
  running a span costs a few microseconds.
"""

from __future__ import annotations

import re
import time
from typing import Dict, List, Optional

import jax

__all__ = [
    "SCOPES", "SPANS", "COUNTERS", "UNSCOPED",
    "Span", "Spans", "scope_of", "op_scopes",
]

# -- device scopes (``jax.named_scope``) -------------------------------------
LOCAL_SGD = "fl.local_sgd"            # local SGD steps, or weighted_grad/_flat's grad + client step
AGGREGATE = "fl.aggregate"            # the strategy's weights and aggregation
FLATTEN = "fl.flatten"                # ravel / unravel of the update stack (inside fl.aggregate)
SERVER_STEP = "fl.server_step"        # server optimizer and parameter update
ROUND_METRICS = "fl.round_metrics"    # the round's metrics dict
TELEMETRY = "fl.telemetry"            # the instrumented round's vector metrics
CHANNEL_SAMPLE = "fl.channel_sample"  # the in-scan connectivity sampler
SCOPES = (LOCAL_SGD, AGGREGATE, FLATTEN, SERVER_STEP, ROUND_METRICS,
          TELEMETRY, CHANNEL_SAMPLE)
UNSCOPED = "unscoped"                 # an instruction under no scope

# -- host spans (``Spans.span``) ----------------------------------------------
BLOCK = "fl.block"                    # one block (a round or a chunk); parent of the next six
CHANNEL_TRACE = "fl.channel_trace"    # the block's taus from the channel
STACK_BATCHES = "fl.stack_batches"    # gathering a block's batches on the host
H2D = "fl.h2d"                        # batches and taus onto the device
DISPATCH = "fl.dispatch"              # the call of the compiled program
FENCE = "fl.fence"                    # waiting for the block's results
LOG_ROUNDS = "fl.log_rounds"          # the block's metrics to the logger
CKPT = "fl.ckpt"                      # a checkpoint save or its final commit
EVAL = "fl.eval"                      # an evaluation
REOPT = "fl.reopt"                    # feeding the adaptive weight schedule
SPANS = (BLOCK, CHANNEL_TRACE, STACK_BATCHES, H2D, DISPATCH, FENCE,
         LOG_ROUNDS, CKPT, EVAL, REOPT)

# -- counters (``Spans.count``) -----------------------------------------------
H2D_BYTES = "h2d_bytes"               # bytes put on the device at fl.h2d
PREFETCHED_ROUNDS = "prefetched_rounds"  # rounds stacked while the block before ran
COUNTERS = (H2D_BYTES, PREFETCHED_ROUNDS)


class Span:
    """One span of a :class:`Spans` recorder, entered with ``with``: its
    seconds, once closed, and the seconds of its direct children by
    name."""

    __slots__ = ("name", "seconds", "children", "_spans", "_note", "_t0")

    def __init__(self, spans: "Spans", name: str, args: Dict[str, object]):
        self.name = name
        self.seconds = 0.0
        self.children: Dict[str, float] = {}
        self._spans = spans
        self._note = jax.profiler.TraceAnnotation(name, **args)
        self._t0 = 0.0

    def __enter__(self) -> "Span":
        self._note.__enter__()
        self._spans._open.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._spans.close(self)


class Spans:
    """Host span recorder: totals of seconds and counts by span name,
    counters by name, and the nesting of the spans now open.

    ``with spans.span(name, **args) as s:`` times a step; ``args``
    (such as a block's first ``round``) ride the trace annotation.  A
    closed span's seconds are added to its parent's ``children``.
    ``open``/``close`` do the same where a ``with`` block does not fit;
    spans close innermost first.
    """

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.counters: Dict[str, float] = {}
        self._open: List[Span] = []

    def span(self, name: str, **args) -> Span:
        return Span(self, name, args)

    def open(self, name: str, **args) -> Span:
        return Span(self, name, args).__enter__()

    def close(self, span: Span) -> Span:
        seconds = time.perf_counter() - span._t0
        if not self._open or self._open[-1] is not span:
            raise RuntimeError(f"span {span.name!r} is not the innermost open span")
        self._open.pop()
        span._note.__exit__(None, None, None)
        span.seconds = seconds
        name = span.name
        self.seconds[name] = self.seconds.get(name, 0.0) + seconds
        self.counts[name] = self.counts.get(name, 0) + 1
        if self._open:
            parent = self._open[-1].children
            parent[name] = parent.get(name, 0.0) + seconds
        return span

    def count(self, counter: str, value: float) -> None:
        self.counters[counter] = self.counters.get(counter, 0) + value

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Copies of the totals, e.g. to difference around a window."""
        return {"seconds": dict(self.seconds), "counts": dict(self.counts),
                "counters": dict(self.counters)}


_SCOPE = re.compile(r"fl\.[a-z_]+")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_LOOP_PARTS = re.compile(r"\b(?:body|condition)=%?([\w.\-]+)")


def scope_of(op_name: Optional[str]) -> str:
    """The innermost scope of an ``op_name`` path, whatever transforms
    wrap it (``transpose(jvp(fl.local_sgd))`` is ``fl.local_sgd``), or
    :data:`UNSCOPED`."""
    found = [s for s in _SCOPE.findall(op_name or "") if s in SCOPES]
    return found[-1] if found else UNSCOPED


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` of every instruction in a compiled
    program's text (``jax.stages.Compiled.as_text()``).

    An instruction takes the innermost scope of its ``op_name``.  One the
    compiler added without metadata (a layout copy, an async copy's
    start and done) inside a loop's body or condition takes the scope of
    the loop, since it runs as part of it; any other is
    :data:`UNSCOPED`."""
    own: Dict[str, str] = {}
    home: Dict[str, str] = {}       # instruction -> its computation
    loop_of: Dict[str, str] = {}    # body/condition computation -> its while
    computation = ""
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c and line[:1] not in ("", " ", "}"):
                computation = c.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1) if op else None)
        home[name] = computation
        for part in _LOOP_PARTS.findall(line):
            loop_of[part] = name

    def resolve(name: str, depth: int = 0) -> str:
        scope = own[name]
        loop = loop_of.get(home[name])
        if scope != UNSCOPED or loop is None or depth > len(loop_of):
            return scope
        return resolve(loop, depth + 1)

    return {name: resolve(name) for name in own}
