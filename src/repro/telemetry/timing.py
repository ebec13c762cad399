"""Timing, throughput and profiling instrumentation.

Three host-side probes, all safe to leave attached:

* :class:`ThroughputMeter` — rounds/sec per block and cumulatively, from
  the seconds of each block's ``fl.block`` host span
  (:mod:`repro.telemetry.spans`), which ends with a
  ``jax.block_until_ready`` fence (async dispatch otherwise makes
  ``perf_counter`` deltas measure the *enqueue*, not the execution).
  The ROADMAP's async direction measures convergence against
  wall-clock, which starts here.
* :class:`CompileTracker` — snapshots the jit cache sizes of registered
  compiled functions and reports growth, catching recompile regressions
  (a shape-unstable carry silently retracing every chunk turns a 20x
  scan speedup into a 0.1x slowdown; the telemetry stream now says so).
* :class:`ProfileWindow` — an opt-in ``jax.profiler`` trace capture
  over a round window (``--profile-dir`` / ``--profile-rounds`` in the
  launchers): starts the trace when the window opens, stops it when the
  window closes, never triggers otherwise.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax

from repro.telemetry.spans import BLOCK, FENCE, Span, Spans

__all__ = ["ThroughputMeter", "CompileTracker", "ProfileWindow",
           "profiler_options"]


class ThroughputMeter:
    """Wall-clock rounds/sec with device fencing.

    The trainer times each execution block (one round or one K-round
    chunk) as an ``fl.block`` span of its :class:`Spans` and hands the
    span's seconds to :meth:`record`.  ``start``/``stop`` do the same
    for a caller with no span of its own::

        meter.start()
        ... dispatch ... (+ host prefetch work)
        dt = meter.stop(rounds=k, fence=metrics)

    ``fence`` is block_until_ready'd (an ``fl.fence`` span) before the
    block closes, so the interval covers the device execution, not just
    its enqueue.  Fencing on the metrics the caller is about to read
    anyway adds no extra sync.
    """

    def __init__(self, spans: Optional[Spans] = None):
        self.spans = spans if spans is not None else Spans()
        self._block: Optional[Span] = None
        self.chunks: List[Dict[str, float]] = []
        self.total_rounds = 0
        self.total_seconds = 0.0

    def start(self) -> None:
        self._block = self.spans.open(BLOCK)

    def stop(self, rounds: int, fence: Any = None) -> float:
        """Fence, close the block, record; returns the elapsed seconds."""
        if self._block is None:
            raise RuntimeError("ThroughputMeter.stop() without start()")
        if fence is not None:
            with self.spans.span(FENCE):
                jax.block_until_ready(fence)
        block, self._block = self._block, None
        return self.record(rounds, self.spans.close(block).seconds)

    def record(self, rounds: int, seconds: float) -> float:
        """Record one block of ``rounds`` rounds that took ``seconds``."""
        self.chunks.append({"rounds": rounds, "seconds": seconds,
                            "rounds_per_sec": rounds / seconds if seconds > 0 else 0.0})
        self.total_rounds += rounds
        self.total_seconds += seconds
        return seconds

    def rounds_per_sec(self) -> float:
        """Cumulative throughput over every recorded block."""
        return (self.total_rounds / self.total_seconds
                if self.total_seconds > 0 else 0.0)


class CompileTracker:
    """Detect recompiles of registered jitted functions.

    ``register(name, fn)`` snapshots the jitted function's current cache
    size; ``check()`` returns ``{name: growth}`` for every function
    whose cache grew since the last call (one compile per distinct input
    shape is expected; growth *during steady-state training* is a
    regression).
    """

    def __init__(self):
        self._fns: Dict[str, Any] = {}
        self._seen: Dict[str, int] = {}

    def register(self, name: str, fn) -> None:
        self._fns[name] = fn
        self._seen[name] = fn._cache_size()

    def compile_counts(self) -> Dict[str, int]:
        """Current cache size per registered function."""
        return {n: f._cache_size() for n, f in self._fns.items()}

    def check(self) -> Dict[str, int]:
        """Cache growth per function since the previous ``check()``."""
        grew: Dict[str, int] = {}
        for name, fn in self._fns.items():
            size = fn._cache_size()
            if size > self._seen[name]:
                grew[name] = size - self._seen[name]
            self._seen[name] = size
        return grew


def profiler_options() -> "jax.profiler.ProfileOptions":
    """Host events of the first level only: at the default level the
    runtime's per-chunk transpose events cost seconds per block of the
    paper's job."""
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    return options


class ProfileWindow:
    """An opt-in ``jax.profiler.trace`` capture over rounds
    ``[start, start + rounds)``.

    The trainer calls ``maybe_start(r)`` before executing a block
    beginning at round ``r`` and ``maybe_stop(r_next)`` after fencing
    the block that ends before round ``r_next``; the window opens/closes
    on the enclosing block boundaries (a chunked run profiles whole
    chunks).  ``close()`` force-stops a window left open at run end.
    The trace starts with :func:`profiler_options`.
    """

    def __init__(self, profile_dir: str, start: int = 0, rounds: int = 1):
        if rounds <= 0:
            raise ValueError("profile window needs rounds >= 1")
        self.profile_dir = str(profile_dir)
        self.start = int(start)
        self.rounds = int(rounds)
        self.active = False
        self.done = False

    def maybe_start(self, r: int) -> bool:
        """Open the trace when block starting at round ``r`` enters the
        window; returns True when (already) capturing."""
        if self.active:
            return True
        if not self.done and r >= self.start:
            jax.profiler.start_trace(self.profile_dir,
                                     profiler_options=profiler_options())
            self.active = True
        return self.active

    def maybe_stop(self, r_next: int) -> bool:
        """Close the trace once execution has passed the window end
        (``r_next`` = first round not yet executed)."""
        if self.active and r_next >= self.start + self.rounds:
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
        return self.done

    def close(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
