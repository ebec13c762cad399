"""Observability subsystem (DESIGN.md §11): three dependency-free tiers.

1. **Device tier** (:mod:`repro.telemetry.device`) — per-client /
   per-link vector metrics computed *inside* the compiled round and
   stacked ``(K, n)`` per scan chunk: participation vectors, per-client
   bits-on-air, outage-streak ages (a traced ``(n,)`` carry), and the
   realized unbiasedness drift.
2. **Host tier** (:mod:`repro.telemetry.logger`,
   :mod:`repro.telemetry.manifest`) — one deduped append path for every
   metric stream, pluggable sinks (JSONL events, CSV summary,
   in-memory), structured health events (``health.nan``,
   ``health.recompile``), and a :class:`RunManifest` written at run
   start (config digest, strategy/channel/codec, mesh, backend, git
   SHA).
3. **Timing tier** (:mod:`repro.telemetry.timing`,
   :mod:`repro.telemetry.spans`) — the names of the program's own work
   (device scopes on the compiled round, host spans on the trainer's
   loop, each listed once in ``spans``), fenced wall-clock throughput,
   jit recompile tracking, and opt-in ``jax.profiler.trace`` capture
   windows.

Everything is stdlib + numpy + jax; nothing here imports the FL stack
(the trainer imports *us*), and with no sinks attached the whole layer
reduces to one numpy cast per chunk.
"""

from repro.telemetry.device import (
    VECTOR_METRICS,
    init_streak,
    instrument_round_fn,
    update_streak,
)
from repro.telemetry.logger import (
    SCALAR_STREAMS,
    CsvSummarySink,
    JsonlSink,
    MemorySink,
    MetricsLogger,
    MetricsSink,
)
from repro.telemetry.manifest import RunManifest, config_digest, git_sha
from repro.telemetry.spans import Spans, op_scopes
from repro.telemetry.timing import (
    CompileTracker,
    ProfileWindow,
    ThroughputMeter,
    profiler_options,
)

__all__ = [
    "VECTOR_METRICS",
    "SCALAR_STREAMS",
    "init_streak",
    "update_streak",
    "instrument_round_fn",
    "MetricsSink",
    "JsonlSink",
    "CsvSummarySink",
    "MemorySink",
    "MetricsLogger",
    "RunManifest",
    "config_digest",
    "git_sha",
    "CompileTracker",
    "ProfileWindow",
    "ThroughputMeter",
    "profiler_options",
    "Spans",
    "op_scopes",
]
