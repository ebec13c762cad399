"""Host-side metrics pipeline: one append path, pluggable sinks.

The :class:`MetricsLogger` is the single choke point every metric
stream passes through.  Both trainer execution paths — the per-round
host loop and the chunked scan engine — call the same
:meth:`MetricsLogger.log_rounds` with the same float-cast code, so the
two streams *cannot* drift (pre-telemetry they built their casts
independently); the legacy :class:`~repro.fl.trainer.TrainLog` remains
attached as a bitwise-compatible facade (same fields, same values, same
python types).

Events flow to pluggable sinks:

* :class:`JsonlSink` — append-only ``events.jsonl``, one compact JSON
  object per line, buffered (one write per chunk, not per round);
* :class:`CsvSummarySink` — per-round scalar table ``rounds.csv``;
* :class:`MemorySink` — in-process list (tests, report tooling).

Event kinds: ``round`` (per-round scalars), ``eval``, ``reopt``,
``timing`` (per-chunk wall clock + rounds/sec, and the seconds of the
chunk's host spans), ``health.nan`` (a
non-finite loss — emitted as a structured event instead of being
silently appended), ``health.recompile`` (jit cache growth), and
``summary.clients`` (end-of-run per-client aggregates of the
device-resident vector metrics).

Vector metrics (``(K, n)`` per chunk off the device) are accumulated
host-side as numpy — O(n) per round, no JSON cost — and exposed as
``logger.vector(name) -> (R, n)``; ``save_vectors`` dumps them as one
``.npz``.  Monotonic indexing: every event carries ``seq`` (emission
order) and round-scoped events carry their round index.
"""

from __future__ import annotations

import json
import pathlib
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro.telemetry.device import VECTOR_METRICS

__all__ = ["MetricsSink", "JsonlSink", "CsvSummarySink", "MemorySink",
           "MetricsLogger", "SCALAR_STREAMS"]

#: scalar metric streams a round event may carry, mapped to their
#: TrainLog facade field (None = event-only, no facade list)
SCALAR_STREAMS = {
    "loss": "loss",
    "participation": "participation",
    "uplink_bits": "uplink_bits",
    "weight_sum": "weight_sums",
    "weight_drift": None,
    "delta_norm": None,
    # async execution mode (DESIGN.md §13): realized staleness profile.
    # Event-only — the TrainLog facade stays bitwise-identical for sync
    # runs and async runs read these off the round events.
    "mean_age": None,
    "max_age": None,
    "stale_frac": None,
}


class MetricsSink:
    """Sink protocol: receives event dicts, flushes on demand."""

    def emit(self, event: Dict[str, Any]) -> None:  # pragma: no cover
        raise NotImplementedError

    def flush(self) -> None:
        pass

    def close(self) -> None:
        self.flush()


class MemorySink(MetricsSink):
    """Keep events in-process (tests / report tooling)."""

    def __init__(self):
        self.events: List[Dict[str, Any]] = []

    def emit(self, event: Dict[str, Any]) -> None:
        self.events.append(event)

    def of_kind(self, kind: str) -> List[Dict[str, Any]]:
        return [e for e in self.events if e["event"] == kind]


class JsonlSink(MetricsSink):
    """Append-only JSONL event log, write-buffered.

    Lines are buffered host-side and flushed every ``buffer`` events
    (and at ``flush``/``close``), so steady-state training costs one
    ``write`` per chunk rather than one syscall per round.
    """

    def __init__(self, path, buffer: int = 256, resume: bool = False):
        self.path = pathlib.Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._buf: List[str] = []
        self._buffer = max(1, int(buffer))
        if not (resume and self.path.exists()):
            self.path.write_text("")  # truncate: one run per file
        # resume reopens in append mode: the stream continues after the
        # prior run's events.  Events emitted after the restored
        # checkpoint but before the kill stay in the file — the JSONL
        # stream is at-least-once across a resume; consumers dedupe on
        # (event, round) or take the last seq per key (DESIGN.md §12).

    def emit(self, event: Dict[str, Any]) -> None:
        self._buf.append(json.dumps(event, separators=(",", ":")))
        if len(self._buf) >= self._buffer:
            self.flush()

    def flush(self) -> None:
        if self._buf:
            with self.path.open("a") as f:
                f.write("\n".join(self._buf) + "\n")
            self._buf.clear()

    @staticmethod
    def load(path) -> List[Dict[str, Any]]:
        """Read an events.jsonl back into a list of dicts."""
        out = []
        for line in pathlib.Path(path).read_text().splitlines():
            line = line.strip()
            if line:
                out.append(json.loads(line))
        return out


class CsvSummarySink(MetricsSink):
    """Per-round scalar summary table (``rounds.csv``)."""

    _COLS = ("round", "loss", "participation", "uplink_bits", "weight_sum",
             "weight_drift")

    def __init__(self, path, resume: bool = False):
        self.path = pathlib.Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._rows: List[str] = [",".join(self._COLS)]
        self._written = False
        if resume and self.path.exists():
            rows = self.path.read_text().splitlines()
            if rows and rows[0] == self._rows[0]:
                self._rows = rows

    def emit(self, event: Dict[str, Any]) -> None:
        if event.get("event") != "round":
            return
        self._rows.append(",".join(
            repr(event[c]) if isinstance(event.get(c), float)
            else str(event.get(c, "")) for c in self._COLS))

    def trim_rounds_after(self, r: int) -> None:
        """Drop rows past round ``r`` — rounds the prior run logged
        after the checkpoint being resumed (they will be re-trained and
        re-logged), keeping the table exactly-once."""
        self._rows = [self._rows[0]] + [
            row for row in self._rows[1:]
            if row and int(row.split(",", 1)[0]) <= r
        ]

    def flush(self) -> None:
        self.path.write_text("\n".join(self._rows) + "\n")


class MetricsLogger:
    """The one metric append path (see module doc).

    ``log`` is the legacy :class:`~repro.fl.trainer.TrainLog` facade the
    trainer exposes; the logger owns it and keeps it bitwise-compatible
    with the pre-telemetry trainer.  ``sinks`` receive the event stream;
    an empty sink list costs one numpy cast per chunk and nothing else.
    """

    def __init__(self, sinks: Sequence[MetricsSink] = (), log=None):
        if log is None:
            from repro.fl.trainer import TrainLog
            log = TrainLog()
        self.log = log
        self.sinks = list(sinks)
        self._seq = 0  # monotonic event index across every kind
        self._vectors: Dict[str, List[np.ndarray]] = {}

    # -- event plumbing --------------------------------------------------
    def emit(self, kind: str, **payload: Any) -> None:
        if not self.sinks:
            self._seq += 1
            return
        event = {"event": kind, "seq": self._seq, **payload}
        self._seq += 1
        for s in self.sinks:
            s.emit(event)

    def flush(self) -> None:
        for s in self.sinks:
            s.flush()

    def close(self) -> None:
        self._emit_client_summary()
        for s in self.sinks:
            s.close()

    # -- checkpoint/resume (DESIGN.md §12) -------------------------------
    def checkpoint_state(self) -> Dict[str, Any]:
        """Everything needed to continue the metric streams seamlessly:
        the monotonic ``seq`` cursor, the full TrainLog facade, and the
        accumulated vector-metric histories."""
        import dataclasses as _dc

        return {
            "seq": int(self._seq),
            "log": _dc.asdict(self.log),
            "vectors": {k: np.concatenate(v, axis=0)
                        for k, v in self._vectors.items() if v},
        }

    def restore_state(self, state: Dict[str, Any]) -> None:
        """Reinstate a checkpointed stream position.

        The TrainLog lists are mutated *in place* so every alias
        (``trainer.log is metrics.log``) observes the restored history;
        sinks that can rewind (``trim_rounds_after``) drop rows the
        prior run logged past the checkpoint."""
        self._seq = int(state["seq"])
        for name, vals in state["log"].items():
            getattr(self.log, name)[:] = list(vals)
        self._vectors = {k: [np.asarray(v)]
                         for k, v in state.get("vectors", {}).items()}
        last = self.log.rounds[-1] if self.log.rounds else -1
        for s in self.sinks:
            trim = getattr(s, "trim_rounds_after", None)
            if trim is not None:
                trim(last)

    # -- the deduped round append path ----------------------------------
    def log_rounds(self, r0: int, metrics: Dict[str, Any], k: int = 1) -> None:
        """Append ``k`` rounds' metrics starting at round ``r0``.

        ``metrics`` holds device (or numpy) values: scalar streams as
        0-d (``k == 1``) or stacked ``(k,)`` arrays, vector streams as
        ``(n,)`` or ``(k, n)``.  This is the *only* float-cast path —
        the per-round loop and the chunked engine both land here, so
        their TrainLog streams are bitwise identical by construction
        (``np.float64`` widening of the device float32, exactly the
        cast both pre-telemetry paths performed).
        """
        cast = {}
        for name in SCALAR_STREAMS:
            if name in metrics:
                cast[name] = np.asarray(metrics[name],
                                        np.float64).reshape(k).tolist()
        rounds = list(range(r0, r0 + k))
        self.log.rounds.extend(rounds)
        for name, field in SCALAR_STREAMS.items():
            if field is not None and name in cast:
                getattr(self.log, field).extend(cast[name])
        for name in VECTOR_METRICS:
            if name in metrics:
                v = np.asarray(metrics[name])
                self._vectors.setdefault(name, []).append(v.reshape(k, -1))
        # health: a non-finite loss becomes a structured event instead of
        # a silently-logged value (the value still lands in the facade —
        # bitwise compatibility — but the event stream flags it)
        for i, lv in enumerate(cast.get("loss", ())):
            if not np.isfinite(lv):
                self.emit("health.nan", round=r0 + i, loss=lv)
        if self.sinks:
            for i, r in enumerate(rounds):
                self.emit("round", round=r,
                          **{name: vals[i] for name, vals in cast.items()})

    # -- other streams ---------------------------------------------------
    def log_eval(self, r: int, eval_metrics: Dict[str, float]) -> None:
        em = {key: float(v) for key, v in eval_metrics.items()}
        self.log.eval_rounds.append(r)
        self.log.eval_metrics.append(em)
        self.emit("eval", round=r, **em)

    def log_reopt(self, r: int, *, S_est: float, S_true: float,
                  p_err: float) -> None:
        self.log.reopt_rounds.append(r)
        self.log.est_p_err.append(p_err)
        self.log.S_est.append(S_est)
        self.log.S_true.append(S_true)
        self.emit("reopt", round=r, S_est=S_est, S_true=S_true, p_err=p_err)

    def log_timing(self, r0: int, rounds: int, seconds: float,
                   spans: Optional[Dict[str, float]] = None) -> None:
        """One block's wall clock; ``spans`` are the seconds of the
        block's host steps by span name (its ``fl.block`` children)."""
        extra = {} if spans is None else {"spans": dict(spans)}
        self.emit("timing", round0=r0, rounds=rounds, seconds=seconds,
                  rounds_per_sec=rounds / seconds if seconds > 0 else 0.0,
                  **extra)

    def log_recompiles(self, grew: Dict[str, int], r: int) -> None:
        for name, growth in grew.items():
            self.emit("health.recompile", round=r, fn=name, growth=growth)

    # -- vector metric access --------------------------------------------
    def vector(self, name: str) -> Optional[np.ndarray]:
        """Stacked ``(R, n)`` history of a vector metric (None if the
        stream was never produced — telemetry off)."""
        parts = self._vectors.get(name)
        if not parts:
            return None
        return np.concatenate(parts, axis=0)

    def save_vectors(self, path) -> Optional[pathlib.Path]:
        """Dump every vector stream into one ``.npz``; returns the path
        (None when no vector stream exists)."""
        arrays = {name: self.vector(name) for name in self._vectors}
        if not arrays:
            return None
        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        np.savez(p, **arrays)
        return p

    def _emit_client_summary(self) -> None:
        """End-of-run per-client aggregates as one ``summary.clients``
        event: participation counts, bits-on-air totals, max streaks —
        the per-client histogram data without per-round JSON cost."""
        part = self.vector("client_participation")
        if part is None or not self.sinks:
            return
        bits = self.vector("client_uplink_bits")
        streak = self.vector("outage_streak")
        self.emit(
            "summary.clients",
            rounds=int(part.shape[0]),
            participation_count=part.sum(axis=0).astype(int).tolist(),
            participation_rate=(part.mean(axis=0)).round(6).tolist(),
            uplink_bits_total=(bits.sum(axis=0).tolist()
                               if bits is not None else None),
            outage_streak_max=(streak.max(axis=0).astype(int).tolist()
                               if streak is not None else None),
        )
