"""One run of one cell: set-up, the measured window, an optional traced
stretch, and the output check against the reference.

Set-up assembles the cell's trainer, drives its first rounds through
the window's own call (``FLTrainer.run(K, chunk=K)``, block by block)
and keeps what the check compares, then runs two more blocks to time
one.  The window is one ``run(R, chunk=K)`` call, R a multiple of K set
from that time, fenced at its end; no program compiles inside it (the
count of compilations in the window is printed).  The trainer's host-span
totals are taken before and after the window.  With ``--trace 1`` the
executed program's instructions are mapped to device scopes and a short
traced stretch follows the window.  Once the window has closed and peak
memory has been read, the program's state is dropped and the reference
follows the same first rounds.

The metric readers (``chipbench/metrics/<name>.py``) get the run's
record:

- ``setup_s``, ``build_s``, ``warmup_s``: seconds of set-up;
- ``window``: ``rounds`` and ``seconds`` of the window, and ``spans``
  (:func:`window_split`: each host span's milliseconds, each span's
  count and each counter, per round of the window);
- ``trace``: ``None`` untraced; else :func:`chipbench.scopes.reduce` of
  the traced stretch (seconds over its ``traced_rounds`` rounds:
  ``busy_s``, ``window_s``, ``ops``, ``scopes`` by device scope,
  ``idle_by_span`` by host span, ...) and its ``rounds_per_s``;
- ``traced_rounds``, ``op_scopes_s``, ``peaks``, ``chips``,
  ``round_flops``, ``n_clients``, ``d``, ``memory_peak_bytes``.
"""

from __future__ import annotations

import gc
import glob
import gzip
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time
import traceback
from typing import Callable, Optional

from chipbench import spec

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 3.0   # least length of the traced stretch
PROBE_ROUNDS = 4      # least length of the rate probe, in rounds


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def check_device(chips: int, bench_dir: pathlib.Path):
    """The devices the cell uses, the ``device`` record, and the chip's
    peaks; refuses any platform but ``tpu``, too few chips, and a
    ``device_kind`` missing from ``peaks.json``."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoChip(f"platform is {d0.platform!r}, not 'tpu'")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    try:
        peaks = spec.load_peaks(d0.device_kind, bench_dir)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return devices, {"platform": d0.platform, "kind": d0.device_kind,
                     "count": len(devices)}, peaks


class CompileCounter:
    """Counts backend compilations, persistent-cache loads included."""

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


def enable_cache() -> pathlib.Path:
    """The program's persistent compilation cache (``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``), caching every program,
    however quick to compile."""
    import jax

    from repro.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def profiler_options():
    """Python frames and host events of the first level only: at the
    default level the runtime's per-chunk transpose events cost seconds
    per block of the paper's job."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    return options


def window_split(before: dict, after: dict, rounds: int) -> dict:
    """Milliseconds per round of each host span, and counts and counters
    per round, between two ``Spans.snapshot()`` s."""
    def per_round(key, scale=1.0):
        return {k: (v - before[key].get(k, 0)) * scale / rounds
                for k, v in after[key].items() if v != before[key].get(k, 0)}

    return {"span_ms": per_round("seconds", 1e3), "counts": per_round("counts"),
            "counters": per_round("counters")}


def _traced_stretch(trainer, rounds: int, k: int, op_scopes: dict,
                    keep: Optional[pathlib.Path] = None) -> dict:
    """Trace ``rounds`` rounds of the window's call and reduce the trace
    by the program's names (``op_scopes``: ``FLTrainer.op_scopes(k)``);
    ``keep`` names a file to hold the trace, gzipped."""
    import jax

    from chipbench import scopes, trace

    tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        jax.profiler.start_trace(tmp, profiler_options=profiler_options())
        try:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                trainer.run(rounds, chunk=k)
                jax.block_until_ready(trainer.params)
            seconds = time.perf_counter() - t
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        if keep is not None:
            keep.write_bytes(gzip.compress(pathlib.Path(path[0]).read_bytes()))
        return dict(scopes.reduce(trace.load(path[0]), op_scopes),
                    rounds_per_s=rounds / seconds)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def drive_check_rounds(job, k: int) -> dict:
    """Drive the job's first rounds through the window's own call, block
    by block, and keep what the check compares: the per-round losses and
    aggregate norms, the server momentum after the first block, the
    parameters, and the relay weights.  Then detach the logger's sinks."""
    import jax

    tr = job.trainer
    rounds = k * math.ceil(3 / k)
    momentum_first = None
    for b in range(rounds // k):
        tr.run(k, chunk=k)
        if b == 0:
            momentum_first = jax.device_get(tr.server_state["m"])
    events = [e for sink in tr.metrics.sinks for e in sink.of_kind("round")]
    tr.metrics.sinks.clear()
    return {"losses": list(tr.log.loss[:rounds]),
            "delta_norms": [e["delta_norm"] for e in events][:rounds],
            "momentum_first": momentum_first,
            "params": jax.device_get(tr.params), "params0": job.params0,
            "A": job.A}


def window_rounds(trainer, k: int, seconds: float) -> int:
    """Rounds for a window of about ``seconds``: probe with two blocks of
    the window's call, take the slower block as the steady block time
    (the trainer's meter times each block from its dispatch to its
    fence) and the rest of the probe's wall time as the call's fixed
    cost, such as stacking its first block."""
    import jax

    blocks = max(2, math.ceil(PROBE_ROUNDS / k))
    t = time.perf_counter()
    trainer.run(blocks * k, chunk=k)
    jax.block_until_ready(trainer.params)
    wall = time.perf_counter() - t
    timed = [c["seconds"] for c in trainer.meter.chunks[-blocks:]]
    fixed = max(0.0, wall - sum(timed))
    return k * max(1, round((seconds - fixed) / max(timed)))


def reference_of(job, k: int, fault: Optional[str] = None, A=None) -> dict:
    """The reference over the rounds :func:`drive_check_rounds` drove,
    with relay weights of its own (:mod:`chipbench.alpha`: COPT-alpha for
    the traffic's sweeps, unless ``A`` is given; and, as the yardstick of
    their variance, settled) and the taus of the program's channel."""
    from chipbench import alpha, reference

    rounds = k * math.ceil(3 / k)
    tau_up, tau_dd = job.channel_trace(rounds)
    links = alpha.link_model(job.traffic["links"])
    if A is None:
        A = alpha.copt_alpha_job(*links, int(job.traffic["copt_sweeps"]))
    ref = reference.run_rounds(job.kind, job.model, job.traffic, job.params0,
                               job.clients, job.batch_indices(rounds), tau_up,
                               tau_dd, A, rounds, k, fault=fault)
    ref.update(params0=job.params0, A=A, A_settled=alpha.copt_alpha(*links),
               links=links)
    return ref


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_start: float, device_check: Callable = check_device) -> dict:
    """One run; returns the result line and the lines of the check."""
    import jax
    import numpy as np

    from chipbench import assemble, check
    from chipbench.data import Seeds

    devices, device, peaks = device_check(cell.chips, cell.bench_dir)
    enable_cache()
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    t = time.perf_counter()
    job = assemble.build(cell.kind(), cell.config, cell.traffic,
                         Seeds.from_seed(seed))
    build_s = time.perf_counter() - t

    t = time.perf_counter()
    tr, k = job.trainer, int(cell.traffic["chunk"])
    prog = drive_check_rounds(job, k)
    rounds = window_rounds(tr, k, seconds)
    warmup_s = time.perf_counter() - t

    setup_s = time.perf_counter() - t_start
    before, spans = compiles.count, tr.spans.snapshot()
    t = time.perf_counter()
    tr.run(rounds, chunk=k)
    jax.block_until_ready(tr.params)
    window_s = time.perf_counter() - t
    window_compiles = compiles.count - before
    spans = window_split(spans, tr.spans.snapshot(), rounds)
    losses = np.asarray(tr.log.loss[-rounds:], np.float64)

    record = {"setup_s": setup_s, "build_s": build_s, "warmup_s": warmup_s,
              "window": {"rounds": rounds, "seconds": window_s, "spans": spans},
              "peaks": peaks, "chips": cell.chips,
              "round_flops": cell.flops().round_flops(cell.config["model"],
                                                       cell.traffic),
              "n_clients": int(cell.traffic["n_clients"]), "d": job.d,
              "trace": None, "traced_rounds": 0}
    if traced:
        t = time.perf_counter()
        op_scopes = tr.op_scopes(k)
        record["op_scopes_s"] = time.perf_counter() - t
        per_round = window_s / rounds
        record["traced_rounds"] = k * max(3, math.ceil(TRACE_SECONDS / per_round / k))
        record["trace"] = _traced_stretch(tr, record["traced_rounds"], k, op_scopes)
    record["memory_peak_bytes"] = _peak_bytes(devices)

    job.trainer = tr = None
    gc.collect()
    numbers = check.compare(prog, reference_of(job, k))
    limits = cell.limits["limits"]

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = cell.reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=record["memory_peak_bytes"])
    if traced:
        device.update(busy_s=record["trace"]["busy_s"],
                      window_s=record["trace"]["window_s"])
    result = {"correct": check.judge(numbers, limits),
              "attempted": rounds,
              "failed": int(np.sum(~np.isfinite(losses))),
              "metrics": metrics, "device": device,
              "window_compiles": window_compiles}
    if traced:
        result["traced_rounds_per_s"] = record["trace"]["rounds_per_s"]
        result["op_scopes_s"] = record["op_scopes_s"]
        result["layers"] = _layers(record)
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in record["trace"]["top_ops"]],
            "idle_gaps": [[n, s] for n, s in record["trace"]["idle_by_label"]]}
    result["checks"] = check.report(numbers, limits)
    return {"result": result, "check_lines": check.lines(numbers, limits)}


def _layers(record: dict) -> dict:
    """The traced run's split by the program's names: device milliseconds
    by scope and idle milliseconds by host span, per traced round; host
    milliseconds by span, span counts and counters, per window round."""
    trace, rounds = record["trace"], record["traced_rounds"]
    return {"scope_ms": {s: v * 1e3 / rounds for s, v in trace["scopes"].items()},
            "idle_by_span_ms": {s: v * 1e3 / rounds
                                for s, v in trace["idle_by_span"].items()},
            **record["window"]["spans"]}


def main(argv=None, *, root: Optional[pathlib.Path] = None,
         bench_dir: Optional[pathlib.Path] = None,
         device_check: Callable = check_device,
         t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = pathlib.Path(root) if root else spec.BENCH_DIR.parent
    try:
        cell = spec.load_cell(root, args.workload, bench_dir)
        out = run(cell, args.seed, args.seconds, bool(args.trace), t_start,
                  device_check)
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    sys.stdout.flush()
    for line in out["check_lines"]:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["result"]))
    sys.stdout.flush()
    return 0
