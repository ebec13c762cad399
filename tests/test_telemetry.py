"""The observability subsystem (DESIGN.md §11).

Five layers:
  1. device tier — the outage-streak recurrence, the instrumented
     round's vector metrics, and the guarantee that instrumentation
     changes *nothing*: trajectories and scalar metric streams are
     bitwise identical with telemetry on or off, in the per-round loop,
     the compiled scan, and no-trace mode, for static and Markov
     channels;
  2. per-client metric agreement — the ``(K, n)`` vectors from the
     compiled scan match the per-round loop exactly, and the no-trace
     in-scan sampler's vectors match an exact host-side replication of
     its PRNG stream;
  3. host tier — the one deduped ``log_rounds`` append path keeps the
     TrainLog facade bitwise-compatible with the pre-telemetry casts,
     sinks receive well-formed event streams (JSONL round-trip, CSV,
     NaN health events, monotonic ``seq``), and the run manifest digest
     is stable;
  4. timing tier — fenced throughput, recompile detection, and the
     profiler window state machine;
  5. the production lowering — ``build_step(telemetry=True)`` lowers
     with the streak operand and client-axis vector shardings on the
     1-device mesh (where every rule degenerates to replication).
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channel import MarkovChannel, StaticChannel, gilbert_elliott
from repro.core import optimize_weights, topology
from repro.data import quadratic_problem
from repro.data.pipeline import ClientDataset
from repro.fl import FLTrainer
from repro.telemetry import (
    SCALAR_STREAMS,
    VECTOR_METRICS,
    CompileTracker,
    CsvSummarySink,
    JsonlSink,
    MemorySink,
    MetricsLogger,
    ProfileWindow,
    RunManifest,
    ThroughputMeter,
    config_digest,
    git_sha,
    init_streak,
    update_streak,
)

N = 10


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

_PROB = quadratic_problem(N, 16, mu=1.0, L=8.0, hetero=1.0, seed=0)
_H = jnp.asarray(_PROB["H"], jnp.float32)
_MODEL = topology.paper_fig2a()
_A = optimize_weights(_MODEL, sweeps=10, fine_tune_sweeps=10).A


def _loss_fn(params, batch):
    x = params["x"]
    d = x - batch["center"][0]
    return 0.5 * d @ (_H @ d) + 0.1 * batch["noise"][0] @ x, {}


def _clients():
    out = []
    for i in range(N):
        c = _PROB["centers"][i].astype(np.float32)
        pool = np.random.default_rng(100 + i).normal(
            size=(2048, 16)).astype(np.float32)
        out.append(ClientDataset({"center": np.tile(c, (2048, 1)),
                                  "noise": pool}, batch_size=1, seed=7 + i))
    return out


def _trainer(*, telemetry=False, metrics=None, channel=None, profile=None,
             strategy="colrel"):
    from repro.optim import sgd, sgd_momentum

    return FLTrainer(_loss_fn, {"x": jnp.zeros(16)}, _MODEL, _A, _clients(),
                     sgd(0.02), sgd_momentum(1.0, beta=0.0), local_steps=4,
                     strategy=strategy, seed=0, telemetry=telemetry,
                     metrics=metrics, channel=channel, profile=profile)


def _markov():
    return MarkovChannel(gilbert_elliott(_MODEL, memory=0.8), seed=3)


def _assert_scalars_bitwise(a, b):
    for field in ("rounds", "loss", "participation", "uplink_bits",
                  "weight_sums"):
        av, bv = getattr(a.log, field), getattr(b.log, field)
        assert len(av) == len(bv), field
        for x, y in zip(av, bv):
            assert x == y or (np.isnan(x) and np.isnan(y)), (field, x, y)
    np.testing.assert_array_equal(np.asarray(a.params["x"]),
                                  np.asarray(b.params["x"]))


def _expected_streak(part: np.ndarray) -> np.ndarray:
    """Roll the outage-streak recurrence over a (R, n) participation
    history on host (the reference the device carry must match)."""
    out = np.zeros_like(part, dtype=np.int64)
    age = np.zeros(part.shape[1], np.int64)
    for r in range(part.shape[0]):
        age = np.where(part[r] > 0, 0, age + 1)
        out[r] = age
    return out


# ---------------------------------------------------------------------------
# 1. device tier
# ---------------------------------------------------------------------------


def test_streak_recurrence():
    s = init_streak(4)
    assert s.dtype == jnp.int32 and s.shape == (4,)
    s = update_streak(s, jnp.asarray([1.0, 0.0, 0.0, 1.0]))
    np.testing.assert_array_equal(np.asarray(s), [0, 1, 1, 0])
    s = update_streak(s, jnp.asarray([0.0, 0.0, 1.0, 1.0]))
    np.testing.assert_array_equal(np.asarray(s), [1, 2, 0, 0])
    assert s.dtype == jnp.int32  # carry stays shape/dtype-stable


def test_instrumented_round_is_inert():
    """Telemetry on vs off: identical params and scalar streams, plus
    correct vector metrics (per-round loop)."""
    base = _trainer()
    base.run(6)
    tel = _trainer(telemetry=True)
    tel.run(6)
    _assert_scalars_bitwise(base, tel)
    part = tel.metrics.vector("client_participation")
    bits = tel.metrics.vector("client_uplink_bits")
    streak = tel.metrics.vector("outage_streak")
    assert part.shape == bits.shape == streak.shape == (6, N)
    # scalar streams are exact reductions of the vector streams
    np.testing.assert_array_equal(
        part.sum(axis=1), np.float64(np.float32(base.log.participation)))
    np.testing.assert_allclose(
        bits.sum(axis=1), np.asarray(base.log.uplink_bits), rtol=1e-6)
    np.testing.assert_array_equal(streak, _expected_streak(part))
    # participation vectors are 0/1 realizations
    assert set(np.unique(part)) <= {0.0, 1.0}


@pytest.mark.parametrize("channel_fn", [None, _markov],
                         ids=["static", "markov"])
def test_loop_vs_scan_telemetry_bitwise(channel_fn):
    """chunk=K with telemetry reproduces the per-round loop bitwise —
    scalars AND per-client vectors — under static and Markov channels."""
    ch = channel_fn() if channel_fn else None
    loop = _trainer(telemetry=True, channel=channel_fn() if channel_fn else None)
    loop.run(8)
    chunked = _trainer(telemetry=True, channel=ch)
    chunked.run(8, chunk=4)
    _assert_scalars_bitwise(loop, chunked)
    for name in VECTOR_METRICS:
        np.testing.assert_array_equal(
            loop.metrics.vector(name), chunked.metrics.vector(name), err_msg=name)


def test_chunked_telemetry_off_matches_pre_telemetry_golden():
    """The telemetry-off chunked path is still bitwise-identical to the
    per-round loop (the satellite-1 dedupe changed the append code)."""
    a = _trainer()
    a.run(7)  # odd round count: chunk path + tail remainder
    b = _trainer()
    b.run(7, chunk=3)
    _assert_scalars_bitwise(a, b)


def test_no_trace_matches_host_replication_of_sampler():
    """No-trace telemetry vectors equal an exact host-side replay of the
    in-scan sampler's PRNG stream (same splits the trainer performs)."""
    ch = _markov()
    t = _trainer(telemetry=True, channel=ch)
    t.run(8, chunk=4, no_trace=True)
    part = t.metrics.vector("client_participation")
    streak = t.metrics.vector("outage_streak")

    init_fn, sample_fn = _markov().scan_sampler()
    key = jax.random.PRNGKey(0)  # trainer seed
    key, sub = jax.random.split(key)
    state = init_fn(sub)
    expect = []
    for _ in range(8):
        key, sub = jax.random.split(key)
        tu, td, state = sample_fn(state, sub)
        expect.append(np.asarray(tu, np.float32))
    expect = np.stack(expect)
    np.testing.assert_array_equal(part, expect)
    np.testing.assert_array_equal(streak, _expected_streak(expect))


def test_streak_carries_across_chunk_and_mode_boundaries():
    """The streak age survives host syncs: a run split across run()
    calls and chunk boundaries equals one uninterrupted run."""
    whole = _trainer(telemetry=True)
    whole.run(8, chunk=4)
    split = _trainer(telemetry=True)
    split.run(4)           # per-round loop...
    split.run(4, chunk=4)  # ...hands the streak to the compiled scan
    np.testing.assert_array_equal(whole.metrics.vector("outage_streak"),
                                  split.metrics.vector("outage_streak"))
    _assert_scalars_bitwise(whole, split)


# ---------------------------------------------------------------------------
# 3. host tier
# ---------------------------------------------------------------------------


def test_log_rounds_cast_matches_legacy_paths():
    """The deduped cast equals both pre-telemetry casts: per-round
    ``float(x)`` and chunked ``np.asarray(x, np.float64).tolist()``."""
    vals = np.asarray([0.1, 2.5, np.float32(1) / 3], np.float32)
    logger = MetricsLogger()
    logger.log_rounds(0, {"loss": vals[0]})          # per-round shape ()
    logger.log_rounds(1, {"loss": vals[1:]}, k=2)    # chunk shape (2,)
    assert logger.log.loss == [float(v) for v in vals]
    assert logger.log.loss == np.asarray(vals, np.float64).tolist()
    assert logger.log.rounds == [0, 1, 2]


def test_round_events_and_seq_monotonic():
    sink = MemorySink()
    logger = MetricsLogger([sink])
    logger.log_rounds(0, {"loss": np.float32(1.0),
                          "participation": np.float32(3.0)})
    logger.log_eval(0, {"acc": 0.5})
    logger.log_timing(0, 4, 2.0)
    seqs = [e["seq"] for e in sink.events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    ev = sink.of_kind("round")[0]
    assert ev["round"] == 0 and ev["loss"] == 1.0 and ev["participation"] == 3.0
    assert sink.of_kind("timing")[0]["rounds_per_sec"] == 2.0


def test_nan_loss_emits_health_event():
    sink = MemorySink()
    logger = MetricsLogger([sink])
    logger.log_rounds(4, {"loss": np.asarray([1.0, np.nan], np.float32)}, k=2)
    nan_ev = sink.of_kind("health.nan")
    assert len(nan_ev) == 1 and nan_ev[0]["round"] == 5
    # the value still lands in the facade (bitwise compatibility)
    assert len(logger.log.loss) == 2 and np.isnan(logger.log.loss[1])


def test_jsonl_sink_round_trip(tmp_path):
    path = tmp_path / "events.jsonl"
    sink = JsonlSink(path, buffer=2)
    logger = MetricsLogger([sink])
    for r in range(5):
        logger.log_rounds(r, {"loss": np.float32(r)})
    logger.close()
    events = JsonlSink.load(path)
    rounds = [e for e in events if e["event"] == "round"]
    assert [e["round"] for e in rounds] == list(range(5))
    assert all(json.dumps(e) for e in events)  # every line valid JSON


def test_csv_summary_sink(tmp_path):
    path = tmp_path / "rounds.csv"
    logger = MetricsLogger([CsvSummarySink(path)])
    logger.log_rounds(0, {"loss": np.float32(1.5),
                          "participation": np.float32(2.0),
                          "uplink_bits": np.float32(8.0),
                          "weight_sum": np.float32(1.0),
                          "weight_drift": np.float32(0.0)})
    logger.close()
    lines = path.read_text().strip().splitlines()
    assert lines[0].startswith("round,loss,participation")
    assert lines[1].split(",")[0] == "0" and float(lines[1].split(",")[1]) == 1.5


def test_client_summary_and_vectors_npz(tmp_path):
    sink = MemorySink()
    logger = MetricsLogger([sink])
    part = np.asarray([[1, 0], [0, 0], [1, 1]], np.float32)
    for r in range(3):
        logger.log_rounds(r, {
            "loss": np.float32(0.0),
            "client_participation": part[r],
            "client_uplink_bits": part[r] * 32.0,
            "outage_streak": _expected_streak(part)[r],
        })
    p = logger.save_vectors(tmp_path / "vectors.npz")
    logger.close()
    summ = sink.of_kind("summary.clients")[0]
    assert summ["participation_count"] == [2, 1]
    assert summ["outage_streak_max"] == [1, 2]
    loaded = np.load(p)
    np.testing.assert_array_equal(loaded["client_participation"], part)


def test_manifest_digest_and_write(tmp_path):
    cfg = {"b": 1, "a": [1, 2], "arr": np.arange(3), "f": np.float32(0.5)}
    d1 = config_digest(cfg)
    d2 = config_digest({"a": [1, 2], "f": np.float32(0.5),
                        "arr": np.arange(3), "b": 1})
    assert d1 == d2  # key order independent
    assert d1 != config_digest({**cfg, "b": 2})
    m = RunManifest.collect(cfg, strategy="colrel", channel="markov",
                            codec="int8", mesh_shape={"data": 1},
                            n_clients=4)
    assert m.backend == jax.default_backend()
    assert m.jax_version == jax.__version__
    assert m.config_digest == d1
    p = m.write(tmp_path)
    loaded = json.loads(p.read_text())
    assert loaded["strategy"] == "colrel" and loaded["codec"] == "int8"
    assert loaded["extra"]["n_clients"] == 4
    # this repo is a git checkout, so the SHA resolves here
    assert git_sha(str(pathlib.Path(__file__).parent)) is not None


# ---------------------------------------------------------------------------
# 4. timing tier
# ---------------------------------------------------------------------------


def test_throughput_meter_fences():
    meter = ThroughputMeter()
    meter.start()
    x = jnp.ones((256, 256)) @ jnp.ones((256, 256))
    dt = meter.stop(4, fence=x)
    assert dt > 0 and meter.total_rounds == 4
    assert meter.rounds_per_sec() == pytest.approx(4 / dt)
    with pytest.raises(RuntimeError):
        meter.stop(1)


def test_compile_tracker_detects_retrace():
    calls = jax.jit(lambda x: x * 2)
    tracker = CompileTracker()
    tracker.register("f", calls)
    calls(jnp.zeros(3))
    assert tracker.check() == {"f": 1}  # first (expected) compile
    calls(jnp.zeros(3))
    assert tracker.check() == {}       # steady state: cache hit
    calls(jnp.zeros(5))                # new shape: retrace
    assert tracker.check() == {"f": 1}
    assert tracker.compile_counts()["f"] == 2


def test_profile_window_state_machine(monkeypatch):
    calls = []
    levels = []

    def start_trace(d, profiler_options=None):
        levels.append(profiler_options.host_tracer_level)
        calls.append(("start", d))

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    w = ProfileWindow("/tmp/prof", start=4, rounds=4)
    assert not w.maybe_start(0) and calls == []
    assert w.maybe_start(4) and calls == [("start", "/tmp/prof")]
    assert levels == [1]  # host events of the first level only
    assert w.maybe_start(6)            # still capturing, no double-start
    assert not w.maybe_stop(6)         # window not yet past r=8
    assert w.maybe_stop(8) and calls[-1] == ("stop", None)
    assert not w.maybe_start(12)       # one-shot: never restarts
    w2 = ProfileWindow("/tmp/prof", start=0, rounds=2)
    w2.maybe_start(0)
    w2.close()                         # force-stop a dangling window
    assert calls[-1] == ("stop", None) and w2.done
    with pytest.raises(ValueError):
        ProfileWindow("/tmp/prof", rounds=0)


def test_trainer_emits_timing_and_registers_compiles():
    sink = MemorySink()
    t = _trainer(telemetry=True, metrics=MetricsLogger([sink]))
    t.run(4, chunk=2)
    timing = sink.of_kind("timing")
    assert [e["round0"] for e in timing] == [0, 2]
    assert all(e["rounds"] == 2 and e["seconds"] > 0 for e in timing)
    assert t.meter.total_rounds == 4
    # the scan fn compiled exactly once; its expected first compile is
    # filtered, so no recompile health events
    assert t.compiles.compile_counts()["scan_fn"] == 1
    assert sink.of_kind("health.recompile") == []


# ---------------------------------------------------------------------------
# 5. production lowering (1-device mesh; rules degenerate to replication)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scan_rounds", [None, 2], ids=["per_round", "scan"])
def test_build_step_telemetry_lowers(scan_rounds):
    from repro.configs.base import get_arch
    from repro.launch.steps import build_step

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = get_arch("qwen3-0.6b").smoke()
    step, lower_args, in_sh, out_sh = build_step(
        "qwen3-0.6b", "train_4k", mesh, scan_rounds=scan_rounds,
        cfg_override=cfg, telemetry=True)
    C = lower_args[4].shape[-1]
    assert lower_args[-1].shape == (C,) and lower_args[-1].dtype == jnp.int32
    # out tree: (params, server_state, agg_state, streak, metrics)
    assert len(out_sh) == 5
    metrics_sh = out_sh[4]
    for name in VECTOR_METRICS:
        assert name in metrics_sh, name
    assert "weight_drift" in metrics_sh
    with mesh:
        jax.jit(step, in_shardings=in_sh, out_shardings=out_sh).lower(*lower_args)


def test_telemetry_rule_shards_client_axis():
    """On a mesh with a real client axis the (n,) streak shards over it;
    the scan variant skips the leading K axis."""
    from repro.launch.sharding import telemetry_rule

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rule = telemetry_rule()
    spec = rule.spec("streak", (8,), mesh)
    assert spec == jax.sharding.PartitionSpec(None)  # 1-device: replicated
    scan_rule = telemetry_rule(scan=True)
    spec = scan_rule.spec("outage_streak", (4, 8), mesh)
    assert spec == jax.sharding.PartitionSpec(None, None)


# ---------------------------------------------------------------------------
# experiment-level wiring
# ---------------------------------------------------------------------------


def test_experiment_spec_telemetry_wiring(tmp_path):
    from repro.fl import ExperimentSpec, build_experiment

    spec = ExperimentSpec(model="quadratic", topology="fig2a", rounds=4,
                          chunk=2, metrics_dir=str(tmp_path / "m"))
    exp = build_experiment(spec)
    assert exp.trainer.telemetry  # metrics_dir implies the device tier
    assert exp.manifest is not None
    assert (tmp_path / "m" / "manifest.json").exists()
    exp.run()
    exp.close()
    assert (tmp_path / "m" / "vectors.npz").exists()
    events = JsonlSink.load(tmp_path / "m" / "events.jsonl")
    kinds = {e["event"] for e in events}
    assert {"round", "timing", "summary.clients"} <= kinds
    assert len([e for e in events if e["event"] == "round"]) == 4
    man = json.loads((tmp_path / "m" / "manifest.json").read_text())
    assert man["config"]["model"] == "quadratic"
    assert man["config_digest"] == config_digest(man["config"])
