"""The resume protocol beyond the conformance matrix.

The golden kill/restore/continue-*bitwise* matrix (every registered
strategy x every execution engine, including jit-cache stability across
the restore) lives in ``test_conformance.py`` now.  This file keeps the
protocol pieces the matrix does not parametrize: directory-based
periodic checkpointing, telemetry-streak and adaptive-schedule resume,
the experiment-layer wiring (spec fields, sink append mode, manifest
provenance), config-mismatch refusal, and the launcher's flag
validation.
"""

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channel import (
    AdaptiveConfig,
    AdaptiveWeightSchedule,
    ClusteredMarkovChannel,
    MarkovChannel,
    gilbert_elliott,
    gilbert_elliott_clustered,
)
from repro.ckpt import CheckpointWriter, read_state
from repro.core import optimize_weights, topology
from repro.data.pipeline import ClientDataset
from repro.fl import FLTrainer
from repro.fl.experiment import ExperimentSpec, build_experiment
from repro.optim import sgd, sgd_momentum
from repro.telemetry import JsonlSink

N, D = 6, 12


def _make_trainer(strategy="colrel", *, telemetry=False, adaptive=None,
                  metrics=None, seed=3, row_noise=0.0):
    """A tiny least-squares problem over a bursty channel with a small
    block size (4), so a 6-round run crosses a buffer refill and resume
    exercises both mid-block and cross-block regeneration.  With
    ``row_noise`` a client's rows differ, so its batches depend on its
    data RNG's state."""
    rng = np.random.default_rng(0)
    targets = rng.normal(size=(N, D)).astype(np.float32)
    noise = row_noise * rng.normal(size=(N, 64, D)).astype(np.float32)
    clients = [ClientDataset({"t": np.repeat(targets[i][None], 64, 0) + noise[i]},
                             batch_size=4, seed=i) for i in range(N)]
    if strategy == "clustered":
        model = topology.clustered_blocks(N, 0.5, 3, p_intra=0.8, rho=0.6)
        channel = ClusteredMarkovChannel(
            gilbert_elliott_clustered(model, memory=0.8), seed=5, block=4)
        A = np.full((2, 3, 3), 1.0, np.float64)  # (C, m, m) block weights
    else:
        model = topology.fully_connected(N, 0.5, p_c=0.8, rho=1.0)
        channel = MarkovChannel(gilbert_elliott(model, memory=0.8),
                                seed=5, block=4)
        A = optimize_weights(model, sweeps=5, fine_tune_sweeps=5).A

    def loss_fn(p, batch):
        r = p["x"] - batch["t"]
        return jnp.mean(r * r), None

    return FLTrainer(loss_fn, {"x": jnp.zeros((D,), jnp.float32)}, model, A,
                     clients, sgd(0.3), sgd_momentum(1.0, beta=0.9),
                     local_steps=2, strategy=strategy, seed=seed,
                     channel=channel, telemetry=telemetry, adaptive=adaptive,
                     metrics=metrics)


def _assert_same_run(a, b):
    for field in ("rounds", "loss", "participation", "uplink_bits",
                  "weight_sums"):
        av, bv = getattr(a.log, field), getattr(b.log, field)
        assert len(av) == len(bv), field
        for x, y in zip(av, bv):
            assert x == y or (np.isnan(x) and np.isnan(y)), (field, x, y)
    for name, ta, tb in (("params", a.params, b.params),
                         ("server_state", a.server_state, b.server_state),
                         ("agg_state", a.agg_state, b.agg_state)):
        la, lb = jax.tree.leaves(ta), jax.tree.leaves(tb)
        assert len(la) == len(lb), name
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)


# ---------------------------------------------------------------------------
# 1. directory-based periodic checkpointing + resume-from-latest
# ---------------------------------------------------------------------------


def test_periodic_ckpt_dir_and_resume_latest(tmp_path):
    ref = _make_trainer()
    ref.run(9, chunk=3)

    a = _make_trainer()
    a.run(6, chunk=3, ckpt_dir=tmp_path, ckpt_every=3, ckpt_keep=2)
    assert CheckpointWriter(tmp_path).steps() == [3, 6]

    b = _make_trainer()
    b.run(9, chunk=3, resume_from=tmp_path)  # directory -> latest step
    _assert_same_run(ref, b)


def test_ckpt_keep_gc(tmp_path):
    a = _make_trainer()
    a.run(8, chunk=2, ckpt_dir=tmp_path, ckpt_every=2, ckpt_keep=2)
    assert CheckpointWriter(tmp_path).steps() == [6, 8]


def test_final_only_checkpoint(tmp_path):
    """``ckpt_every=0`` with a ckpt_dir commits exactly one final state."""
    a = _make_trainer()
    a.run(5, chunk=1, ckpt_dir=tmp_path)
    assert CheckpointWriter(tmp_path).steps() == [5]
    assert read_state(CheckpointWriter(tmp_path).path_for(5))["round"] == 5


def test_misaligned_cadence_is_an_error(tmp_path):
    t = _make_trainer()
    with pytest.raises(ValueError, match="multiple of"):
        t.run(6, chunk=3, ckpt_dir=tmp_path, ckpt_every=2)


def test_per_round_prefetch_resumes_bitwise(tmp_path):
    """The per-round loop stacks round r+1's batches before round r's
    checkpoint; the checkpoint holds the client RNGs from before that
    prefetch, so resuming from round 6 continues bitwise."""
    ref = _make_trainer(row_noise=0.5)
    ref.run(9)
    a = _make_trainer(row_noise=0.5)
    a.run(9, ckpt_dir=tmp_path, ckpt_every=3)
    assert CheckpointWriter(tmp_path).steps() == [3, 6, 9]
    _assert_same_run(ref, a)
    b = _make_trainer(row_noise=0.5)
    b.run(9, resume_from=CheckpointWriter(tmp_path).path_for(6))
    _assert_same_run(ref, b)


# ---------------------------------------------------------------------------
# 2. telemetry + adaptive state across a resume
# ---------------------------------------------------------------------------


def test_telemetry_streak_resumes_bitwise(tmp_path):
    ref = _make_trainer(telemetry=True)
    ref.run(6, chunk=3)

    t1 = _make_trainer(telemetry=True)
    t1.run(3, chunk=3)
    path = t1.save_checkpoint(tmp_path / "c.msgpack")
    t2 = _make_trainer(telemetry=True)
    t2.run(6, chunk=3, resume_from=path)
    _assert_same_run(ref, t2)
    np.testing.assert_array_equal(np.asarray(ref._streak),
                                  np.asarray(t2._streak))
    np.testing.assert_array_equal(ref.metrics.vector("client_participation"),
                                  t2.metrics.vector("client_participation"))


def test_adaptive_schedule_resumes_bitwise(tmp_path):
    cfg = AdaptiveConfig(every=4, warmup=2, sweeps=3, fine_tune_sweeps=3)

    def mk():
        return _make_trainer("colrel",
                             adaptive=AdaptiveWeightSchedule(N, cfg))

    ref = mk()
    ref.run(8, chunk=2)
    assert ref.log.reopt_rounds, "fixture must actually re-optimize"

    t1 = mk()
    t1.run(4, chunk=2)
    path = t1.save_checkpoint(tmp_path / "c.msgpack")
    t2 = mk()
    t2.run(8, chunk=2, resume_from=path)
    _assert_same_run(ref, t2)
    assert t2.log.reopt_rounds == ref.log.reopt_rounds
    assert t2.log.S_est == ref.log.S_est
    np.testing.assert_array_equal(np.asarray(ref.A), np.asarray(t2.A))


# ---------------------------------------------------------------------------
# 3. experiment-layer wiring: spec fields, sinks, manifest
# ---------------------------------------------------------------------------


def test_experiment_resume_with_metrics(tmp_path):
    def spec(mdir, **kw):
        return ExperimentSpec(model="quadratic", topology="fig2a",
                              strategy="colrel", channel="markov", chunk=3,
                              rounds=6, seed=3, metrics_dir=str(mdir),
                              ckpt_dir=str(tmp_path / "ck"), ckpt_every=3,
                              **kw)

    ref = build_experiment(ExperimentSpec(
        model="quadratic", topology="fig2a", strategy="colrel",
        channel="markov", chunk=3, rounds=6, seed=3))
    ref.run(6)

    m1 = tmp_path / "m"
    a = build_experiment(spec(m1))
    a.run(3)
    a.close()

    b = build_experiment(spec(m1, resume_from=str(tmp_path / "ck")))
    b.run(6)
    b.close()
    assert ref.log.loss == b.log.loss
    # the CSV stream is exactly-once across the resume
    rows = (m1 / "rounds.csv").read_text().splitlines()
    assert [r.split(",")[0] for r in rows[1:]] == [str(r) for r in range(6)]
    # events appended, seq monotonic at-least-once across the two runs
    seqs = [e["seq"] for e in JsonlSink.load(m1 / "events.jsonl")
            if e["event"] == "round"]
    assert seqs == sorted(seqs)
    manifest = json.loads((m1 / "manifest.json").read_text())
    assert manifest["resumed_from"].endswith("ck")


# ---------------------------------------------------------------------------
# 4. mismatched configurations refuse to restore
# ---------------------------------------------------------------------------


def test_restore_refuses_mismatches(tmp_path):
    t1 = _make_trainer("colrel")
    t1.run(2)
    path = t1.save_checkpoint(tmp_path / "c.msgpack")

    with pytest.raises(ValueError, match="strategy"):
        _make_trainer("memory").run(6, resume_from=path)
    with pytest.raises(ValueError, match="telemetry"):
        _make_trainer("colrel", telemetry=True).run(6, resume_from=path)

    from repro.ckpt import restore_run_state
    state = read_state(path)
    state["version"] = 0
    with pytest.raises(ValueError, match="version"):
        restore_run_state(_make_trainer("colrel"), state)

    state = read_state(path)
    state["clients"] = state["clients"][:-1]
    with pytest.raises(ValueError, match="client"):
        restore_run_state(_make_trainer("colrel"), state)

    state = read_state(path)
    state["adaptive"] = {"estimator": {}, "events": "[]"}
    with pytest.raises(ValueError, match="adaptive"):
        restore_run_state(_make_trainer("colrel"), state)

    with pytest.raises(ValueError, match="behind"):
        # the resumed total must not be behind the checkpointed round
        _make_trainer("colrel").run(1, resume_from=path)


# ---------------------------------------------------------------------------
# 5. launcher flag validation (clear errors, not silent fallback)
# ---------------------------------------------------------------------------


def test_launcher_flag_validation():
    repo = pathlib.Path(__file__).parent.parent

    def run(*flags):
        return subprocess.run(
            [sys.executable, "-m", "repro.launch.train", "--smoke",
             "--rounds", "8", *flags],
            cwd=repo, capture_output=True, text=True,
            env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})

    r = run("--chunk", "4", "--ckpt-dir", "/tmp/x", "--ckpt-every", "6")
    assert r.returncode == 2
    assert "multiple of --chunk" in r.stderr

    r = run("--resume")
    assert r.returncode == 2
    assert "--ckpt-dir" in r.stderr

    r = run("--ckpt-every", "2")
    assert r.returncode == 2
    assert "--ckpt-dir" in r.stderr
