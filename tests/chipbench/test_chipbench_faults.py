"""A whole run of the harness on the CPU, with the look for a chip
skipped, sees ``correct`` come out true on the sound program and false
with the timed path broken underneath: a round that returns its state
unchanged, half of every batch left out (the mean over the rest), and
the aggregate altered where the kernel produces it (the heaviest
client's update counted twice), and relay weights from half of
COPT-alpha's sweeps.  (The cells run on one chip, so no exchange between
chips can be left out.)"""

import json

import pytest

from chipbench_tiny import CELL, cpu_device, tiny_root

from chipbench import harness


def _unchanged(monkeypatch):
    import repro.fl.trainer as trainer

    make = trainer.make_scan_round_fn

    def broken(*a, **kw):
        fn = make(*a, **kw)

        def scan(params, server_state, agg_state, batches, tau_up, tau_dd, A):
            metrics = fn(params, server_state, agg_state, batches, tau_up, tau_dd, A)[-1]
            return params, server_state, agg_state, metrics

        return scan

    monkeypatch.setattr(trainer, "make_scan_round_fn", broken)


def _half_batch(monkeypatch):
    import jax

    import repro.fl.round as rnd

    local = rnd._local_sgd

    def broken(loss_fn, opt, params, batches, unroll=False):
        half = jax.tree.map(lambda x: x[:, : x.shape[1] // 2], batches)
        return local(loss_fn, opt, params, half, unroll)

    monkeypatch.setattr(rnd, "_local_sgd", broken)


def _aggregate_altered(monkeypatch):
    import jax.numpy as jnp

    import repro.kernels.ops as ops

    fused = ops.fused_aggregate

    def broken(A, tau_up, tau_dd, updates, **kw):
        w = ops.collapsed_weight_row(A, tau_up, tau_dd)
        j = jnp.argmax(w)  # the heaviest client's update counted twice
        return (fused(A, tau_up, tau_dd, updates, **kw)
                + w[j] * updates[j].astype(jnp.float32))

    monkeypatch.setattr(ops, "fused_aggregate", broken)


def _copt_shortened(monkeypatch):
    import repro.core as core

    from chipbench_tiny import COPT_SETTLED

    optimize = core.optimize_weights

    def broken(model, sweeps, fine_tune_sweeps, **kw):
        del sweeps, fine_tune_sweeps
        return optimize(model, sweeps=15, fine_tune_sweeps=15, **kw)

    assert COPT_SETTLED > 15
    monkeypatch.setattr(core, "optimize_weights", broken)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("root"))


@pytest.mark.parametrize("fault,correct", [
    (None, True), (_unchanged, False), (_half_batch, False),
    (_aggregate_altered, False), (_copt_shortened, False)])
def test_harness_run_sees_correct(root, monkeypatch, capsys, fault, correct):
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    if fault is not None:
        fault(monkeypatch)
    rc = harness.main(["--workload", CELL, "--seed", "3000000019",
                       "--seconds", "0.3", "--trace", "0"],
                      root=root, device_check=cpu_device)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is correct, result["checks"]
    assert list(result)[-1] == "checks"
    tail = out.err.strip().splitlines()[-len(result["checks"]):]
    assert [line.split(":")[0] for line in tail] == [f"check {k}" for k in result["checks"]]
    assert result["window_compiles"] == 0
    assert {"rounds_per_s", "setup_s"} <= set(result["metrics"])
