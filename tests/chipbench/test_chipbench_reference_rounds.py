"""The reference holds O(d), not O(n d): each client's update is added
to the aggregate and dropped before the next client trains, and the
device holds one copy of the parameters and one client's training.  Its
numbers on the tiny cell equal those of the reference that kept every
client's update on the device and summed them after the round: that
loop, kept below (``_kept_updates``), run in the same process, and the
numbers it printed on the CPU at seed 2,900,000,001 (``PARENT``)."""

import json
import math
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import CELL, tiny_root

from chipbench import alpha, assemble, harness, reference, spec
from chipbench.data import Seeds

SEED = 2_900_000_001
# XLA's CPU thread count alone moves these by up to 4.1e-6 (with Eigen
# single-threaded), so they hold at 1e-5; the same process gives 1e-6.
PARENT_RTOL = 1e-5
PARENT = {
    "losses": [2.20026273727417, 2.184919238090515, 2.149329221248627,
               2.2258406400680544],
    "delta_norms": [0.0653122060224443, 0.032149206357777266, 0.06011179685486386,
                    0.06657024824934771],
    # per-leaf norms, in the parameters' leaf order
    "momentum_first": [
        0.01182787156698389, 0.06935375776867712, 0.0005052588915039055,
        0.0004990180866783719, 0.002989409811237055, 0.0016986309232591165,
        0.002800493091836771, 0.003355770587976274, 0.001209630703437944,
        0.0009343150023326857, 0.0028838886836171146, 0.0020719550846465535,
        0.004357959511834667, 0.009108730919637098, 0.0056276425779181885,
        0.0010608718240310375, 0.001085923630111482, 0.0021363271125138306,
        0.0017608371182530928, 0.0051251029845028944, 0.010842170043193147,
        0.008252512438422479, 0.00180677594553876, 0.0014184427753140465,
        0.002193622054184216],
    "change": [
        0.030459117423463244, 0.19031094437961824, 0.001983078535031816,
        0.0022784013152096294, 0.015190351473439586, 0.00886598543461953,
        0.013162735252876305, 0.01626439168523818, 0.005816217162809923,
        0.0045547342774745194, 0.013860062172522667, 0.009661456626406634,
        0.019597065833465616, 0.043784460709896854, 0.027023504686737285,
        0.005131324655388742, 0.0052639971744109955, 0.010296733988098198,
        0.008644888017221346, 0.024114586944692647, 0.05173145049672388,
        0.03989750667984776, 0.009201874253645704, 0.0072036863764514064,
        0.00997904723679572],
}


def _kept_updates(job, k: int) -> dict:
    """The rounds as the reference ran them when it kept every client's
    update on the device and summed them after the round."""
    rounds = k * math.ceil(3 / k)
    tau_up, tau_dd = job.channel_trace(rounds)
    A = alpha.copt_alpha_job(*alpha.link_model(job.traffic["links"]),
                             int(job.traffic["copt_sweeps"]))
    T = int(job.traffic["local_steps"])
    lr, wd = np.float32(job.traffic["lr"]), np.float32(job.traffic["weight_decay"])
    beta = np.float32(job.traffic["server_momentum"])
    key = json.dumps(job.model, sort_keys=True)
    batch_idx = job.batch_indices(rounds)
    params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), job.params0)
    momentum = jax.tree.map(jnp.zeros_like, params)
    losses, delta_norms, momentum_first = [], [], None
    for r in range(rounds):
        deltas, client_losses = [], []
        for i, data in enumerate(job.clients):
            idx = batch_idx[i][r * T:(r + 1) * T]
            delta, value = reference._client_update(
                params, {n: jnp.asarray(v[idx]) for n, v in data.items()}, lr, wd,
                loss=job.kind.loss, model_key=key)
            deltas.append(delta)
            client_losses.append(value)
        w = reference.collapsed_weights(tau_up[r], tau_dd[r], A).astype(np.float32)
        agg = jax.tree.map(lambda *ds: sum(wj * d for wj, d in zip(w, ds)), *deltas)
        momentum = jax.tree.map(lambda m, d: beta * m - d, momentum, agg)
        params = jax.tree.map(lambda p, m: p - m, params, momentum)
        losses.append(float(np.mean([float(v) for v in client_losses])))
        delta_norms.append(float(np.sqrt(sum(
            float(jnp.sum(x * x)) for x in jax.tree.leaves(agg)))))
        if r + 1 == k:
            momentum_first = jax.device_get(momentum)
    return {"losses": losses, "delta_norms": delta_norms,
            "momentum_first": momentum_first, "params": jax.device_get(params)}


def _numbers(ref, params0) -> dict:
    change = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b,
                          ref["params"], params0)
    return {"losses": ref["losses"], "delta_norms": ref["delta_norms"],
            "momentum_first": _norms(ref["momentum_first"]),
            "change": _norms(change)}


def _norms(tree):
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    cell = spec.load_cell(tiny_root(tmp_path_factory.mktemp("root")), CELL)
    return assemble.build(cell.kind(), cell.config, cell.traffic,
                          Seeds.from_seed(SEED))


def test_reference_numbers_equal_the_kept_updates_reference(job):
    k = int(job.traffic["chunk"])
    got = _numbers(harness.reference_of(job, k), job.params0)
    with jax.default_matmul_precision("highest"):
        kept = _numbers(_kept_updates(job, k), job.params0)
    for key, want in kept.items():
        np.testing.assert_allclose(got[key], want, rtol=1e-6, atol=0, err_msg=key)
    for key, want in PARENT.items():
        np.testing.assert_allclose(got[key], want, rtol=PARENT_RTOL, atol=0,
                                   err_msg=key)


def test_reference_keeps_one_client_update_alive(job, monkeypatch):
    train = reference._train_client
    d_bytes = 4 * job.d
    earlier, alive, device_growth = [], [], []
    base = sum(x.nbytes for x in jax.live_arrays())

    def counted(*args):
        # before this client trains: the updates of the clients before
        # it that are still alive, and what the device holds beyond the
        # arrays of before the reference
        alive.append(sum(any(r() is not None for r in refs) for refs in earlier))
        device_growth.append(sum(x.nbytes for x in jax.live_arrays()) - base)
        delta, value = train(*args)
        earlier.append([weakref.ref(x) for x in jax.tree.leaves(delta)])
        return delta, value

    monkeypatch.setattr(reference, "_train_client", counted)
    ref = harness.reference_of(job, int(job.traffic["chunk"]))
    n, rounds = len(job.clients), len(ref["losses"])
    assert len(alive) == n * rounds
    assert max(alive) == 0, alive
    # one copy of the parameters (the round's, on the device), nothing
    # that grows with the clients
    assert max(device_growth) <= d_bytes, (device_growth, d_bytes)
    # the server's state lives on the host
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(ref["params"]))
