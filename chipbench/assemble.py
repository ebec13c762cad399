"""Assembling a cell's ``FLTrainer`` from the program's public pieces,
the same ones ``repro.fl.experiment.build_experiment`` uses, with the
weights and data the benchmark makes from the seed.  What depends on the
model comes from the configuration's kind (``chipbench/kinds/<kind>.py``);
the channel, the relay weights, the strategy, the optimizers and the
trainer are the same for every kind."""

from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import data as bench_data


@dataclasses.dataclass
class Job:
    """A built trainer and the inputs the reference needs to follow it."""

    trainer: Any
    kind: ModuleType           # chipbench/kinds/<kind>.py
    model: Dict[str, Any]
    traffic: Dict[str, Any]
    seeds: bench_data.Seeds
    clients: List[Dict[str, np.ndarray]]
    A: np.ndarray
    params0: Any               # host copy of the initial parameters
    d: int

    def channel_trace(self, rounds: int):
        """The connectivity of rounds ``[0, rounds)``: a fresh channel
        with the trainer's seed serves the same stream."""
        ch = make_channel(self.traffic, self.seeds)
        up, dd = ch.trace(0, rounds)
        return np.asarray(up, np.float64), np.asarray(dd, np.float64)

    def batch_indices(self, rounds: int) -> List[np.ndarray]:
        T, B = int(self.traffic["local_steps"]), int(self.traffic["batch_size"])
        return [bench_data.batch_indices(self.seeds.client(i),
                                         len(next(iter(c.values()))), rounds * T, B)
                for i, c in enumerate(self.clients)]


def link_model(traffic: dict):
    from repro.fl.experiment import TOPOLOGIES

    return TOPOLOGIES[traffic["topology"]]()


def make_channel(traffic: dict, seeds: bench_data.Seeds):
    from repro.configs import make_channel as program_channel

    return program_channel(traffic["channel"], link_model(traffic),
                           seed=seeds.channel)


def build(kind: ModuleType, config: dict, traffic: dict,
          seeds: bench_data.Seeds, *, dtype: Optional[str] = None,
          init_params=None, clients=None) -> Job:
    """The cell's trainer, its metrics logger holding one in-memory sink
    (the per-round events carry the aggregate's norm, which the check
    reads; the harness empties the sink list before the window, so the
    window runs the logger as a default trainer does).

    ``kind`` is the configuration's kind module.  ``dtype`` overrides the
    model's (the program's own lower-precision path); ``init_params`` /
    ``clients`` (each client's host arrays) replace the benchmark's
    weights or data (the tests use them to show the assembly equals
    ``build_experiment``'s)."""
    import jax

    from repro import strategies
    from repro.core import optimize_weights
    from repro.data.pipeline import ClientDataset
    from repro.fl.trainer import FLTrainer
    from repro.optim import sgd, sgd_momentum
    from repro.telemetry import MemorySink, MetricsLogger

    model = config["model"]
    bundle = kind.program_model(model, dtype)
    channel = make_channel(traffic, seeds)
    init_model = channel.model_for_round(0)
    n = init_model.n
    if n != int(traffic["n_clients"]):
        raise ValueError(f"topology {traffic['topology']!r} has {n} clients, "
                         f"traffic says {traffic['n_clients']}")
    strategy = strategies.resolve(traffic["strategy"],
                                  **dict(traffic.get("strategy_options", {})))
    A = optimize_weights(init_model, sweeps=int(traffic["copt_sweeps"]),
                         fine_tune_sweeps=int(traffic["copt_sweeps"])).A
    strategy = strategy.calibrate(init_model, A)

    if clients is None:
        clients = kind.client_arrays(model, traffic, seeds)
    B = int(traffic["batch_size"])
    datasets = [ClientDataset(a, B, seed=seeds.client(i))
                for i, a in enumerate(clients)]

    if init_params is None:
        init_params = kind.init_params(model, seeds.init, dtype)
    expect = jax.tree.structure(jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    if jax.tree.structure(init_params) != expect:
        raise ValueError("the benchmark's parameter layout differs from the "
                         "program's model")
    params0 = jax.device_get(init_params)
    d = int(sum(np.size(x) for x in jax.tree.leaves(params0)))

    trainer = FLTrainer(
        bundle.loss_fn, init_params, init_model, A, datasets,
        sgd(float(traffic["lr"]), weight_decay=float(traffic["weight_decay"])),
        sgd_momentum(1.0, beta=float(traffic["server_momentum"])),
        local_steps=int(traffic["local_steps"]), strategy=strategy,
        mode=traffic["mode"], segment_d=int(traffic["segment_d"]),
        seed=seeds.channel, channel=channel,
        metrics=MetricsLogger([MemorySink()]))
    return Job(trainer=trainer, kind=kind, model=model, traffic=traffic,
               seeds=seeds, clients=clients, A=np.asarray(A, np.float64),
               params0=params0, d=d)
