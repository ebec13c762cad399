"""The benchmark's assembly, its plain float32 reference and its control,
on the CPU at reduced width (``resnet20-thin``)."""

import numpy as np
import pytest

from chipbench_tiny import CELL, THIN, tiny_root

from chipbench import alpha, assemble, check, control, data, spec
from chipbench.kinds import cnn

SEED = 7
TRAFFIC = {"n_clients": 10, "local_steps": 2, "batch_size": 4, "data_size": 400,
           "partition": {"kind": "sort_and_partition", "s": 3},
           "topology": "fig2b", "channel": "markov", "strategy": "colrel",
           "strategy_options": {"fused": "kernel"}, "copt_sweeps": 5,
           "mode": "per_client", "segment_d": 0, "chunk": 1, "lr": 0.05,
           "weight_decay": 1e-4, "server_momentum": 0.9}


def test_assembled_first_round_equals_build_experiment_bitwise():
    import jax

    from repro.data import partition_sort_and_partition, synthetic_cifar
    from repro.fl import ExperimentSpec, build_experiment

    exp = build_experiment(ExperimentSpec(
        model="cifar_cnn", topology="fig2b", non_iid_s=3, strategy="colrel",
        strategy_options={"fused": "kernel"}, channel="markov",
        data_size=400, batch_size=4, local_steps=2, copt_sweeps=5, seed=SEED))
    images, labels = synthetic_cifar(n=400, seed=SEED + 1)
    np.testing.assert_array_equal(
        np.concatenate(data.partition(labels, 10, TRAFFIC["partition"], SEED)),
        np.concatenate(partition_sort_and_partition(labels, 10, s=3, seed=SEED)))
    seeds = data.Seeds(data=SEED + 1, partition=SEED, init=SEED, channel=SEED,
                       clients=SEED)
    clients = [{"images": images[idx], "labels": labels[idx]}
               for idx in data.partition(labels, 10, TRAFFIC["partition"], SEED)]
    job = assemble.build(cnn, {"model": THIN}, TRAFFIC, seeds,
                         init_params=cnn.program_model(THIN).init(
                             jax.random.PRNGKey(SEED)),
                         clients=clients)
    np.testing.assert_array_equal(job.A, exp.A)
    exp.run(1, chunk=1)
    job.trainer.run(1, chunk=1)
    assert job.trainer.log.loss == exp.log.loss
    for a, b in zip(jax.tree.leaves(job.trainer.params), jax.tree.leaves(exp.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    root = tiny_root(tmp_path_factory.mktemp("root"))
    return spec.load_cell(root, CELL)


def test_reference_agrees_and_the_control_does_not(tiny_cell):
    limits = tiny_cell.limits["limits"]
    out = control.readings(tiny_cell, 2_900_000_001, with_control=True,
                           detail=True)
    assert check.judge(out["sound"], limits), out["sound"]
    # float32 on the CPU: the program and the reference differ by
    # rounding alone, at any matmul precision
    assert out["sound"]["loss"] < 1e-5
    assert out["highest"]["change"] < 1e-3
    assert len(out["sound_detail"]["losses"]) == 4  # two blocks of 2
    # (COPT-alpha on half of this cell's sweeps still settles: the
    # harness test shortens it further)
    for case in ("control", "half_batch", "double_client", "unchanged",
                 "alpha_float32"):
        assert not check.judge(out[case], limits), (case, out[case])
    assert out["unchanged"]["grad"] == pytest.approx(1.0)


def _links():
    traffic = spec.load_json(spec.BENCH_DIR / "traffic/paper_chunk8.json")
    return alpha.link_model(traffic["links"])


def test_relay_weights_match_the_programs_settled_solve():
    from repro.core import optimize_weights, topology, variance_S

    p, P, E = links = _links()
    program = topology.paper_fig2b()
    for a, b in zip((p, P, E), (program.p, program.P, program.E)):
        np.testing.assert_array_equal(a, b)
    ours = alpha.copt_alpha(*links)
    assert alpha.unbiasedness_gap(p, P, ours) < 1e-14
    theirs = optimize_weights(program, sweeps=300, fine_tune_sweeps=300).A
    # the same variance by the program's formula, and the same optimum
    assert alpha.variance(p, P, E, theirs) == pytest.approx(variance_S(program, theirs),
                                                            rel=1e-14)
    assert alpha.variance(p, P, E, theirs) / alpha.variance(p, P, E, ours) - 1 < 1e-9
    np.testing.assert_allclose(theirs, ours, atol=1e-3 * np.max(ours))
    # the job's weights: the program's at the traffic's sweeps, to rounding
    sweeps = spec.load_json(spec.BENCH_DIR / "traffic/paper_chunk8.json")["copt_sweeps"]
    job = alpha.copt_alpha_job(*links, sweeps)
    program_job = optimize_weights(program, sweeps=sweeps, fine_tune_sweeps=sweeps).A
    np.testing.assert_allclose(job, program_job, rtol=0, atol=1e-9)
    assert alpha.unbiasedness_gap(p, P, job) < 1e-14
    # the relaxed bound lies above, and the start is feasible but far off
    assert alpha.variance(p, P, E, ours, relaxed=True) > alpha.variance(p, P, E, ours)
    start = alpha._initial(p, P)
    assert alpha.unbiasedness_gap(p, P, start) < 1e-14
    assert alpha.variance(p, P, E, start) > 2 * alpha.variance(p, P, E, ours)


def test_rounds_without_an_update_and_unmoved_leaves():
    links = _links()
    A = alpha.copt_alpha(*links)
    one = {"losses": [2.0, 1.0, 1.0], "delta_norms": [0.5, 0.0, 0.2],
           "momentum_first": {"a": np.ones(4), "b": np.ones(4)},
           "params0": {"a": np.zeros(4), "b": np.zeros(4)},
           "params": {"a": np.ones(4), "b": np.ones(4)},
           "A": A, "A_settled": A, "links": links}
    same = check.compare(one, one)
    assert same.pop("alpha_bias") < 1e-14
    assert all(v == 0.0 for v in same.values())
    # an update where the reference's round had none is infinitely off
    moved = dict(one, delta_norms=[0.5, 0.1, 0.2])
    assert check.compare(moved, one)["delta"] == float("inf")
    # a leaf left unmoved reads 1 on the worst-leaf measure
    stuck = dict(one, params={"a": np.ones(4), "b": np.zeros(4)})
    assert check.compare(stuck, one)["change"] == pytest.approx(1.0)
