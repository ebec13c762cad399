"""The chunked multi-round scan engine (DESIGN.md §9).

Five layers:
  1. round-level equivalence — ``make_scan_round_fn`` over K rounds is
     *bitwise identical* to K sequential ``make_round_fn`` calls for
     every registered strategy, including stateful ones (memory's replay
     buffer, quantized int8's threaded PRNG key), pinned against the
     frozen pre-refactor fixture ``tests/golden/round_golden.npz``;
  2. stream equivalence — the vectorized batch gather and the channel
     ``trace`` service produce the exact streams their per-round
     counterparts do, for any chunking of the consumption;
  3. trainer-level equivalence — ``FLTrainer.run(chunk=K)`` reproduces
     the per-round loop bitwise (loss/participation/weight-sum/uplink-
     bits trajectories and final params), including resumed runs, tail
     remainders, and adaptive re-optimization at chunk boundaries (with
     the misaligned-cadence fallback); a per-round call, which stacks
     each round's batches while the round before runs, matches
     single-round calls;
  4. the in-scan channel samplers — marginals match the process law and
     the sampled-tau scan variant runs end to end;
  5. the wire-format-aware uplink accounting and the production
     ``build_step(scan_rounds=K)`` lowering.
"""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import strategies
from repro.channel import (
    AdaptiveConfig,
    AdaptiveWeightSchedule,
    MarkovChannel,
    MobilityChannel,
    StaticChannel,
    channel_key,
    ge_scan_sampler,
    gilbert_elliott,
    static_scan_sampler,
)
from repro.core import fedavg_weights, optimize_weights, topology
from repro.core.connectivity import sample_round
from repro.data import quadratic_problem
from repro.data.pipeline import ClientDataset, stack_chunk_batches
from repro.fl import FLTrainer
from repro.fl.round import RoundConfig, make_round_fn, make_scan_round_fn
from repro.optim import sgd, sgd_momentum

_GG_PATH = pathlib.Path(__file__).parent / "golden" / "generate_golden.py"
_spec = importlib.util.spec_from_file_location("_golden_gen_scan", _GG_PATH)
gg = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gg)

GOLDEN = np.load(pathlib.Path(__file__).parent / "golden" / "round_golden.npz")


# ---------------------------------------------------------------------------
# harnesses
# ---------------------------------------------------------------------------


def _golden_inputs(mode: str, rounds: int):
    """The golden problem's tau/batch streams, stacked for a K-round scan
    (identical draws to gg.run_config's per-round loop)."""
    T = 1 if mode == "weighted_grad" else 2
    tau_rng = np.random.default_rng(77)
    bat_rng = np.random.default_rng(99)
    taus = [sample_round(gg.PROB[3], tau_rng) for _ in range(rounds)]
    bs = [gg.batches_for(bat_rng, T) for _ in range(rounds)]
    if mode == "weighted_grad":
        bs = [{k: v[:, 0] for k, v in b.items()} for b in bs]
    batches = {k: jnp.asarray(np.stack([b[k] for b in bs])) for k in bs[0]}
    tau_up = jnp.asarray(np.stack([t[0] for t in taus]), jnp.float32)
    tau_dd = jnp.asarray(np.stack([t[1] for t in taus]), jnp.float32)
    return batches, tau_up, tau_dd


def run_config_scan(strategy, mode, *, rounds=gg.ROUNDS, use_fused_kernel=False):
    """gg.run_config's experiment executed as ONE scan chunk of K rounds."""
    H, centers, Wc, model, A = gg.PROB
    T = 1 if mode == "weighted_grad" else 2
    rc_kwargs = dict(n_clients=gg.N, local_steps=T, mode=mode,
                     aggregation=strategy)
    if use_fused_kernel:
        rc_kwargs["use_fused_kernel"] = True
    rc = RoundConfig(**rc_kwargs)
    server_opt = sgd_momentum(1.0, beta=0.9)
    fn = jax.jit(make_scan_round_fn(gg.make_loss(H, Wc), sgd(0.05),
                                    server_opt, rc))
    params = {"x": jnp.zeros(gg.DX, jnp.float32),
              "W": jnp.zeros((3, 4), jnp.float32)}
    batches, tau_up, tau_dd = _golden_inputs(mode, rounds)
    params, _, agg_state, metrics = fn(
        params, server_opt.init(params),
        rc.resolve_strategy().init_state(gg.N, gg.DX + 12),
        batches, tau_up, tau_dd, jnp.asarray(A, jnp.float32))
    return params, metrics, agg_state


def _quadratic_trainer(*, channel=None, adaptive=None, strategy="colrel",
                       A=None, local_steps=4, seed=0):
    prob = quadratic_problem(10, 16, mu=1.0, L=8.0, hetero=1.0, seed=0)
    H = jnp.asarray(prob["H"], jnp.float32)
    model = topology.paper_fig2a()

    def loss_fn(params, batch):
        x = params["x"]
        d = x - batch["center"][0]
        return 0.5 * d @ (H @ d) + 0.1 * batch["noise"][0] @ x, {}

    clients = []
    for i in range(10):
        c = prob["centers"][i].astype(np.float32)
        pool = np.random.default_rng(100 + i).normal(size=(2048, 16)).astype(np.float32)
        clients.append(ClientDataset({"center": np.tile(c, (2048, 1)),
                                      "noise": pool}, batch_size=1, seed=7 + i))
    if A is None:
        A = optimize_weights(model, sweeps=10, fine_tune_sweeps=10).A
    return FLTrainer(loss_fn, {"x": jnp.zeros(16)}, model, A, clients,
                     sgd(0.02), sgd_momentum(1.0, beta=0.0),
                     local_steps=local_steps, strategy=strategy, seed=seed,
                     channel=channel, adaptive=adaptive)


def _assert_logs_bitwise(a, b):
    for field in ("rounds", "loss", "participation", "uplink_bits",
                  "weight_sums"):
        av, bv = getattr(a.log, field), getattr(b.log, field)
        # list equality is bitwise for floats (and treats NaN != NaN, so
        # compare NaN-bearing weight_sums positionally)
        assert len(av) == len(bv), field
        for x, y in zip(av, bv):
            assert x == y or (np.isnan(x) and np.isnan(y)), (field, x, y)
    np.testing.assert_array_equal(np.asarray(a.params["x"]),
                                  np.asarray(b.params["x"]))


# ---------------------------------------------------------------------------
# 1. round-level scan == loop, pinned against the golden fixture
# ---------------------------------------------------------------------------

GOLDEN_CONFIGS = [(s, m, False) for s in gg.STRATEGIES for m in gg.MODES]
GOLDEN_CONFIGS.append(("colrel", "per_client", True))


@pytest.mark.parametrize("strategy,mode,fused_kernel", GOLDEN_CONFIGS,
                         ids=[f"{s}-{m}{'-kernel' if k else ''}"
                              for s, m, k in GOLDEN_CONFIGS])
@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_scan_matches_golden_fixture(strategy, mode, fused_kernel):
    """One K-round scan reproduces the frozen pre-refactor trajectory
    bitwise — the same fixture the per-round loop is pinned against."""
    params, metrics, _ = run_config_scan(strategy, mode,
                                         use_fused_kernel=fused_kernel)
    tag = f"{strategy}|{mode}" + ("|kernel" if fused_kernel else "")
    np.testing.assert_array_equal(np.asarray(params["x"], np.float32),
                                  GOLDEN[f"{tag}|x"])
    np.testing.assert_array_equal(np.asarray(params["W"], np.float32),
                                  GOLDEN[f"{tag}|W"])
    # stacked (K,) metrics: the last round's weight_sum is the frozen one
    np.testing.assert_array_equal(
        np.float32(np.asarray(metrics["weight_sum"])[-1]),
        GOLDEN[f"{tag}|weight_sum"])


def test_scan_matches_golden_quantized_int8():
    """Stateful codec PRNG key threads through the scan carry: the pinned
    quantized-int8 trajectory replays bitwise."""
    params, _, (codec_state, _) = run_config_scan(
        gg.quantized_int8_strategy(), "per_client")
    np.testing.assert_array_equal(np.asarray(params["x"], np.float32),
                                  GOLDEN[f"{gg.QUANT_TAG}|x"])
    np.testing.assert_array_equal(np.asarray(params["W"], np.float32),
                                  GOLDEN[f"{gg.QUANT_TAG}|W"])
    # the key advanced (fresh quantization noise every scanned round)
    init_key = gg.quantized_int8_strategy().init_state(gg.N, gg.DX + 12)[0]
    assert not np.array_equal(np.asarray(codec_state), np.asarray(init_key))


@pytest.mark.parametrize("name,options", [
    ("colrel", {}),
    ("fedavg_perfect", {}),
    ("fedavg_blind", {}),
    ("fedavg_nonblind", {}),
    ("multihop", {"hops": 2}),
    ("memory", {}),
    ("quantized", {"codec": "int8"}),
])
def test_scan_bitwise_matches_sequential_rounds(name, options):
    """Every registered strategy: scanned K rounds == K sequential
    ``round_fn`` calls, bit for bit (params, metrics and carried state)."""
    strategy = strategies.get(name, **options)
    p_loop, m_loop = gg.run_config(strategy, "per_client")
    p_scan, m_scan, _ = run_config_scan(strategies.get(name, **options),
                                        "per_client")
    for a, b in zip(jax.tree.leaves(p_loop), jax.tree.leaves(p_scan)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in ("loss", "participation", "uplink_bits"):
        np.testing.assert_array_equal(np.float32(m_loop[k]),
                                      np.asarray(m_scan[k])[-1])


def test_every_registered_strategy_is_scan_covered():
    """Fail when a new strategy lands without scan-equivalence coverage."""
    covered = {"colrel", "fedavg_perfect", "fedavg_blind", "fedavg_nonblind",
               "multihop", "memory", "quantized",
               # clustered: C=1 scan trajectories pinned bitwise against
               # colrel's golden fixture in tests/test_clustered.py
               "clustered",
               # async_colrel: the async scan's chunked/no-trace/resume
               # trajectories are pinned for every mode by the conformance
               # matrix (tests/test_conformance.py), and zero-blockage
               # bitwise sync reduction by tests/test_property.py
               "async_colrel"}
    assert set(strategies.available()) <= covered


# ---------------------------------------------------------------------------
# 2. stream equivalence: batches and channel traces
# ---------------------------------------------------------------------------


def test_next_batches_stream_equivalent():
    mk = lambda: ClientDataset(
        {"a": np.arange(500, dtype=np.float32).reshape(100, 5)},
        batch_size=3, seed=11)
    c1, c2 = mk(), mk()
    bulk = c1.next_batches(6)["a"]
    seq = np.stack([c2.next_batch()["a"] for _ in range(6)])
    np.testing.assert_array_equal(bulk, seq)
    # and the stream continues identically after a bulk draw
    np.testing.assert_array_equal(c1.next_batch()["a"], c2.next_batch()["a"])


def test_stack_chunk_batches_layout_and_stream():
    clients = [ClientDataset({"a": np.arange(40, dtype=np.float32).reshape(20, 2)},
                             batch_size=2, seed=3 + i) for i in range(4)]
    chunk = stack_chunk_batches(clients, local_steps=3, rounds=5)
    assert chunk["a"].shape == (5, 4, 3, 2, 2)
    clients2 = [ClientDataset({"a": np.arange(40, dtype=np.float32).reshape(20, 2)},
                              batch_size=2, seed=3 + i) for i in range(4)]
    for r in range(5):
        per_round = stack_chunk_batches(clients2, local_steps=3, rounds=1)
        np.testing.assert_array_equal(chunk["a"][r], per_round["a"][0])


@pytest.mark.parametrize("make", [
    lambda m: StaticChannel(m, seed=5, block=16),
    lambda m: MarkovChannel(gilbert_elliott(m, memory=0.8), seed=5, block=16),
])
def test_trace_matches_per_round_service(make):
    m = topology.fully_connected(6, 0.6, p_c=0.5, rho=0.5)
    ch_a, ch_b = make(m), make(m)
    ups, dds = ch_a.trace(0, 40)  # spans multiple 16-round blocks
    assert np.asarray(ups).shape == (40, 6) and np.asarray(dds).shape == (40, 6, 6)
    for r in range(40):
        tu, td = ch_b.tau_for_round(r)
        np.testing.assert_array_equal(np.asarray(ups[r], np.float64), tu)
        np.testing.assert_array_equal(np.asarray(dds[r], np.float64), td)
    # interleaved consumption reads the same stream
    tu, td = ch_a.tau_for_round(40)
    np.testing.assert_array_equal(tu, ch_b.tau_for_round(40)[0])
    u2, _ = ch_a.trace(41, 5)
    for i in range(5):
        np.testing.assert_array_equal(np.asarray(u2[i], np.float64),
                                      ch_b.tau_for_round(41 + i)[0])
    with pytest.raises(ValueError, match="rewind"):
        ch_a.trace(0, 4)


def test_mobility_trace_matches_per_round_service():
    ch_a = MobilityChannel(8, area=250.0, speed=10.0, epoch=5, seed=0)
    ch_b = MobilityChannel(8, area=250.0, speed=10.0, epoch=5, seed=0)
    ups, dds = ch_a.trace(0, 12)
    assert ups.shape == (12, 8) and dds.shape == (12, 8, 8)
    for r in range(12):
        tu, td = ch_b.tau_for_round(r)
        np.testing.assert_array_equal(ups[r], tu)
        np.testing.assert_array_equal(dds[r], td)


# ---------------------------------------------------------------------------
# 3. trainer-level chunked == loop
# ---------------------------------------------------------------------------


def test_trainer_chunked_matches_loop_static():
    t1 = _quadratic_trainer()
    t1.run(14)
    t2 = _quadratic_trainer()
    t2.run(14, chunk=4)  # 3 full chunks + a 2-round per-round tail
    _assert_logs_bitwise(t1, t2)


def test_trainer_chunked_matches_loop_markov_and_resume():
    mk_ch = lambda: MarkovChannel(gilbert_elliott(topology.paper_fig2a(),
                                                  memory=0.8), seed=1, block=16)
    t1 = _quadratic_trainer(channel=mk_ch())
    t1.run(20)
    t2 = _quadratic_trainer(channel=mk_ch())
    t2.run(7)           # per-round prefix ...
    t2.run(13, chunk=5)  # ... resumed chunked: aligns at round 10
    _assert_logs_bitwise(t1, t2)


@pytest.mark.parametrize("adaptive", [False, True], ids=["markov", "adaptive"])
def test_per_round_call_prefetch_matches_single_round_calls(adaptive):
    """One ``run(R)`` call stacks each round's batches while the round
    before runs; R ``run(1)`` calls never prefetch.  Same trajectory to
    the bit, re-opts included."""
    R = 12
    mk = lambda: _quadratic_trainer(
        channel=MarkovChannel(gilbert_elliott(topology.paper_fig2a(),
                                              memory=0.8), seed=1, block=16),
        adaptive=AdaptiveWeightSchedule(10, AdaptiveConfig(
            every=5, warmup=3, sweeps=3, fine_tune_sweeps=3)) if adaptive else None,
        A=fedavg_weights(10) if adaptive else None, local_steps=2)
    t1 = mk()
    t1.run(R)
    t2 = mk()
    for _ in range(R):
        t2.run(1)
    _assert_logs_bitwise(t1, t2)
    assert t1.spans.counters["prefetched_rounds"] == R - 1
    assert t2.spans.counters.get("prefetched_rounds", 0) == 0
    assert t1.log.reopt_rounds == t2.log.reopt_rounds == ([4, 9] if adaptive else [])
    assert t1.log.S_est == t2.log.S_est
    np.testing.assert_array_equal(np.asarray(t1.A), np.asarray(t2.A))


def test_trainer_chunked_adaptive_matches_loop_at_boundaries():
    """Re-opt cadence a multiple of the chunk: estimator state, re-opt
    rounds and the refreshed alphas replay exactly."""
    mk = lambda: _quadratic_trainer(
        channel=MarkovChannel(gilbert_elliott(topology.paper_fig2a(),
                                              memory=0.8), seed=1, block=16),
        adaptive=AdaptiveWeightSchedule(10, AdaptiveConfig(
            every=10, warmup=5, sweeps=3, fine_tune_sweeps=3)),
        A=fedavg_weights(10), local_steps=2)
    t1 = mk()
    t1.run(30)
    t2 = mk()
    t2.run(30, chunk=5)
    _assert_logs_bitwise(t1, t2)
    assert t2.log.reopt_rounds == [9, 19, 29]
    assert t1.log.S_est == t2.log.S_est
    np.testing.assert_array_equal(np.asarray(t1.A), np.asarray(t2.A))


def test_trainer_misaligned_chunk_falls_back_to_per_round():
    adaptive = AdaptiveWeightSchedule(10, AdaptiveConfig(every=10, warmup=5))
    t = _quadratic_trainer(adaptive=adaptive, A=fedavg_weights(10))
    assert t._effective_chunk(7, 0) == 1   # 10 % 7 != 0
    assert t._effective_chunk(5, 0) == 5
    assert t._effective_chunk(5, 8) == 1   # eval cadence misaligned
    assert t._effective_chunk(5, 10) == 5


def test_trainer_chunked_eval_at_boundaries():
    t = _quadratic_trainer()
    t.eval_fn = lambda p: {"d": float(jnp.sum(p["x"] ** 2))}
    t.run(12, chunk=4, eval_every=4)
    assert t.log.eval_rounds == [3, 7, 11]
    t2 = _quadratic_trainer()
    t2.eval_fn = t.eval_fn
    t2.run(12, eval_every=4)
    assert t.log.eval_metrics == t2.log.eval_metrics


# ---------------------------------------------------------------------------
# 4. in-scan channel samplers
# ---------------------------------------------------------------------------


def _scan_sample(init_fn, sample_fn, rounds, seed=0):
    key = channel_key(seed)
    key, k_init = jax.random.split(key)
    state = init_fn(k_init)

    def body(carry, _):
        st, k = carry
        k, sub = jax.random.split(k)
        tu, td, st = sample_fn(st, sub)
        return (st, k), (tu, td)

    (_, _), (ups, dds) = jax.lax.scan(body, (state, key), None, length=rounds)
    return np.asarray(ups), np.asarray(dds)


def test_ge_scan_sampler_matches_marginals():
    m = topology.fully_connected(8, 0.6, p_c=0.5, rho=0.5)
    params = gilbert_elliott(m, memory=0.8)
    ups, dds = _scan_sample(*ge_scan_sampler(params), rounds=4000)
    ess = (1 - 0.8) / (1 + 0.8)
    sd_up = np.sqrt(0.25 / (4000 * ess * 8))
    assert abs(ups.mean() - m.p.mean()) < 6 * sd_up
    off = ~np.eye(8, dtype=bool)
    sd_dd = np.sqrt(0.25 / (4000 * ess * 28))
    assert abs(dds.mean(0)[off].mean() - m.P[off].mean()) < 6 * sd_dd
    np.testing.assert_array_equal(dds[:, np.arange(8), np.arange(8)], 1.0)


def test_static_scan_sampler_matches_marginals():
    m = topology.fully_connected(8, 0.6, p_c=0.5, rho=0.5)
    ups, dds = _scan_sample(*static_scan_sampler(m), rounds=2000)
    assert abs(ups.mean() - m.p.mean()) < 6 * np.sqrt(0.25 / (2000 * 8))
    off = ~np.eye(8, dtype=bool)
    assert abs(dds.mean(0)[off].mean() - m.P[off].mean()) < 6 * np.sqrt(0.25 / (2000 * 28))
    # reciprocity joint survives the in-scan coupling
    joint = (dds * np.swapaxes(dds, 1, 2)).mean(0)[off].mean()
    assert abs(joint - m.E[off].mean()) < 6 * np.sqrt(0.25 / (2000 * 28))


def test_scan_round_fn_with_in_scan_sampler_runs():
    """The sampled-tau variant: carry = (params, server_state, agg_state,
    channel_state, rng); taus never materialize outside the program."""
    H, centers, Wc, model, A = gg.PROB
    params_ge = gilbert_elliott(model, memory=0.8)
    init_fn, sample_fn = ge_scan_sampler(params_ge)
    rc = RoundConfig(n_clients=gg.N, local_steps=2, mode="per_client",
                     aggregation="colrel")
    server_opt = sgd_momentum(1.0, beta=0.9)
    fn = jax.jit(make_scan_round_fn(gg.make_loss(H, Wc), sgd(0.05), server_opt,
                                    rc, channel_sampler=sample_fn))
    K = 8
    bat_rng = np.random.default_rng(5)
    bs = [gg.batches_for(bat_rng, 2) for _ in range(K)]
    batches = {k: jnp.asarray(np.stack([b[k] for b in bs])) for k in bs[0]}
    params = {"x": jnp.zeros(gg.DX, jnp.float32),
              "W": jnp.zeros((3, 4), jnp.float32)}
    key = channel_key(3)
    key, k_init = jax.random.split(key)
    state = init_fn(k_init)
    p2, _, _, state2, key2, metrics = fn(
        params, server_opt.init(params), (), batches, state, key,
        jnp.asarray(A, jnp.float32))
    assert np.isfinite(np.asarray(metrics["loss"])).all()
    assert np.asarray(metrics["participation"]).shape == (K,)
    assert not np.array_equal(np.asarray(jax.random.key_data(key2)),
                              np.asarray(jax.random.key_data(key)))
    assert np.asarray(state2).shape == np.asarray(state).shape
    # rerunning from the returned state continues the chain (shape-stable
    # carry: no retrace needed)
    fn(p2, server_opt.init(p2), (), batches, state2, key2,
       jnp.asarray(A, jnp.float32))


# ---------------------------------------------------------------------------
# 5. uplink accounting + production lowering
# ---------------------------------------------------------------------------


def test_uplink_bits_metric_uncoded_and_quantized():
    d = gg.DX + 12
    _, m_col, _ = run_config_scan(strategies.get("colrel"), "per_client")
    part = np.asarray(m_col["participation"])
    np.testing.assert_allclose(np.asarray(m_col["uplink_bits"]),
                               part * d * 32.0, rtol=1e-6)
    quant = strategies.get("quantized", codec="int8", codec_options={"bits": 4})
    assert quant.wire_bits_per_coord(d) == pytest.approx(4 + 32.0 / d)
    _, m_q, _ = run_config_scan(
        strategies.get("quantized", codec="int8", codec_options={"bits": 4}),
        "per_client")
    np.testing.assert_allclose(np.asarray(m_q["uplink_bits"]),
                               np.asarray(m_q["participation"]) * d * (4 + 32.0 / d),
                               rtol=1e-6)


def test_trainer_logs_uplink_bits_both_paths():
    t = _quadratic_trainer()
    t.run(6, chunk=3)
    assert len(t.log.uplink_bits) == 6
    want = np.asarray(t.log.participation) * 16 * 32.0
    np.testing.assert_allclose(np.asarray(t.log.uplink_bits), want, rtol=1e-6)


def test_build_scan_step_lowers():
    from repro.configs.base import get_arch
    from repro.launch.steps import build_step

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = get_arch("qwen3-0.6b").smoke()
    step, lower_args, in_sh, out_sh = build_step(
        "qwen3-0.6b", "train_4k", mesh, scan_rounds=2, cfg_override=cfg)
    K = 2
    assert all(v.shape[0] == K for v in lower_args[3].values())
    assert lower_args[4].shape[0] == K and lower_args[5].shape[:1] == (K,)
    with mesh:
        jax.jit(step, in_shardings=in_sh, out_shardings=out_sh).lower(*lower_args)
