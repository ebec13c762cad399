"""The names of the program's own work (``repro.telemetry.spans``).

1. device scopes — the ``op_name`` parser, and the scopes it finds in a
   CPU-compiled tiny round of every mode, in the chunk program, and in
   the sampled scan; backward ops belong to ``fl.local_sgd``;
2. host spans — the recorder's totals and nesting, the throughput
   meter's block span, the trainer's spans in a CPU profiler trace, the
   child seconds of the ``timing`` event, the ``h2d_bytes`` and
   ``prefetched_rounds`` counters, and where the per-round loop stacks
   the next round's batches.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channel import StaticChannel
from repro.core import optimize_weights, topology
from repro.data.pipeline import ClientDataset
from repro.fl import FLTrainer
from repro.fl.round import RoundConfig, make_round_fn, make_scan_round_fn
from repro.optim import sgd, sgd_momentum
from repro.strategies import ColRelStrategy
from repro.telemetry import MemorySink, MetricsLogger, Spans, ThroughputMeter, op_scopes
from repro.telemetry import spans as names

N, D, H, C, B, T = 4, 6, 5, 3, 2, 2
S = jax.ShapeDtypeStruct
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = .*op_name=\"([^\"]*)\"")


def _loss_fn(p, batch):
    h = jnp.tanh(batch["x"] @ p["w1"] + p["b"])
    logp = jax.nn.log_softmax(h @ p["w2"])
    ce = -jnp.take_along_axis(logp, batch["y"][:, None], 1)[:, 0]
    w = batch.get("ce_weight")
    return (jnp.mean(ce) if w is None else jnp.sum(w * ce)), {}


def _params():
    return {"w1": jnp.full((D, H), 0.1), "b": jnp.zeros(H),
            "w2": jnp.full((H, C), 0.1)}


def _compiled_text(fn, lead, *extra):
    params = _params()
    server = sgd_momentum(1.0, beta=0.9).init(params)
    batches = {"x": S((*lead, D), jnp.float32), "y": S(lead, jnp.int32)}
    return jax.jit(fn).lower(
        params, server, (), batches, *extra).compile().as_text()


# ---------------------------------------------------------------------------
# 1. device scopes
# ---------------------------------------------------------------------------


def test_scope_of_takes_the_innermost_scope_through_transforms():
    assert names.scope_of(
        "jit(f)/while/body/transpose(jvp(fl.local_sgd))/dot_general") == names.LOCAL_SGD
    assert names.scope_of("jit(f)/fl.aggregate/fl.flatten/concatenate") == names.FLATTEN
    assert names.scope_of("jit(f)/fl.aggregate/jit(k)/k/pallas_call") == names.AGGREGATE
    assert names.scope_of("jit(f)/fl.unknown/add") == names.UNSCOPED
    assert names.scope_of("jit(f)/while") == names.UNSCOPED
    assert names.scope_of(None) == names.UNSCOPED


def test_op_scopes_parses_instruction_lines():
    text = "\n".join([
        "HloModule m",
        "ENTRY %main {",
        '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%c, '
        'metadata={op_type="add" op_name="jit(f)/fl.server_step/add" source_line=3}',
        '  ROOT %tuple.1 = (f32[4]{0}) tuple(%fusion.3)',
        "}",
    ])
    assert op_scopes(text) == {"fusion.3": names.SERVER_STEP,
                               "tuple.1": names.UNSCOPED}


# mode -> (batch leading axes, strategy's fused option, local steps)
MODES = {
    "per_client_kernel": ("per_client", (N, T, B), "kernel", T),
    "per_client_faithful": ("per_client", (N, T, B), False, T),
    "client_sequential": ("client_sequential", (N, T, B), "collapse", T),
    "weighted_grad": ("weighted_grad", (N, B), "collapse", 1),
    "weighted_flat": ("weighted_flat", (N * B,), "collapse", 1),
}


@pytest.mark.parametrize("case", list(MODES))
def test_round_scopes_in_each_mode(case):
    mode, lead, fused, steps = MODES[case]
    rc = RoundConfig(n_clients=N, local_steps=steps, mode=mode,
                     aggregation=ColRelStrategy(fused=fused))
    fn = make_round_fn(_loss_fn, sgd(0.1), sgd_momentum(1.0, beta=0.9), rc,
                       telemetry=True)
    text = _compiled_text(fn, lead, S((N,), jnp.float32), S((N, N), jnp.float32),
                          S((N, N), jnp.float32), S((N,), jnp.int32))
    found = set(op_scopes(text).values())
    want = {names.LOCAL_SGD, names.AGGREGATE, names.SERVER_STEP,
            names.ROUND_METRICS, names.TELEMETRY}
    if fused == "kernel":
        want.add(names.FLATTEN)
    assert want <= found, want - found
    # every backward op of the round is local training (ops named from the
    # program's root; a called reducer's ops carry a relative name)
    backward = [m.group(2) for m in map(_INSTRUCTION.match, text.splitlines())
                if m and m.group(2).startswith("jit(") and "transpose(" in m.group(2)]
    assert backward
    assert {names.scope_of(op) for op in backward} == {names.LOCAL_SGD}


def _clients(n=N, size=64):
    rng = np.random.default_rng(0)
    return [ClientDataset({"x": rng.normal(size=(size, D)).astype(np.float32),
                           "y": rng.integers(0, C, size).astype(np.int32)},
                          batch_size=B, seed=i) for i in range(n)]


def _trainer(metrics=None, telemetry=True):
    model = topology.paper_fig2a()
    A = optimize_weights(model, sweeps=5, fine_tune_sweeps=5).A
    return FLTrainer(_loss_fn, _params(), model, A, _clients(model.n), sgd(0.1),
                     sgd_momentum(1.0, beta=0.9), local_steps=T,
                     strategy=ColRelStrategy(fused="kernel"), seed=0,
                     telemetry=telemetry, metrics=metrics)


@pytest.mark.parametrize("k", [1, 2], ids=["round", "chunk"])
def test_trainer_op_scopes_cover_the_executed_program(k):
    tr = _trainer()
    scopes = tr.op_scopes(k)
    assert {names.LOCAL_SGD, names.AGGREGATE, names.FLATTEN, names.SERVER_STEP,
            names.ROUND_METRICS, names.TELEMETRY} <= set(scopes.values())
    # local SGD's loops are its own; the chunk program's loop over rounds
    # is under no scope
    loops = {s for op, s in scopes.items() if op.startswith("while")}
    assert loops == ({names.LOCAL_SGD, names.UNSCOPED} if k > 1 else {names.LOCAL_SGD})


def test_sampled_scan_names_the_channel_sampler():
    model = topology.paper_fig2a()
    n = model.n
    rc = RoundConfig(n_clients=n, local_steps=T,
                     aggregation=ColRelStrategy(fused="collapse"))
    init_fn, sample_fn = StaticChannel(model, seed=0).scan_sampler()
    fn = make_scan_round_fn(_loss_fn, sgd(0.1), sgd_momentum(1.0, beta=0.9), rc,
                            channel_sampler=sample_fn)
    params = _params()
    lead = (2, n, T, B)
    batches = {"x": S((*lead, D), jnp.float32), "y": S(lead, jnp.int32)}
    text = jax.jit(fn).lower(
        params, sgd_momentum(1.0, beta=0.9).init(params), (), batches,
        init_fn(jax.random.PRNGKey(1)), jax.random.PRNGKey(2),
        S((n, n), jnp.float32)).compile().as_text()
    assert names.CHANNEL_SAMPLE in set(op_scopes(text).values())


# ---------------------------------------------------------------------------
# 2. host spans
# ---------------------------------------------------------------------------


def test_span_recorder_totals_and_nesting():
    spans = Spans()
    with spans.span("outer", round=3) as outer:
        with spans.span("inner"):
            pass
        with spans.span("inner"):
            with spans.span("leaf"):
                pass
    assert spans.counts == {"outer": 1, "inner": 2, "leaf": 1}
    # direct children only, each closed span's seconds added once
    assert set(outer.children) == {"inner"}
    assert outer.children["inner"] == pytest.approx(spans.seconds["inner"])
    assert spans.seconds["outer"] == outer.seconds >= spans.seconds["inner"]
    assert spans.seconds["inner"] >= spans.seconds["leaf"] > 0
    a = spans.open("a")
    b = spans.open("b")
    with pytest.raises(RuntimeError):
        spans.close(a)  # spans close innermost first
    spans.close(b)
    spans.close(a)
    spans.count(names.H2D_BYTES, 10)
    spans.count(names.H2D_BYTES, 5)
    snap = spans.snapshot()
    assert snap["counters"] == {names.H2D_BYTES: 15}
    spans.count(names.H2D_BYTES, 1)
    assert snap["counters"][names.H2D_BYTES] == 15  # a copy


def test_throughput_meter_takes_seconds_from_the_block_span():
    meter = ThroughputMeter()
    meter.start()
    dt = meter.stop(2, fence=jnp.ones(8) * 2)
    assert meter.spans.counts == {names.BLOCK: 1, names.FENCE: 1}
    assert dt == meter.spans.seconds[names.BLOCK] == meter.chunks[0]["seconds"]
    assert meter.record(4, 2.0) == 2.0
    assert meter.total_rounds == 6 and meter.chunks[-1]["rounds_per_sec"] == 2.0


BLOCK_CHILDREN = {names.CHANNEL_TRACE, names.H2D, names.DISPATCH, names.FENCE,
                  names.LOG_ROUNDS}


@pytest.mark.parametrize("k", [1, 2], ids=["per_round", "chunk"])
def test_timing_event_carries_the_block_child_seconds(k):
    sink = MemorySink()
    tr = _trainer(metrics=MetricsLogger([sink]))
    tr.run(4, chunk=k)
    timing = sink.of_kind("timing")
    assert len(timing) == 4 // k == tr.spans.counts[names.BLOCK]
    for i, e in enumerate(timing):
        stacked = i + 1 < len(timing)  # the call's last block prefetches nothing
        assert set(e["spans"]) == BLOCK_CHILDREN | ({names.STACK_BATCHES} if stacked else set())
        assert 0 < sum(e["spans"].values()) <= e["seconds"]
    assert [c["seconds"] for c in tr.meter.chunks] == [e["seconds"] for e in timing]
    assert set(tr.spans.seconds) <= set(names.SPANS)
    assert set(tr.spans.counters) <= set(names.COUNTERS)
    # the call stacks its first block before the first fl.block
    assert tr.spans.counts[names.STACK_BATCHES] == 4 // k
    assert tr.spans.counters[names.PREFETCHED_ROUNDS] == 4 - k
    # bytes put on the device: each round's batches, tau_up and tau_dd
    n = tr.rc.n_clients
    per_round = n * T * B * (D * 4 + 4) + 4 * (n + n * n)
    assert tr.spans.counters[names.H2D_BYTES] == 4 * per_round


def test_per_round_prefetch_nests_in_the_block_before(monkeypatch):
    """Round r+1's batches are stacked inside round r's ``fl.block``,
    after its dispatch and before its fence; the call's first round
    stacks before its block and its last prefetches nothing."""
    tr = _trainer(telemetry=False)
    closed = []
    close = tr.spans.close

    def spy(span):
        parent = tr.spans._open[-2].name if len(tr.spans._open) > 1 else None
        closed.append((span.name, parent))
        return close(span)

    monkeypatch.setattr(tr.spans, "close", spy)
    tr.run(3)
    blk = names.BLOCK
    steps = [(names.CHANNEL_TRACE, blk), (names.H2D, blk), (names.DISPATCH, blk)]
    tail = [(names.FENCE, blk), (names.LOG_ROUNDS, blk), (blk, None)]
    prefetch = [(names.STACK_BATCHES, blk)]
    assert closed == ([(names.STACK_BATCHES, None)]
                      + 2 * (steps + prefetch + tail) + steps + tail)
    assert tr.spans.counters[names.PREFETCHED_ROUNDS] == 2
    assert names.COUNTERS.count(names.PREFETCHED_ROUNDS) == 1


def test_cpu_profiler_trace_holds_the_host_spans(tmp_path):
    from jax.profiler import ProfileData

    tr = _trainer(telemetry=False)
    tr.run(2, chunk=2)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        tr.run(4, chunk=2)
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = ProfileData.from_file(str(path)).find_plane_with_name("/host:CPU")
    events = [e.name for line in host.lines for e in line.events]
    for name in (names.BLOCK, names.STACK_BATCHES, names.H2D, names.DISPATCH,
                 names.FENCE):
        assert name in events, name
    assert events.count(names.BLOCK) == 2
