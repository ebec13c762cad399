"""Host-side FL training driver: samples connectivity, streams per-client
batches, invokes the compiled round function, tracks metrics, evaluates.

This is the entry point the paper-reproduction experiments, the examples
and ``chip_smoke.py`` use, on the CPU and on one TPU chip; the dry-run
(``repro/launch/steps.py``) wraps the same round function in jit with
mesh shardings.  Prefer building it declaratively through
:func:`repro.fl.experiment.build_experiment` — the constructor below is
the assembled form.

Connectivity comes from a :class:`~repro.channel.ChannelProcess` — the
paper's i.i.d. model (the default, built from ``link_model``), bursty
Gilbert–Elliott chains, or waypoint mobility.  With an
:class:`~repro.channel.AdaptiveWeightSchedule` attached, the trainer no
longer assumes oracle link knowledge: it estimates ``(p, P, E)`` online
from the realized taus and re-runs COPT-alpha every K rounds, swapping
the fresh alpha into the (traced, so recompile-free) ``A`` argument of
the compiled round.

Aggregation is a pluggable :class:`~repro.strategies.AggregationStrategy`
(``strategy=`` accepts a registry name or an instance); stateful
strategies' carried state (e.g. the memory strategy's replay buffer)
lives on the trainer and threads through the compiled round.

**Chunked execution** (DESIGN.md §9): ``run(rounds, chunk=K)`` drives
the multi-round scan engine — K rounds compiled into one device program
(:func:`~repro.fl.round.make_scan_round_fn`), connectivity served as a
bulk ``channel.trace`` per chunk, batches pre-stacked in one vectorized
gather, and per-round metrics bulk-appended from the stacked ``(K,)``
outputs.  The per-round and the chunk loop are one block loop (a block
is a round or a chunk): between a block's dispatch and its fence the
host stacks the next block's batches, while the device runs, unless the
block is the last of its ``run`` call or the next round starts an
aligned chunk (which stacks its own).  The trajectory is
bitwise-identical to the per-round loop: both consume the same
channel/batch streams and the scan body *is* the loop's round function.
Adaptive re-optimization and eval stay correct by construction — the
chunk size must divide their cadences (and re-opts then land exactly on
chunk boundaries); otherwise the trainer falls back to the per-round
loop.

**Telemetry** (DESIGN.md §11): every metric stream — both execution
paths — routes through one :class:`~repro.telemetry.MetricsLogger`
append path; :class:`TrainLog` remains attached as the bitwise-compatible
facade (``trainer.log is trainer.metrics.log``).  ``telemetry=True``
additionally compiles the instrumented round (per-client participation /
bits-on-air vectors, a device-resident outage-streak carry, unbiasedness
drift), and ``profile=``/``run(log_every=)`` expose the opt-in profiler
window and throughput readout.  All of it is off by default and the
default path's TrainLog streams are unchanged to the bit.  Host spans
are always on: each block is an ``fl.block`` span whose children time
the host loop's steps (``trainer.spans`` keeps the totals; the names are
listed in :mod:`repro.telemetry.spans`), and :meth:`FLTrainer.op_scopes`
names the compiled program's instructions by device scope.
"""

from __future__ import annotations

import dataclasses
import pathlib
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import strategies as strategy_registry
from repro.channel.base import ChannelProcess, StaticChannel
from repro.channel.schedule import AdaptiveWeightSchedule
from repro.core import LinkModel, variance_S
from repro.core.flatten import flat_spec
from repro.data.pipeline import ClientDataset, stack_chunk_batches
from repro.fl.round import (
    RoundConfig,
    make_async_round_fn,
    make_async_scan_round_fn,
    make_round_fn,
    make_scan_round_fn,
)
from repro.optim import Optimizer
from repro.telemetry import (
    CompileTracker,
    MetricsLogger,
    ProfileWindow,
    Spans,
    ThroughputMeter,
    init_streak,
    op_scopes,
)
from repro.telemetry import spans as names

Params = Any


@dataclasses.dataclass
class TrainLog:
    rounds: List[int] = dataclasses.field(default_factory=list)
    loss: List[float] = dataclasses.field(default_factory=list)
    eval_rounds: List[int] = dataclasses.field(default_factory=list)
    eval_metrics: List[Dict[str, float]] = dataclasses.field(default_factory=list)
    participation: List[float] = dataclasses.field(default_factory=list)
    # wire-format-aware uplink accounting: bits-on-air delivered to the PS
    # that round (participation x flat-dim x the active codec's
    # bits-per-coordinate) — np.cumsum(log.uplink_bits) is the x-axis of a
    # loss-vs-bytes curve
    uplink_bits: List[float] = dataclasses.field(default_factory=list)
    # realized sum of scalar aggregation weights (E = 1 when unbiased);
    # its dispersion is the realized counterpart of the variance proxy S.
    # NaN for strategies with no scalar collapse (e.g. memory).
    weight_sums: List[float] = dataclasses.field(default_factory=list)
    # adaptive re-optimization events (empty without a schedule)
    reopt_rounds: List[int] = dataclasses.field(default_factory=list)
    est_p_err: List[float] = dataclasses.field(default_factory=list)
    S_est: List[float] = dataclasses.field(default_factory=list)
    S_true: List[float] = dataclasses.field(default_factory=list)

    def to_dict(self):
        return dataclasses.asdict(self)


class FLTrainer:
    """Orchestrates pluggable-strategy FL training over an intermittent
    network (ColRel, FedAvg baselines, multihop, memory, ...)."""

    def __init__(
        self,
        loss_fn: Callable,
        init_params: Params,
        link_model: Optional[LinkModel],
        A: np.ndarray,
        clients: Sequence[ClientDataset],
        client_opt: Optimizer,
        server_opt: Optimizer,
        *,
        local_steps: int = 8,
        strategy: "str | strategy_registry.AggregationStrategy | None" = None,
        aggregation: "str | strategy_registry.AggregationStrategy | None" = None,
        mode: str = "per_client",
        use_fused_kernel: bool = False,
        seed: int = 0,
        eval_fn: Optional[Callable[[Params], Dict[str, float]]] = None,
        channel: Optional[ChannelProcess] = None,
        adaptive: Optional[AdaptiveWeightSchedule] = None,
        telemetry: bool = False,
        metrics: Optional[MetricsLogger] = None,
        profile: Optional[ProfileWindow] = None,
        async_options: Optional[Dict[str, Any]] = None,
        donate: bool = True,
        segment_d: int = 0,
    ):
        if strategy is not None and aggregation is not None:
            raise ValueError("pass strategy= or aggregation=, not both")
        spec = strategy if strategy is not None else (
            aggregation if aggregation is not None else "colrel")
        self.strategy = strategy_registry.resolve(
            spec, fused_kernel=use_fused_kernel)
        # async execution mode (DESIGN.md §13): wrap the configured
        # strategy in the staleness-weighted opportunistic-relaying
        # carrier and run it through the per_client engine — the async
        # state (age vector + staging buffer) rides ``agg_state``, so
        # every execution path below works unchanged.
        if mode == "async":
            if getattr(self.strategy, "is_async", False):
                if async_options:
                    raise ValueError(
                        "strategy is already async; pass gamma/opportunistic "
                        "through the strategy spec, not async_options")
            else:
                self.strategy = strategy_registry.AsyncRelayStrategy(
                    inner=self.strategy, **dict(async_options or {}))
            mode = "per_client"
        elif async_options:
            raise ValueError("async_options requires mode='async'")
        # an async strategy — whether wrapped above or registered directly
        # (strategy="async_colrel") — runs through the age-carrying builders
        self.async_mode = getattr(self.strategy, "is_async", False)
        if channel is None:
            if link_model is None:
                raise ValueError("provide link_model or channel")
            channel = StaticChannel(link_model, seed=seed)
        self.channel = channel
        self.adaptive = adaptive
        if adaptive is not None and not self.strategy.needs_A:
            raise ValueError(
                f"adaptive alpha re-optimization only affects strategies "
                f"that read A; {self.strategy.name!r} ignores it"
            )
        if adaptive is not None and self.strategy.calibration_tracks_A:
            raise ValueError(
                f"strategy {self.strategy.name!r} was calibrated against a "
                "fixed alpha; the adaptive schedule swaps alpha mid-run, "
                "which would silently stale the calibration — run it "
                "uncalibrated or without adaptive"
            )
        n = channel.n
        if link_model is not None and link_model.n != n:
            raise ValueError(f"link_model.n={link_model.n} != channel.n={n}")
        assert len(clients) == n, (len(clients), n)
        self.link_model = link_model if link_model is not None else channel.model_for_round(0)
        self.A = jnp.asarray(A, jnp.float32)
        self.clients = list(clients)
        # Buffer donation (DESIGN.md §14): the compiled round/scan carry
        # (params, server_state, agg_state, plus the sampled-scan channel
        # state / rng and the telemetry streak) is donated back into each
        # call, so XLA aliases the outputs onto the input buffers instead
        # of allocating a second copy of every carry array.  The caller's
        # init_params must then be defensively copied — donation would
        # delete the caller's own buffers on the first round.
        self.donate = bool(donate)
        if self.donate:
            init_params = jax.tree.map(jnp.array, init_params)
        self.params = init_params
        self.eval_fn = eval_fn
        rc = RoundConfig(
            n_clients=n, local_steps=local_steps, mode=mode,
            aggregation=self.strategy, segment_d=int(segment_d),
        )
        self.rc = rc
        self._loss_fn = loss_fn
        self._client_opt = client_opt
        self.server_opt = server_opt
        self.server_state = server_opt.init(init_params)
        self.agg_state = self.strategy.init_state(n, flat_spec(init_params).d)
        # telemetry (DESIGN.md §11): `telemetry=True` switches the
        # compiled round/scan to the instrumented signature (outage-streak
        # carry + (n,)-vector metrics); `metrics` is the host-side logger
        # every stream routes through (a bare facade-only one otherwise).
        self.telemetry = bool(telemetry)
        self.metrics = metrics if metrics is not None else MetricsLogger()
        self.profile = profile
        self.spans = Spans()
        self.meter = ThroughputMeter(self.spans)
        self.compiles = CompileTracker()
        self._warm_fns: set = set()
        self._streak = init_streak(n) if self.telemetry else None
        self._log_every = 0
        self._last_tlog = 0
        make_fn = make_async_round_fn if self.async_mode else make_round_fn
        self._make_scan_fn = (make_async_scan_round_fn if self.async_mode
                              else make_scan_round_fn)
        # donated argnums per signature: the carry slots only — never
        # batches (host-built each call), taus, or A (reused across calls)
        self._donate_round = ()
        self._donate_sampled = ()
        if self.donate:
            streak = (7,) if self.telemetry else ()
            self._donate_round = (0, 1, 2) + streak
            self._donate_sampled = (0, 1, 2, 4, 5) + streak
        self._round_fn = jax.jit(make_fn(
            loss_fn, client_opt, server_opt, rc, telemetry=self.telemetry),
            donate_argnums=self._donate_round)
        self.compiles.register("round_fn", self._round_fn)
        self._scan_fn = None  # built on first chunked run
        self._seed = seed
        # no-trace mode: in-scan sampler fn + carried (channel_state, rng)
        self._sampled_scan_fn = None
        self._sampled_init_fn = None
        self._channel_state = None
        self._channel_rng = None
        # checkpoint/resume (DESIGN.md §12): the authoritative round
        # counter, the client-RNG snapshot at the consumed-round boundary
        # (the block loop prefetches past it), and the per-run async
        # checkpointer wiring set up by `run`.
        self.round = 0
        self._data_rng_snapshot: Optional[List[str]] = None
        self._ckpt = None
        self._ckpt_every = 0
        self._ckpt_last = -1
        self.log = self.metrics.log

    # ------------------------------------------------------------------
    def _stack_batches(self, rounds: int = 1) -> Dict[str, np.ndarray]:
        """Stacked local-step batches: ``(n, T, B, ...)`` for ``rounds=1``
        (the per-round loop) or ``(rounds, n, T, B, ...)`` for a chunk —
        one vectorized gather per client, same RNG stream either way."""
        out = stack_chunk_batches(self.clients, self.rc.local_steps, rounds)
        if rounds == 1:
            out = {k: v[0] for k, v in out.items()}
            if self.rc.mode == "weighted_grad":
                out = {k: v[:, 0] for k, v in out.items()}  # T==1 collapse
        elif self.rc.mode == "weighted_grad":
            out = {k: v[:, :, 0] for k, v in out.items()}
        return out

    # -- checkpoint/resume (DESIGN.md §12) -----------------------------
    def _live_rng_states(self) -> List[str]:
        from repro.ckpt.schema import rng_state_to_json
        return [rng_state_to_json(c._rng) for c in self.clients]

    def _client_rng_states(self) -> List[str]:
        """Per-client data-RNG states at the consumed-round boundary.

        The block loop prefetches the next block's batches *before* the
        checkpoint point, so the live generators then sit one block
        ahead of the boundary; ``_run_blocks`` snapshots the boundary
        states pre-prefetch and this prefers that snapshot."""
        if self._data_rng_snapshot is not None:
            return list(self._data_rng_snapshot)
        return self._live_rng_states()

    def save_checkpoint(self, path) -> pathlib.Path:
        """Synchronously write the complete run state to one file."""
        from repro.ckpt.schema import capture_run_state
        from repro.ckpt.writer import write_state
        return write_state(path, capture_run_state(self))

    def restore(self, source) -> int:
        """Restore from a checkpoint file or directory (latest step).

        The trainer must be assembled with the same configuration as the
        checkpointed run; returns the restored round counter."""
        from repro.ckpt.schema import restore_run_state
        from repro.ckpt.writer import CheckpointWriter, read_state
        p = pathlib.Path(source)
        state = CheckpointWriter(p).load() if p.is_dir() else read_state(p)
        restore_run_state(self, state)
        return self.round

    def _maybe_ckpt(self) -> None:
        """Periodic async save at a round/chunk boundary."""
        if self._ckpt is None or self._ckpt_every <= 0:
            return
        if self.round % self._ckpt_every == 0 and self.round != self._ckpt_last:
            from repro.ckpt.schema import capture_run_state
            with self.spans.span(names.CKPT):
                self._ckpt.save(self.round, capture_run_state(self))
            self._ckpt_last = self.round

    def _finish_ckpt(self) -> None:
        """End-of-run: commit a final checkpoint, drain, shut down."""
        if self._ckpt is None:
            return
        try:
            with self.spans.span(names.CKPT):
                if self.round != self._ckpt_last:
                    from repro.ckpt.schema import capture_run_state
                    self._ckpt.save(self.round, capture_run_state(self))
                    self._ckpt_last = self.round
                self._ckpt.wait()
        finally:
            self._ckpt.close()
            self._ckpt = None

    # ------------------------------------------------------------------
    def _ingest_adaptive(self, r: int, tau_up: np.ndarray, tau_dd: np.ndarray,
                         verbose: bool) -> bool:
        """Feed one round's realization to the adaptive schedule; swap in
        the fresh alpha (and log the event) on re-opt rounds."""
        A_new = self.adaptive.step(r, tau_up, tau_dd)
        if A_new is None:
            return False
        self.A = jnp.asarray(A_new, jnp.float32)
        true_m = self.channel.model_for_round(r)
        info = self.adaptive.events[-1]
        self.metrics.log_reopt(
            r,
            S_est=float(info["S_est"]),
            S_true=float(variance_S(true_m, A_new)),
            p_err=self.adaptive.estimator.errors(true_m)["p"],
        )
        if verbose:
            print(
                f"  round {r+1:4d}  re-opt alpha: "
                f"S_est={info['S_est']:.3f} "
                f"S_true={self.log.S_true[-1]:.3f} "
                f"p_err={self.log.est_p_err[-1]:.3f}"
            )
        return True

    def _maybe_eval(self, r: int, eval_every: int, verbose: bool) -> None:
        if eval_every and (r + 1) % eval_every == 0 and self.eval_fn is not None:
            with self.spans.span(names.EVAL):
                em = self.eval_fn(self.params)
            self.metrics.log_eval(r, em)
            if verbose:
                print(f"  round {r+1:4d}  loss={self.log.loss[-1]:.4f}  " +
                      "  ".join(f"{k}={v:.4f}" for k, v in em.items()))
        elif verbose and (r + 1) % 10 == 0:
            print(f"  round {r+1:4d}  loss={self.log.loss[-1]:.4f}")

    # ------------------------------------------------------------------
    def _to_device(self, *arrays):
        """``fl.h2d``: a block's host batches and taus onto the device
        (taus as float32), counted in ``h2d_bytes``."""
        with self.spans.span(names.H2D):
            batches, *taus = arrays
            out = (jax.tree.map(jnp.asarray, batches),
                   *(jnp.asarray(t, jnp.float32) for t in taus))
        self.spans.count(names.H2D_BYTES,
                         sum(x.nbytes for x in jax.tree.leaves(out)))
        return out

    def _dispatch(self, fn, args):
        """``fl.dispatch``: call a compiled program on the carry and
        ``args``; returns what it carries besides the model state (the
        sampled scan's channel state and key) and its metrics."""
        with self.spans.span(names.DISPATCH):
            carry = (self.params, self.server_state, self.agg_state)
            if self.telemetry:
                *carry, self._streak, metrics = fn(*carry, *args, self._streak)
            else:
                *carry, metrics = fn(*carry, *args)
            self.params, self.server_state, self.agg_state = carry[:3]
        return carry[3:], metrics

    def _end_block(self, metrics, r: int, k: int) -> None:
        """``fl.fence`` then ``fl.log_rounds``, the last steps of a block."""
        with self.spans.span(names.FENCE):
            jax.block_until_ready(metrics)
        with self.spans.span(names.LOG_ROUNDS):
            self.metrics.log_rounds(r, metrics, k)

    def _record_block(self, block, r: int, k: int) -> None:
        """A closed ``fl.block``: to the meter and the ``timing`` event."""
        self.meter.record(k, block.seconds)
        if self.profile is not None:
            self.profile.maybe_stop(r + k)
        self.metrics.log_timing(r, k, block.seconds, block.children)
        self._log_compile_growth(r + k - 1)

    # ------------------------------------------------------------------
    def _effective_chunk(self, chunk: int, eval_every: int) -> int:
        """Largest usable chunk: the requested one when it divides every
        host-side cadence (adaptive re-opt, eval) — so those events land
        exactly on chunk boundaries — else 1 (per-round fallback)."""
        if chunk <= 1:
            return 1
        if self.adaptive is not None and self.adaptive.cfg.every % chunk != 0:
            return 1
        if eval_every and eval_every % chunk != 0:
            return 1
        return chunk

    def _log_compile_growth(self, r: int) -> None:
        """Emit ``health.recompile`` for jit cache growth past each
        function's expected first compile."""
        grew = self.compiles.check()
        fresh = {}
        for name, growth in grew.items():
            if name in self._warm_fns:
                fresh[name] = growth
            else:
                self._warm_fns.add(name)
        if fresh:
            self.metrics.log_recompiles(fresh, r)

    def _maybe_log_throughput(self, r_next: int) -> None:
        if not self._log_every or r_next - self._last_tlog < self._log_every:
            return
        self._last_tlog = r_next
        import sys
        print(
            f"[telemetry] round {r_next}: "
            f"{self.meter.rounds_per_sec():.2f} rounds/s "
            f"({self.meter.total_rounds} rounds in "
            f"{self.meter.total_seconds:.2f}s)",
            file=sys.stderr,
        )

    def _chunk_fn(self):
        """The jitted traced-scan chunk program, built on first use."""
        if self._scan_fn is None:
            self._scan_fn = jax.jit(self._make_scan_fn(
                self._loss_fn, self._client_opt, self.server_opt, self.rc,
                telemetry=self.telemetry),
                donate_argnums=self._donate_round)
            self.compiles.register("scan_fn", self._scan_fn)
        return self._scan_fn

    def lower_chunk(self, k: int) -> "jax.stages.Lowered":
        """Lower the ``k``-round chunk program that ``run(chunk=k)``
        executes, at the trainer's current state, without running it or
        drawing batches — e.g. to read which kernels its compiled HLO
        calls."""
        return self._lower(self._chunk_fn(), (k,))

    def _lower(self, fn, lead: tuple) -> "jax.stages.Lowered":
        """Lower ``fn`` (the round or the chunk program) at the
        trainer's state, with batches and taus of leading axes ``lead``
        (``()`` for one round, ``(k,)`` for a chunk)."""
        n, c = self.rc.n_clients, self.clients[0]
        steps = () if self.rc.mode == "weighted_grad" else (self.rc.local_steps,)
        batches = {key: jax.ShapeDtypeStruct(
                       (*lead, n, *steps, c.batch_size, *a.shape[1:]), a.dtype)
                   for key, a in c.arrays.items()}
        args = (self.params, self.server_state, self.agg_state, batches,
                jax.ShapeDtypeStruct((*lead, n), jnp.float32),
                jax.ShapeDtypeStruct((*lead, n, n), jnp.float32), self.A)
        if self.telemetry:
            args += (self._streak,)
        return fn.lower(*args)

    def op_scopes(self, k: int) -> Dict[str, str]:
        """``{HLO instruction: device scope}`` of the program that
        ``run(chunk=k)`` executes (the chunk program for ``k > 1``, the
        round for ``k = 1``): each instruction of the compiled program
        goes to the innermost ``fl.*`` scope of its metadata, or to
        ``unscoped``.  A profiler trace names executed operations by
        these instruction names.  Compiles the program, which is a
        persistent-cache load where the cache holds it."""
        lowered = (self.lower_chunk(k) if k > 1
                   else self._lower(self._round_fn, ()))
        return op_scopes(lowered.compile().as_text())

    def _run_blocks(self, r0: int, n_blocks: int, k: int,
                    eval_every: int, verbose: bool) -> None:
        """``n_blocks`` blocks of ``k`` rounds from round ``r0``: single
        rounds through the round program (``k = 1``) or chunks through
        the scan engine.  Each block but the last stacks the next
        block's batches between its dispatch and its fence, so the
        host's gather overlaps the device's work; the batches stay host
        arrays until their own block's ``fl.h2d``."""
        if k == 1:
            fn, trace = self._round_fn, self.channel.tau_for_round
        else:
            fn, trace = self._chunk_fn(), lambda r: self.channel.trace(r, k)
        with self.spans.span(names.STACK_BATCHES):
            batches = self._stack_batches(k)
        for c in range(n_blocks):
            r = r0 + c * k
            if self.profile is not None:
                self.profile.maybe_start(r)
            with self.spans.span(names.BLOCK, round=r) as block:
                with self.spans.span(names.CHANNEL_TRACE):
                    tau_up, tau_dd = trace(r)
                _, metrics = self._dispatch(
                    fn, (*self._to_device(batches, tau_up, tau_dd), self.A))
                # host prefetch: the dispatch above is async, so stacking
                # the next block's batches overlaps this block's device
                # execution.  A checkpoint taken at this boundary must see
                # the client RNGs *before* the prefetch advances them —
                # snapshot first.
                if c + 1 < n_blocks:
                    self._data_rng_snapshot = self._live_rng_states()
                    with self.spans.span(names.STACK_BATCHES):
                        batches = self._stack_batches(k)
                    self.spans.count(names.PREFETCHED_ROUNDS, k)
                else:
                    self._data_rng_snapshot = None  # live RNGs sit at the boundary
                self._end_block(metrics, r, k)
                del metrics  # logged: free its device buffers before the next block
            self._record_block(block, r, k)
            if self.adaptive is not None:
                ups, dds = np.asarray(tau_up), np.asarray(tau_dd)
                if k == 1:  # one round's taus have no leading round axis
                    ups, dds = ups[None], dds[None]
                with self.spans.span(names.REOPT):
                    for i in range(k):
                        swapped = self._ingest_adaptive(r + i, ups[i], dds[i],
                                                        verbose)
                        if swapped and i != k - 1:  # guarded by _effective_chunk
                            raise RuntimeError(
                                "adaptive re-opt fired mid-chunk (round "
                                f"{r + i}, chunk [{r}, {r + k})); the cadence "
                                "must be a multiple of chunk"
                            )
            self._maybe_eval(r + k - 1, eval_every, verbose)
            self._maybe_log_throughput(r + k)
            self.round = r + k
            self._maybe_ckpt()

    def _run_chunks_sampled(self, r0: int, k: int,
                            eval_every: int, verbose: bool) -> None:
        """One chunk of ``k`` rounds with connectivity drawn *inside* the
        compiled scan (``make_scan_round_fn(channel_sampler=...)``): no tau
        tensors ever materialize on host — the channel's gate state and a
        PRNG key thread through the device program instead."""
        if self._sampled_scan_fn is None:
            init_fn, sample_fn = self.channel.scan_sampler()
            self._sampled_scan_fn = jax.jit(self._make_scan_fn(
                self._loss_fn, self._client_opt, self.server_opt, self.rc,
                channel_sampler=sample_fn, telemetry=self.telemetry),
                donate_argnums=self._donate_sampled)
            self.compiles.register("sampled_scan_fn", self._sampled_scan_fn)
            self._sampled_init_fn = init_fn
        # state init is guarded separately from fn build: a restored run
        # arrives here with `_channel_state`/`_channel_rng` already set
        # (the checkpointed carry) and a fresh, unbuilt scan fn — the
        # lazy init must not clobber the restored carry.  The rng, not
        # the state, is the sentinel: static samplers carry state `()`.
        if self._channel_rng is None:
            key = jax.random.PRNGKey(self._seed)
            key, sub = jax.random.split(key)
            self._channel_state = self._sampled_init_fn(sub)
            self._channel_rng = key
        if self.profile is not None:
            self.profile.maybe_start(r0)
        with self.spans.span(names.BLOCK, round=r0) as block:
            with self.spans.span(names.STACK_BATCHES):
                batches = self._stack_batches(k)
            (batches,) = self._to_device(batches)
            (self._channel_state, self._channel_rng), metrics = self._dispatch(
                self._sampled_scan_fn,
                (batches, self._channel_state, self._channel_rng, self.A))
            self._end_block(metrics, r0, k)
        self._record_block(block, r0, k)
        self._maybe_eval(r0 + k - 1, eval_every, verbose)
        self._maybe_log_throughput(r0 + k)
        self.round = r0 + k
        self._data_rng_snapshot = None  # no prefetch on this path
        self._maybe_ckpt()

    # ------------------------------------------------------------------
    def run(self, rounds: int, *, chunk: int = 1, eval_every: int = 0,
            verbose: bool = False, no_trace: bool = False,
            log_every: int = 0, ckpt_dir=None, ckpt_every: int = 0,
            ckpt_keep: int = 3, resume_from=None) -> TrainLog:
        """Train for ``rounds`` communication rounds.

        ``chunk=K`` compiles K rounds into one device program and syncs
        to the host only at chunk boundaries (bitwise-identical
        trajectory to the per-round loop).  Rounds that cannot form an
        aligned full chunk — leading rounds until the global round
        counter hits a multiple of K, and the tail remainder — run
        through the per-round path; if K does not divide the adaptive
        re-opt cadence or ``eval_every``, the whole run falls back to
        per-round execution.

        ``no_trace=True`` draws connectivity *inside* the compiled scan
        via the channel's ``scan_sampler()`` (the in-scan sampler of
        :func:`~repro.fl.round.make_scan_round_fn`): no tau tensors ever
        cross the host boundary — only the channel's packed gate state
        and a PRNG key thread through the program.  The draws come from
        the sampler's own jax PRNG stream, so the trajectory is
        distributionally identical (same marginals / GE dynamics) but not
        bitwise equal to the traced path.  Requires a channel exposing
        ``scan_sampler`` and no adaptive schedule (re-optimization needs
        the realized taus on host).

        ``log_every=N`` prints a cumulative rounds/sec line to stderr
        every N rounds (throughput is measured either way — see
        ``self.meter``).

        **Checkpoint/resume** (DESIGN.md §12): ``ckpt_dir`` enables
        checkpointing — an async save of the complete run state every
        ``ckpt_every`` rounds (``0`` = only the final end-of-run save),
        keep-last-``ckpt_keep`` retention.  When chunked, ``ckpt_every``
        must be a multiple of the chunk (the host only syncs at chunk
        boundaries).  ``resume_from`` (a checkpoint file or a ckpt
        directory, whose latest committed step is used) restores the
        state *first* and reinterprets ``rounds`` as the **total** round
        target: ``run(100, resume_from=ckpt_at_40)`` trains rounds
        40..99, continuing bitwise-identically to the uninterrupted run.
        """
        if resume_from is not None:
            self.restore(resume_from)
        start = self.round
        end = rounds if resume_from is not None else start + rounds
        if end < start:
            raise ValueError(
                f"resume target {end} is behind the restored round {start}")
        k = self._effective_chunk(int(chunk), eval_every)
        self._ckpt_every = int(ckpt_every)
        if ckpt_dir is not None:
            if self._ckpt_every > 0 and k > 1 and self._ckpt_every % k != 0:
                raise ValueError(
                    f"ckpt_every={ckpt_every} must be a multiple of the "
                    f"chunk size {k}: the chunked engine only reaches the "
                    "host at chunk boundaries")
            from repro.ckpt.writer import AsyncCheckpointer
            self._ckpt = AsyncCheckpointer(ckpt_dir, keep=ckpt_keep,
                                           copy_arrays=self.donate)
            self._ckpt_last = -1
        self._log_every = int(log_every)
        self._last_tlog = start
        if no_trace:
            if not hasattr(self.channel, "scan_sampler"):
                raise ValueError(
                    f"no_trace needs a channel with scan_sampler(); "
                    f"{type(self.channel).__name__} cannot sample in-scan"
                )
            if self.adaptive is not None:
                raise ValueError(
                    "no_trace is incompatible with adaptive re-optimization: "
                    "the estimator consumes realized taus on host, which "
                    "no_trace never materializes"
                )
            r = start
            while r < end:
                # any chunk size works (no trace stream to stay aligned
                # with); a short tail just retraces the jit once
                self._run_chunks_sampled(r, min(k, end - r), eval_every,
                                         verbose)
                r += min(k, end - r)
            return self._finish_run()
        r = start
        while r < end:
            if k > 1 and r % k == 0 and r + k <= end:
                n_chunks = (end - r) // k
                self._run_blocks(r, n_chunks, k, eval_every, verbose)
                r += n_chunks * k
            else:
                # single rounds up to the next aligned full chunk (which
                # stacks its own batches), else to the end of the call
                aligned = r + (-r) % k
                stop = aligned if k > 1 and aligned + k <= end else end
                self._run_blocks(r, stop - r, 1, eval_every, verbose)
                r = stop
        return self._finish_run()

    def _finish_run(self) -> TrainLog:
        """End-of-run bookkeeping: final checkpoint commit + writer
        drain, close a dangling profile window and flush the sinks (the
        logger itself stays open — ``run`` may be called again; owners
        call ``self.metrics.close()`` at teardown)."""
        self._finish_ckpt()
        if self.profile is not None:
            self.profile.close()
        self.metrics.flush()
        return self.log
