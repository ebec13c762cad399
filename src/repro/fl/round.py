"""One federated round as a pure JAX function (jit / pjit compatible).

The round implements Algorithms 1 + 2 of the paper:
  1. every client runs ``T`` local SGD steps from the PS model (Alg. 1, 1-7),
  2. clients exchange updates over the sampled D2D links and each transmits
     a weighted consensus to the PS (Alg. 1, 8-11 / Eq. (3)),
  3. the PS applies whatever aggregation *strategy* the round was built
     with (the paper's ColRel, a FedAvg baseline, K-hop relaying, memory
     replay, or anything registered in ``repro.strategies``) and the
     server optimizer (global momentum in the paper's experiments).

Connectivity realizations ``tau_up (n,) / tau_dd (n, n)`` are *traced
inputs* so a single compiled round serves every round of training.
Strategy state (e.g. the memory strategy's replay buffer) threads
through the round as the ``agg_state`` pytree — shape-stable across
rounds, so tau/alpha swaps never recompile; stateless strategies carry
``()``.

Execution modes (DESIGN.md §3):
  * ``per_client``        — vmap over the client axis (client = mesh "data"
                            shard).  The one mode that materializes the
                            per-client update stack, so the only mode
                            open to non-scalar-collapsible strategies.
  * ``client_sequential`` — lax.scan over clients; peak memory is a single
    model copy regardless of n (for the 100B+ archs).  Mathematically
    identical; consumes the strategy's scalar collapse (a running
    weighted sum).
  * ``weighted_grad``     — the T=1 algebraic collapse: ColRel ==
    per-client-weighted data-parallel SGD, no per-client model copies.

Multi-round execution (DESIGN.md §9): :func:`make_scan_round_fn` wraps
the round body in a ``lax.scan`` over a leading K-round axis, so K
communication rounds run as one device program with a single host
round-trip — the chunked engine ``FLTrainer.run(chunk=K)`` and the
production launch path drive.

Each part of the round runs under a ``jax.named_scope`` named in
:mod:`repro.telemetry.spans` (``fl.local_sgd``, ``fl.aggregate``,
``fl.server_step``, ``fl.round_metrics``; ``fl.flatten`` inside the
aggregation, ``fl.channel_sample`` in the sampled scan).  Scopes are
metadata: they name the compiled instructions and change no arithmetic.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp

from repro import strategies as strategy_registry
from repro.core import flatten
from repro.core.aggregation import Aggregation
from repro.dist import constrain_grads, spmd_axis_name
from repro.optim import Optimizer
from repro.optim.base import global_norm
from repro.strategies.base import AggregationStrategy, ExecutionContext
from repro.telemetry import spans as names

Params = Any

StrategySpec = Union[Aggregation, str, AggregationStrategy]


@dataclasses.dataclass(frozen=True)
class RoundConfig:
    n_clients: int
    local_steps: int  # the paper's T
    mode: str = "per_client"  # per_client | client_sequential | weighted_grad
    # aggregation strategy: registry name, legacy Aggregation enum value,
    # or a constructed AggregationStrategy instance
    aggregation: StrategySpec = "colrel"
    use_flash: bool = False
    # Under pjit, pin the vmapped client axis to these mesh axes so each
    # client's divergent model copy lives on its own data shard.
    spmd_axes: Optional[tuple] = None
    # unroll the local-steps / client scans (dry-run cost probes)
    unroll: bool = False
    # DEPRECATED: forwards to the colrel strategy's fused="kernel"
    # execution option (strategies.get("colrel", fused="kernel")).
    use_fused_kernel: bool = False
    # dtype of the flattened (n, d) update stack ("float32" | "bfloat16");
    # accumulation is fp32 either way.
    flat_dtype: str = "float32"
    # d-axis tile of the fused kernel's grid
    fused_block_d: int = 2048
    # flat-dim threshold for segment-streaming aggregation (DESIGN.md
    # §14): at d >= segment_d the kernel-fused strategies stream per-leaf
    # (n, d_i) segments instead of materializing the monolithic (n, d)
    # stack; 0 keeps the monolithic path (the golden-pinned default).
    segment_d: int = 0

    def __post_init__(self):
        # fail at construction, not first trace; canonical_name does not
        # instantiate, so no deprecation warning fires twice
        name = strategy_registry.canonical_name(self.aggregation)
        if self.use_fused_kernel and name != "colrel":
            raise ValueError(
                "use_fused_kernel only applies to the colrel strategy "
                f"(got {self.aggregation}); it would be silently inert"
            )

    def resolve_strategy(self) -> AggregationStrategy:
        """The configured strategy instance (deprecated spellings warn)."""
        return strategy_registry.resolve(
            self.aggregation, fused_kernel=self.use_fused_kernel
        )

    def execution_context(self) -> ExecutionContext:
        return ExecutionContext(
            n_clients=self.n_clients,
            flat_dtype=jnp.dtype(self.flat_dtype),
            fused_block_d=self.fused_block_d,
            spmd_axes=self.spmd_axes,
            segment_d=self.segment_d,
        )


def _tree_sub(a: Params, b: Params) -> Params:
    return jax.tree.map(lambda x, y: (x.astype(jnp.float32) - y.astype(jnp.float32)), a, b)


def _local_sgd(loss_fn, client_opt: Optimizer, params: Params, batches: Params,
               unroll: bool = False):
    """T local SGD steps.  ``batches`` leaves have leading dim T."""

    def step(carry, batch):
        p, ostate = carry
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
        upd, ostate = client_opt.update(grads, ostate, p)
        p = jax.tree.map(lambda x, u: (x.astype(jnp.float32) + u).astype(x.dtype), p, upd)
        return (p, ostate), loss

    T = jax.tree.leaves(batches)[0].shape[0]
    with jax.named_scope(names.LOCAL_SGD):
        (p_final, _), losses = jax.lax.scan(
            step, (params, client_opt.init(params)), batches, unroll=T if unroll else 1
        )
        return _tree_sub(p_final, params), jnp.mean(losses)


def make_round_fn(
    loss_fn: Callable,
    client_opt: Optimizer,
    server_opt: Optimizer,
    rc: RoundConfig,
    grad_shardings: Optional[Params] = None,
    telemetry: bool = False,
):
    """Returns round(params, server_state, agg_state, batches,
    tau_up, tau_dd, A) -> (params, server_state, agg_state, metrics).

    ``batches``: pytree with leaves shaped (n_clients, T, B, ...) for
    per_client/client_sequential, or (T=1 collapsed) (n_clients, B, ...)
    for weighted_grad.  ``agg_state`` is the strategy's carried state
    (``strategy.init_state(n, d)``; ``()`` for stateless strategies).

    ``telemetry=True`` wraps the body with the device-resident vector
    metrics (DESIGN.md §11): the signature grows one trailing ``streak``
    carry — ``round(params, server_state, agg_state, batches, tau_up,
    tau_dd, A, streak) -> (params, server_state, agg_state, streak,
    metrics)`` — and ``metrics`` additionally carries per-client
    ``client_participation`` / ``client_uplink_bits`` / ``outage_streak``
    ``(n,)`` vectors plus the ``weight_drift`` scalar.  The body itself
    is untouched, so trajectories and scalar metrics stay bitwise
    identical with telemetry on or off.
    """
    strategy = rc.resolve_strategy()
    ctx = rc.execution_context()
    if rc.mode != "per_client" and (strategy.stateful
                                    or not strategy.scalar_collapsible):
        # non-per_client modes consume only the scalar collapse and never
        # call aggregate/aggregate_tree, so a stateful strategy's carried
        # state would silently freeze at init_state
        raise ValueError(
            f"strategy {strategy.name!r} needs the per_client mode: only it "
            f"materializes the update stack that stateful or "
            f"non-scalar-collapsible strategies require (got mode={rc.mode!r})"
        )

    def client_delta(params, client_batches):
        return _local_sgd(loss_fn, client_opt, params, client_batches, unroll=rc.unroll)

    def round_fn(params, server_state, agg_state, batches, tau_up, tau_dd, A):
        # Realized scalar weights this round (for COLREL: the exact fused
        # collapse w_j = sum_i tau_i tau_ji alpha_ij, scaled 1/n).  Used by
        # the scalar-weight execution branches below and logged as
        # ``weight_sum`` — under the unbiasedness condition (5) its
        # expectation is 1, so its round-to-round dispersion is the
        # realized counterpart of the variance proxy S that COPT-alpha
        # (and the adaptive re-optimization schedule) minimize.  None for
        # strategies that do not collapse (their weight_sum logs as NaN).
        with jax.named_scope(names.AGGREGATE):
            w_scalar = strategy.weights(tau_up, tau_dd, A)
        if rc.mode == "per_client":
            spmd = spmd_axis_name(rc.spmd_axes)
            deltas, losses = jax.vmap(
                client_delta, in_axes=(None, 0), spmd_axis_name=spmd
            )(params, batches)
            with jax.named_scope(names.AGGREGATE):
                gdelta, agg_state = strategy.aggregate_tree(
                    deltas, tau_up, tau_dd, A, agg_state, ctx
                )
            with jax.named_scope(names.ROUND_METRICS):
                mean_loss = jnp.mean(losses)

        elif rc.mode == "client_sequential":
            w = w_scalar

            def body(carry, inp):
                acc, loss_acc = carry
                wi, client_batches = inp
                delta, loss = client_delta(params, client_batches)
                with jax.named_scope(names.AGGREGATE):
                    acc = jax.tree.map(lambda a, d: a + wi * d, acc, delta)
                with jax.named_scope(names.ROUND_METRICS):
                    loss_acc = loss_acc + loss
                return (acc, loss_acc), None

            with jax.named_scope(names.AGGREGATE):
                zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gdelta, loss_sum), _ = jax.lax.scan(
                body, (zeros, 0.0), (w, batches),
                unroll=rc.n_clients if rc.unroll else 1,
            )
            with jax.named_scope(names.ROUND_METRICS):
                mean_loss = loss_sum / rc.n_clients

        elif rc.mode == "weighted_grad":
            # T = 1 collapse: one backward pass over all clients' batches with
            # per-client loss weights — ColRel as weighted data parallelism.
            w = w_scalar
            spmd = spmd_axis_name(rc.spmd_axes)

            def weighted_loss(p):
                def per_client(batch):
                    return loss_fn(p, batch)[0]

                losses = jax.vmap(per_client, spmd_axis_name=spmd)(batches)  # (n,)
                return jnp.sum(w * losses), losses

            with jax.named_scope(names.LOCAL_SGD):
                (_, losses), grads = jax.value_and_grad(
                    weighted_loss, has_aux=True)(params)
                grads = constrain_grads(grads, grad_shardings)
                upd, _ = client_opt.update(grads, client_opt.init(params), params)
                gdelta = jax.tree.map(lambda u: u.astype(jnp.float32), upd)
            with jax.named_scope(names.ROUND_METRICS):
                mean_loss = jnp.mean(losses)

        elif rc.mode == "weighted_flat":
            # Beyond-paper (exact) flattening of the T=1 round: instead of a
            # per-client vmap (which multiplies backward intermediates by a
            # lane factor), fold the client dim into the batch and weight
            # each SEQUENCE by w_{client(seq)} / B inside the loss.  Same
            # gradient as weighted_grad; one flat data-parallel backward.
            w = w_scalar
            n_total = jax.tree.leaves(batches)[0].shape[0]
            B_per = n_total // rc.n_clients
            with jax.named_scope(names.AGGREGATE):
                seq_w = jnp.repeat(w, B_per) / B_per

            def flat_loss(p):
                return loss_fn(p, {**batches, "ce_weight": seq_w})[0]

            with jax.named_scope(names.LOCAL_SGD):
                loss_val, grads = jax.value_and_grad(flat_loss)(params)
                # pin the gradient tree to the params' fully-sharded layout
                # (otherwise the partitioner may materialize it replicated
                # over the data axes — 100s of GB for the 100B+ archs)
                grads = constrain_grads(grads, grad_shardings)
                upd, _ = client_opt.update(grads, client_opt.init(params), params)
                gdelta = jax.tree.map(lambda u: u.astype(jnp.float32), upd)
            mean_loss = loss_val
        else:
            raise ValueError(f"unknown mode {rc.mode}")

        # PS applies the round delta through the server optimizer by feeding
        # the negative delta as a pseudo-gradient (FedOpt convention); with
        # sgd_momentum(lr=1, beta) this is exactly the paper's PS momentum.
        with jax.named_scope(names.SERVER_STEP):
            pseudo_grads = jax.tree.map(lambda d: -d, gdelta)
            upd, server_state = server_opt.update(pseudo_grads, server_state, params)
            new_params = jax.tree.map(
                lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype), params, upd
            )
        with jax.named_scope(names.ROUND_METRICS):
            participation = jnp.sum(tau_up.astype(jnp.float32))
            # Wire-format-aware uplink accounting: bits put on air by the
            # clients whose uplink delivered this round, priced at the
            # active codec's per-coordinate wire cost (32 bits/coord for
            # uncoded f32; the quantized strategy reports its codec
            # descriptor).  d and the rate are static, so this folds to
            # one multiply in the compiled round.
            d_flat = flatten.flat_spec(params).d
            bits_per_client = jnp.float32(
                d_flat * strategy.wire_bits_per_coord(d_flat))
            metrics = {
                "loss": mean_loss,
                "delta_norm": global_norm(gdelta),
                "participation": participation,
                "uplink_bits": participation * bits_per_client,
                "weight_sum": (jnp.sum(w_scalar) if w_scalar is not None
                               else jnp.float32(jnp.nan)),
            }
        return new_params, server_state, agg_state, metrics

    if not telemetry:
        return round_fn
    from repro.telemetry.device import instrument_round_fn

    # the wire rate is static per strategy (a function of the flat dim,
    # which the wrapper reads off params at trace time)
    return instrument_round_fn(round_fn, strategy.wire_bits_per_coord)


def make_scan_round_fn(
    loss_fn: Callable,
    client_opt: Optimizer,
    server_opt: Optimizer,
    rc: RoundConfig,
    grad_shardings: Optional[Params] = None,
    channel_sampler: Optional[Callable] = None,
    telemetry: bool = False,
):
    """The chunked multi-round engine: K rounds compiled into one program.

    Wraps the :func:`make_round_fn` body in a single ``lax.scan`` over a
    leading K-round axis, so a whole chunk of communication rounds runs
    on device with one host round-trip.  The scan carry is
    ``(params, server_state, agg_state)`` (plus ``(channel_state, rng)``
    with an in-scan sampler); per-round ``loss / participation /
    uplink_bits / weight_sum / delta_norm`` metrics come back stacked
    with a leading ``(K,)`` axis for bulk host-side logging.

    Two tau sources:

    * default — pre-generated **device-resident channel traces**:
      ``scan(params, server_state, agg_state, batches, tau_up, tau_dd,
      A)`` with ``tau_up (K, n)`` / ``tau_dd (K, n, n)`` scanned as
      per-round inputs (``ChannelProcess.trace`` produces them).  Since
      the body is the very ``round_fn`` the per-round loop jits, the
      K-round trajectory is *bitwise identical* to K sequential calls on
      the same inputs (asserted in ``tests/test_scan_engine.py``).
    * ``channel_sampler=(...)`` — an in-scan sampler ``sample_fn(state,
      key) -> (tau_up, tau_dd, state)`` (see
      ``ChannelProcess.scan_sampler``): connectivity is drawn *inside*
      the compiled program, no tau tensors ever materialize on host.
      Signature becomes ``scan(params, server_state, agg_state, batches,
      channel_state, rng, A) -> (params, server_state, agg_state,
      channel_state, rng, metrics)``.

    ``batches`` leaves carry a leading K axis on top of the per-round
    layout of the configured mode: ``(K, n, T, B, ...)`` for
    per_client / client_sequential, ``(K, n, B, ...)`` for
    weighted_grad.  K is baked into the trace via the input shapes —
    one compile per distinct chunk size, reused across chunks.

    ``telemetry=True`` (DESIGN.md §11) threads the ``(n,)`` int32
    outage-streak age vector through the scan carry — next to the
    channel gate state in the sampled variant — and stacks the vector
    metrics ``(K, n)``: both signatures grow one trailing ``streak``
    input and a ``streak`` result before ``metrics``, and nothing
    telemetry-related leaves the device mid-scan.
    """
    round_fn = make_round_fn(loss_fn, client_opt, server_opt, rc,
                             grad_shardings=grad_shardings,
                             telemetry=telemetry)
    return _scan_engine(round_fn, channel_sampler, telemetry)


def _scan_engine(round_fn, channel_sampler, telemetry):
    """Wrap a compiled round body in the K-round ``lax.scan`` closures.

    Shared by :func:`make_scan_round_fn` and
    :func:`make_async_scan_round_fn` — the async carry (age vector +
    staging buffer) lives inside ``agg_state``, so the scan signatures
    are identical for both.
    """
    if channel_sampler is None:
        if telemetry:

            def scan_traced_tel(params, server_state, agg_state, batches,
                                tau_up, tau_dd, A, streak):
                def body(carry, xs):
                    p, ss, ag, st = carry
                    b, tu, td = xs
                    p, ss, ag, st, metrics = round_fn(p, ss, ag, b, tu, td,
                                                      A, st)
                    return (p, ss, ag, st), metrics

                (params, server_state, agg_state, streak), metrics = (
                    jax.lax.scan(
                        body, (params, server_state, agg_state, streak),
                        (batches, tau_up, tau_dd),
                    )
                )
                return params, server_state, agg_state, streak, metrics

            return scan_traced_tel

        def scan_traced(params, server_state, agg_state, batches,
                        tau_up, tau_dd, A):
            def body(carry, xs):
                p, ss, ag = carry
                b, tu, td = xs
                p, ss, ag, metrics = round_fn(p, ss, ag, b, tu, td, A)
                return (p, ss, ag), metrics

            (params, server_state, agg_state), metrics = jax.lax.scan(
                body, (params, server_state, agg_state),
                (batches, tau_up, tau_dd),
            )
            return params, server_state, agg_state, metrics

        return scan_traced

    sample_fn = channel_sampler

    if telemetry:

        def scan_sampled_tel(params, server_state, agg_state, batches,
                             channel_state, rng, A, streak):
            def body(carry, b):
                p, ss, ag, cs, key, st = carry
                with jax.named_scope(names.CHANNEL_SAMPLE):
                    key, sub = jax.random.split(key)
                    tu, td, cs = sample_fn(cs, sub)
                p, ss, ag, st, metrics = round_fn(p, ss, ag, b, tu, td, A, st)
                return (p, ss, ag, cs, key, st), metrics

            (params, server_state, agg_state, channel_state, rng, streak), \
                metrics = jax.lax.scan(
                    body,
                    (params, server_state, agg_state, channel_state, rng,
                     streak),
                    batches,
                )
            return (params, server_state, agg_state, channel_state, rng,
                    streak, metrics)

        return scan_sampled_tel

    def scan_sampled(params, server_state, agg_state, batches,
                     channel_state, rng, A):
        def body(carry, b):
            p, ss, ag, cs, key = carry
            with jax.named_scope(names.CHANNEL_SAMPLE):
                key, sub = jax.random.split(key)
                tu, td, cs = sample_fn(cs, sub)
            p, ss, ag, metrics = round_fn(p, ss, ag, b, tu, td, A)
            return (p, ss, ag, cs, key), metrics

        (params, server_state, agg_state, channel_state, rng), metrics = (
            jax.lax.scan(
                body,
                (params, server_state, agg_state, channel_state, rng),
                batches,
            )
        )
        return params, server_state, agg_state, channel_state, rng, metrics

    return scan_sampled


def make_async_round_fn(
    loss_fn: Callable,
    client_opt: Optimizer,
    server_opt: Optimizer,
    rc: RoundConfig,
    grad_shardings: Optional[Params] = None,
    telemetry: bool = False,
):
    """Async execution mode: staleness-weighted opportunistic relaying.

    Same signature and carry structure as :func:`make_round_fn` — the
    async state (the traced ``(n,)`` int32 age vector and the ``(n, d)``
    staging buffer, DESIGN.md §13) lives *inside* ``agg_state``, where
    the strategy's :meth:`~repro.strategies.AsyncRelayStrategy.advance`
    recurrence updates it every round.  On top of the base metrics the
    round reports the realized staleness profile:

    * ``mean_age`` / ``max_age`` — the post-delivery age vector's mean
      and max (rounds since each client's update last reached the PS),
    * ``stale_frac`` — fraction of clients aggregating a stale update.

    ``rc.aggregation`` must be an async strategy (``async_colrel`` or an
    :class:`~repro.strategies.AsyncRelayStrategy` wrapping the desired
    inner scheme); building the async round over a sync strategy is
    refused rather than silently running sync semantics.
    """
    strategy = rc.resolve_strategy()
    if not getattr(strategy, "is_async", False):
        raise ValueError(
            f"make_async_round_fn needs an async strategy (e.g. "
            f"'async_colrel'), got {strategy.name!r}; wrap it in "
            f"AsyncRelayStrategy or use FLTrainer(mode='async')"
        )
    base = make_round_fn(loss_fn, client_opt, server_opt, rc,
                         grad_shardings=grad_shardings, telemetry=False)

    def round_fn(params, server_state, agg_state, batches, tau_up, tau_dd, A):
        params, server_state, agg_state, metrics = base(
            params, server_state, agg_state, batches, tau_up, tau_dd, A)
        with jax.named_scope(names.ROUND_METRICS):
            age = agg_state["age"].astype(jnp.float32)
            metrics = dict(
                metrics,
                mean_age=jnp.mean(age),
                max_age=jnp.max(age),
                stale_frac=jnp.mean((age > 0).astype(jnp.float32)),
            )
        return params, server_state, agg_state, metrics

    if not telemetry:
        return round_fn
    from repro.telemetry.device import instrument_round_fn

    return instrument_round_fn(round_fn, strategy.wire_bits_per_coord)


def make_async_scan_round_fn(
    loss_fn: Callable,
    client_opt: Optimizer,
    server_opt: Optimizer,
    rc: RoundConfig,
    grad_shardings: Optional[Params] = None,
    channel_sampler: Optional[Callable] = None,
    telemetry: bool = False,
):
    """Chunked async engine: K staleness-weighted rounds in one scan.

    Identical scan signatures to :func:`make_scan_round_fn` (traced and
    in-scan-sampled variants, with or without telemetry) — the age
    vector and staging buffer ride the existing ``agg_state`` slot of
    the scan carry, so chunking, no-trace sampling, checkpoint/resume
    and the telemetry streak all compose with async execution for free.
    The per-round ``mean_age`` / ``max_age`` / ``stale_frac`` metrics
    come back stacked ``(K,)`` like every other scalar stream.
    """
    round_fn = make_async_round_fn(loss_fn, client_opt, server_opt, rc,
                                   grad_shardings=grad_shardings,
                                   telemetry=telemetry)
    return _scan_engine(round_fn, channel_sampler, telemetry)
