"""Device-resident telemetry: vector metrics computed inside the round.

The paper's whole argument is about *who got through* — collaborative
relaying exists to lift the participation of poorly-connected clients,
and the Theorem 1 variance bound is a function of per-link outage
statistics — so the fleet-scalar ``participation`` stream is not enough
to observe a run.  This module adds the per-client view without any
mid-scan host traffic:

* ``client_participation (n,)`` — this round's realized uplink vector
  (``tau_up``): which clients' updates reached the PS;
* ``client_uplink_bits (n,)`` — per-client bits-on-air, priced at the
  active wire codec's rate (the per-client decomposition of the scalar
  ``uplink_bits`` metric);
* ``outage_streak (n,)`` — consecutive rounds (including this one) each
  client's uplink has been down: the online view of blockage-burst
  sojourns (the quantity the Gilbert–Elliott gates of
  ``channel/markov.py`` model), carried as a traced ``(n,)`` int32 age
  vector through the scan carry exactly like the channel gate state;
* ``weight_drift`` — ``|sum(w) - 1|``, the realized unbiasedness drift
  of the scalar aggregation weights (condition (5) of the paper makes
  ``E[sum w] = 1``; NaN for strategies with no scalar collapse).

Inside the chunked scan engine the vectors come back stacked ``(K, n)``
per chunk, so nothing leaves the device mid-scan; the per-round loop
sees the same ``(n,)`` values one round at a time.  All functions here
are pure jnp — safe under ``jit`` / ``vmap`` / ``lax.scan`` and under
client-axis sharding (every op is lane-local in the client dim).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.telemetry.spans import TELEMETRY

__all__ = [
    "VECTOR_METRICS",
    "init_streak",
    "update_streak",
    "instrument_round_fn",
]

#: vector metric streams added by ``instrument_round_fn`` (all carry a
#: client axis; stacked ``(K, n)`` by the scan engine)
VECTOR_METRICS = ("client_participation", "client_uplink_bits",
                  "outage_streak")


def init_streak(n: int) -> jax.Array:
    """Zeroed ``(n,)`` int32 outage-age vector (no observed history)."""
    return jnp.zeros((n,), jnp.int32)


def update_streak(streak: jax.Array, tau_up: jax.Array) -> jax.Array:
    """Advance the outage-streak recurrence one round.

    ``streak[i]`` counts consecutive rounds client ``i``'s uplink has
    failed, *including* the current round: a delivered uplink resets to
    0, a blocked one increments.  Pure lane-local select — the same
    shape-stable carry discipline as the channel gate state.
    """
    return jnp.where(tau_up > 0, 0, streak + 1).astype(jnp.int32)


def instrument_round_fn(round_fn, wire_bits_per_coord):
    """Wrap a :func:`~repro.fl.round.make_round_fn` body with the
    device-resident vector metrics.

    The wrapped signature grows one trailing carry argument/result::

        wrapped(params, server_state, agg_state, batches,
                tau_up, tau_dd, A, streak)
            -> (params, server_state, agg_state, streak, metrics)

    where ``metrics`` is the base round's dict plus the
    :data:`VECTOR_METRICS` vectors and the ``weight_drift`` scalar.  The
    base body is untouched (the wrapper only *reads* its inputs and
    outputs), so the training trajectory and the scalar metric streams
    are bitwise identical with telemetry on or off.

    ``wire_bits_per_coord`` is the active strategy's rate method
    (``strategy.wire_bits_per_coord``, bits per coordinate as a function
    of the flat dim); the flat dim itself is read off the params at
    trace time, so the per-client bits fold to one static multiply in
    the compiled round.
    """
    from repro.core import flatten

    def wrapped(params, server_state, agg_state, batches,
                tau_up, tau_dd, A, streak):
        params, server_state, agg_state, metrics = round_fn(
            params, server_state, agg_state, batches, tau_up, tau_dd, A)
        with jax.named_scope(TELEMETRY):
            streak = update_streak(streak, tau_up)
            d_flat = flatten.flat_spec(params).d
            bits = jnp.float32(d_flat * wire_bits_per_coord(d_flat))
            metrics = dict(
                metrics,
                client_participation=tau_up.astype(jnp.float32),
                client_uplink_bits=tau_up.astype(jnp.float32) * bits,
                outage_streak=streak,
                weight_drift=jnp.abs(metrics["weight_sum"] - 1.0),
            )
        return params, server_state, agg_state, streak, metrics

    return wrapped
