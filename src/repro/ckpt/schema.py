"""The versioned run-state schema (DESIGN.md §12).

One checkpoint = one pytree capturing *everything* a training run
threads across rounds, so a restore continues bitwise-identically:

======================  =====================================================
key                     contents
======================  =====================================================
``version``             schema version (``CKPT_VERSION``)
``round``               the trainer's authoritative round counter
``strategy``            registry name (checked on restore — a checkpoint
                        from one aggregation scheme cannot silently seed
                        another)
``params``              model parameters
``server_state``        PS optimizer state
``agg_state``           the strategy's carried pytree, via its
                        ``checkpoint_state``/``restore_state`` hooks (memory
                        replay buffer, quantized codec PRNG key, ...)
``A``                   the live relay-weight matrix (the adaptive schedule
                        mutates it mid-run)
``streak``              telemetry outage-streak carry (None when telemetry
                        is off)
``clients``             per-client data-RNG generator states (JSON-encoded
                        ``bit_generator.state``) at the *consumed-round
                        boundary* — the block loop prefetches the next
                        round's or chunk's batches before the checkpoint
                        point, so the trainer snapshots these before
                        prefetching
``channel``             the channel process's generator/chain state, via its
                        ``checkpoint_state``/``restore_state`` (restores
                        regenerate the current block bitwise)
``no_trace``            the in-scan sampler carry ``{state, rng}`` (None
                        unless the run used ``no_trace=True``)
``adaptive``            estimator posteriors + re-opt event log (None
                        without a schedule)
``metrics``             ``MetricsLogger`` state: monotonic ``seq`` cursor,
                        the full TrainLog facade, accumulated vector streams
======================  =====================================================

Nothing here imports the trainer — capture/restore work on any object
with the ``FLTrainer`` state attributes, so the module stays free of
import cycles.
"""

from __future__ import annotations

import json
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CKPT_VERSION", "capture_run_state", "restore_run_state",
           "rng_state_to_json", "rng_from_json"]

CKPT_VERSION = 1


def rng_state_to_json(rng: np.random.Generator) -> str:
    """A numpy Generator's full state as a JSON string (PCG64 state is
    plain ints/dicts; JSON holds its 128-bit ints exactly)."""
    return json.dumps(rng.bit_generator.state)


def rng_from_json(s: str) -> np.random.Generator:
    """Rebuild a Generator mid-stream from :func:`rng_state_to_json`."""
    state = json.loads(s)
    rng = np.random.default_rng()
    if rng.bit_generator.state["bit_generator"] != state["bit_generator"]:
        raise ValueError(
            f"checkpointed RNG is a {state['bit_generator']}, default_rng "
            f"builds a {rng.bit_generator.state['bit_generator']}")
    rng.bit_generator.state = state
    return rng


def capture_run_state(trainer) -> Dict[str, Any]:
    """Snapshot a trainer's complete run state as one checkpointable
    pytree (host views are copied by the writer's ``snapshot``)."""
    channel = trainer.channel
    if not hasattr(channel, "checkpoint_state"):
        raise TypeError(
            f"{type(channel).__name__} does not implement "
            "checkpoint_state(); its tau stream cannot be resumed")
    no_trace = None
    if trainer._channel_rng is not None:
        no_trace = {"state": trainer._channel_state,
                    "rng": trainer._channel_rng}
    return {
        "version": CKPT_VERSION,
        "round": int(trainer.round),
        "strategy": trainer.strategy.name,
        "params": trainer.params,
        "server_state": trainer.server_state,
        "agg_state": trainer.strategy.checkpoint_state(trainer.agg_state),
        "A": trainer.A,
        "streak": trainer._streak,
        "clients": trainer._client_rng_states(),
        "channel": channel.checkpoint_state(),
        "no_trace": no_trace,
        "adaptive": (trainer.adaptive.checkpoint_state()
                     if trainer.adaptive is not None else None),
        "metrics": trainer.metrics.checkpoint_state(),
    }


def restore_run_state(trainer, state: Dict[str, Any]) -> None:
    """Reinstate a captured state onto a freshly-built trainer.

    The trainer must be assembled identically to the checkpointed one
    (same strategy, channel type, client count, telemetry flag) — the
    checkpoint carries *state*, not configuration; mismatches raise.
    """
    version = state.get("version")
    if version != CKPT_VERSION:
        raise ValueError(
            f"checkpoint schema version {version!r} != {CKPT_VERSION}")
    if state["strategy"] != trainer.strategy.name:
        raise ValueError(
            f"checkpoint was written by strategy {state['strategy']!r}; "
            f"this trainer runs {trainer.strategy.name!r}")
    if (state.get("streak") is not None) != bool(trainer.telemetry):
        raise ValueError(
            "telemetry mismatch: checkpoint "
            f"{'has' if state.get('streak') is not None else 'lacks'} a "
            "streak carry but the trainer's telemetry flag disagrees")

    trainer.params = jax.tree.map(jnp.asarray, state["params"])
    trainer.server_state = jax.tree.map(jnp.asarray, state["server_state"])
    trainer.agg_state = trainer.strategy.restore_state(state["agg_state"])
    trainer.A = jnp.asarray(state["A"], jnp.float32)
    trainer.round = int(state["round"])
    if state.get("streak") is not None:
        trainer._streak = jnp.asarray(state["streak"], jnp.int32)

    clients = state["clients"]
    if len(clients) != len(trainer.clients):
        raise ValueError(
            f"checkpoint has {len(clients)} client RNG streams; trainer "
            f"has {len(trainer.clients)} clients")
    for c, s in zip(trainer.clients, clients):
        c._rng = rng_from_json(s)
    trainer._data_rng_snapshot = None

    if not hasattr(trainer.channel, "restore_state"):
        raise TypeError(
            f"{type(trainer.channel).__name__} does not implement "
            "restore_state()")
    trainer.channel.restore_state(state["channel"])

    no_trace = state.get("no_trace")
    if no_trace is not None:
        trainer._channel_state = jax.tree.map(jnp.asarray, no_trace["state"])
        trainer._channel_rng = jnp.asarray(no_trace["rng"])

    adaptive = state.get("adaptive")
    if adaptive is not None:
        if trainer.adaptive is None:
            raise ValueError(
                "checkpoint carries adaptive-schedule state but the "
                "trainer has no schedule attached")
        trainer.adaptive.restore_state(adaptive)
    trainer.metrics.restore_state(state["metrics"])
