"""Step builders + sharding assembly for the production launch/dry-run.

``build_step(arch_id, shape_name, mesh, ...)`` returns
``(step_fn, specs, in_shardings, out_shardings)`` ready for

    with jax.set_mesh(mesh):
        jax.jit(step_fn, in_shardings=..., out_shardings=...).lower(**specs)

Step kinds per input shape (see configs/base.py):
  train_4k               -> one full ColRel FL round (T local SGD steps,
                            relay consensus, blind PS sum, PS momentum)
  prefill_32k            -> forward logits
  decode_32k / long_500k -> one-token serve step against a deep KV cache

``scan_rounds=K`` turns the train step into the chunked multi-round scan
engine (DESIGN.md §9): the same round body scanned over a leading K axis
— batches ``(K, C, T, B, ...)``, channel trace ``tau_up (K, C)`` /
``tau_dd (K, C, C)``, metrics stacked ``(K,)`` — so the production pjit
path compiles K communication rounds into one program exactly like
``FLTrainer.run(chunk=K)`` does on CPU.

``telemetry=True`` lowers the instrumented round instead (DESIGN.md
§11): one extra ``(C,)`` int32 outage-streak operand/result, per-client
metric vectors sharded along the client axes like ``tau_up`` (stacked
``(K, C)`` under ``scan_rounds``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import strategies as strategy_registry
from repro.configs.base import get_arch
from repro.core import flatten
from repro.fl.round import (
    RoundConfig,
    StrategySpec,
    make_async_round_fn,
    make_async_scan_round_fn,
    make_round_fn,
    make_scan_round_fn,
)


def get_arch_cfg(arch_id: str):
    return get_arch(arch_id).full()
from repro.launch import sharding as shard_rules
from repro.launch.mesh import client_axes, n_clients
from repro.launch.specs import DRYRUN_LOCAL_STEPS, input_specs
from repro.models import build
from repro.optim import sgd, sgd_momentum

# Paper hyperparameters carried into the production round.
CLIENT_LR = 0.05
CLIENT_WD = 1e-4
SERVER_MOMENTUM = 0.9


def train_state_shardings(
    arch_id: str,
    shape_name: str,
    mesh,
    *,
    aggregation: StrategySpec = "colrel",
    fl_mode: str | None = None,
    cfg_override=None,
) -> Dict[str, Any]:
    """Shardings for the checkpointable train-state leaves.

    ``repro.ckpt`` writes each array per-shard via its ``Sharding`` (the
    ``(n, d)`` client-axis stacks never gather); on restore the reverse
    trip needs the same layouts to ``jax.device_put`` the reassembled
    hosts arrays back onto the production mesh.  Returns
    ``{"params", "server_state", "agg_state"}`` sharding trees matching
    :func:`build_step`'s train in/out shardings (DESIGN.md §12).
    """
    mode = fl_mode or (cfg_override or get_arch_cfg(arch_id)).fl_mode
    specs = input_specs(arch_id, shape_name, mesh, cfg=cfg_override,
                        fl_mode=mode)
    cfg = specs["cfg"]
    fsdp = mode in ("client_sequential", "weighted_grad", "weighted_flat")
    strategy = strategy_registry.resolve(aggregation)
    d_flat = flatten.flat_spec(specs["params"]).d
    agg_state = jax.eval_shape(
        lambda: strategy.init_state(n_clients(mesh), d_flat)
    )
    return {
        "params": shard_rules.param_shardings(cfg, specs["params"], mesh,
                                              fsdp=fsdp),
        "server_state": shard_rules.param_shardings(cfg, specs["server_state"],
                                                    mesh, fsdp=fsdp),
        "agg_state": shard_rules.client_state_shardings(mesh, agg_state,
                                                        n_clients(mesh)),
    }


def build_step(
    arch_id: str,
    shape_name: str,
    mesh,
    *,
    aggregation: StrategySpec = "colrel",
    fl_mode: str | None = None,
    cfg_override=None,
    scan_rounds: int | None = None,
    telemetry: bool = False,
) -> Tuple[Any, Dict[str, Any], Any, Any]:
    mode = fl_mode or (cfg_override or get_arch_cfg(arch_id)).fl_mode
    specs = input_specs(arch_id, shape_name, mesh, cfg=cfg_override, fl_mode=mode)
    cfg = specs["cfg"]
    fsdp = mode in ("client_sequential", "weighted_grad", "weighted_flat")
    ca = client_axes(mesh)

    if cfg.n_experts > 0:
        # expert-parallel dispatch buffers (all step kinds)
        if cfg.n_experts % mesh.shape["model"] == 0:
            cfg = cfg.replace(moe_buf_spec=("model", None, None))
        else:
            cfg = cfg.replace(moe_buf_spec=(None, "model", None))
        specs["cfg"] = cfg

    caxis_spec = ca if len(ca) > 1 else ca[0]
    if specs["kind"] == "train":
        # Residual-stream layout (see repro/dist/constraints.py):
        #  * fsdp (ZeRO) giants: per-client batch over the model axis when it
        #    divides, else sequence over model — makes the partitioner gather
        #    weights instead of all-reducing activation partials.
        #  * per_client archs: Megatron-SP-style sequence sharding over the
        #    model axis (the client lane is already pinned to the data axes
        #    via spmd_axis_name; without this, backward intermediates
        #    replicate over the model axis).
        C = n_clients(mesh)
        from repro.configs.base import INPUT_SHAPES

        B = INPUT_SHAPES[shape_name].global_batch // C
        if mode == "weighted_flat":
            # pin the flat batch to the full-mesh layout at every block
            # boundary (without this the partitioner drifts to replication)
            gb = INPUT_SHAPES[shape_name].global_batch
            full = (*ca, "model")
            n_full = 1
            for a in full:
                n_full *= mesh.shape[a]
            if gb % n_full == 0:
                cfg = cfg.replace(act_spec=(full, None, None))
            else:
                cfg = cfg.replace(act_spec=(caxis_spec, "model", None))
        elif fsdp and B % mesh.shape["model"] == 0:
            cfg = cfg.replace(act_spec=("model", None, None))
        else:
            cfg = cfg.replace(act_spec=(None, "model", None))
        specs["cfg"] = cfg
    elif specs["kind"] == "prefill" and (fsdp or cfg.n_experts == 0):
        # prefill: batch over the client axes, sequence over model.
        # (skipped for per_client MoE archs — sequence-sharded tokens fight
        # the capacity-dispatch scatter and regress memory; measured on
        # granite: 31 GB -> 122 GB with the constraint.)
        cfg = cfg.replace(act_spec=(caxis_spec, "model", None))
        specs["cfg"] = cfg
    bundle = build(cfg)

    if specs["kind"] == "train":
        strategy = strategy_registry.resolve(aggregation)
        rc = RoundConfig(
            n_clients=n_clients(mesh),
            local_steps=DRYRUN_LOCAL_STEPS,
            mode=mode,
            aggregation=strategy,
            spmd_axes=ca if mode in ("per_client", "weighted_grad") else None,
            unroll=getattr(cfg, "scan_unroll", False),
        )
        psh = shard_rules.param_shardings(cfg, specs["params"], mesh, fsdp=fsdp)
        # async strategies (DESIGN.md §13) lower through the async round
        # builders: same signatures, agg_state additionally carries the
        # (n,) age vector + (n, d) staging buffer (client-axis sharded by
        # client_state_shardings below) and three extra scalar metrics.
        is_async = getattr(strategy, "is_async", False)
        make_fn = make_async_round_fn if is_async else make_round_fn
        if scan_rounds:
            K = int(scan_rounds)
            make_fn = make_async_scan_round_fn if is_async else make_scan_round_fn
            # leading K-round axis on the scanned per-round inputs
            SDS = jax.ShapeDtypeStruct
            specs["batches"] = jax.tree.map(
                lambda s: SDS((K, *s.shape), s.dtype), specs["batches"])
            specs["tau_up"] = SDS((K, *specs["tau_up"].shape),
                                  specs["tau_up"].dtype)
            specs["tau_dd"] = SDS((K, *specs["tau_dd"].shape),
                                  specs["tau_dd"].dtype)
        round_fn = make_fn(
            bundle.loss_fn,
            sgd(CLIENT_LR, weight_decay=CLIENT_WD),
            sgd_momentum(1.0, beta=SERVER_MOMENTUM),
            rc,
            grad_shardings=psh if fsdp else None,
            telemetry=telemetry,
        )
        # strategy carried state (replay buffers etc.): lower against its
        # abstract shape; client-indexed leaves (the memory strategy's
        # (n, d) buffer) shard over the client axes next to the update
        # stack, everything else replicates
        d_flat = flatten.flat_spec(specs["params"]).d
        agg_state = jax.eval_shape(
            lambda: strategy.init_state(rc.n_clients, d_flat)
        )
        ssh = shard_rules.param_shardings(cfg, specs["server_state"], mesh, fsdp=fsdp)
        bsh = shard_rules.train_batch_shardings(
            mesh, mode, specs["batches"], scan=bool(scan_rounds))
        rep = NamedSharding(mesh, P())
        st_sh = shard_rules.client_state_shardings(mesh, agg_state, rc.n_clients)
        # connectivity realizations + relay weights shard along the client
        # axes together with the update stack (scalax-style rule,
        # launch/sharding.fl_round_rule): dense (n, n) operands shard rows,
        # block (C, m, m) cluster tensors shard the cluster axis; the scan
        # trace's leading K axis stays unsharded.  A carries no K axis.
        tau_sh = shard_rules.fl_round_rule(scan=bool(scan_rounds)).shardings(
            mesh, {"tau_up": specs["tau_up"], "tau_dd": specs["tau_dd"]})
        A_sh = shard_rules.fl_round_rule().shardings(mesh, {"A": specs["A"]})["A"]
        in_sh = (psh, ssh, st_sh, bsh, tau_sh["tau_up"], tau_sh["tau_dd"], A_sh)
        metrics_sh = {
            "loss": rep,
            "delta_norm": rep,
            "participation": rep,
            "uplink_bits": rep,
            "weight_sum": rep,
        }
        if is_async:
            metrics_sh = dict(metrics_sh, mean_age=rep, max_age=rep,
                              stale_frac=rep)
        out_sh = (psh, ssh, st_sh, metrics_sh)
        lower_args = (
            specs["params"],
            specs["server_state"],
            agg_state,
            specs["batches"],
            specs["tau_up"],
            specs["tau_dd"],
            specs["A"],
        )
        if telemetry:
            # instrumented round (DESIGN.md §11): an (n,) int32 outage-
            # streak carry rides as one extra operand/result, and the
            # metrics dict grows the per-client vector streams — the
            # vectors shard their client dim exactly like tau_up (they
            # are lane-local reads of it), stacked (K, n) under scan.
            import jax.numpy as jnp

            SDS = jax.ShapeDtypeStruct
            C = rc.n_clients
            specs["streak"] = SDS((C,), jnp.int32)
            streak_sh = shard_rules.telemetry_rule().shardings(
                mesh, {"streak": specs["streak"]})["streak"]
            lead = (int(scan_rounds),) if scan_rounds else ()
            vec = {
                "client_participation": SDS((*lead, C), jnp.float32),
                "client_uplink_bits": SDS((*lead, C), jnp.float32),
                "outage_streak": SDS((*lead, C), jnp.int32),
            }
            metrics_sh = dict(
                metrics_sh,
                weight_drift=rep,
                **shard_rules.telemetry_rule(
                    scan=bool(scan_rounds)).shardings(mesh, vec),
            )
            in_sh = (*in_sh, streak_sh)
            out_sh = (psh, ssh, st_sh, streak_sh, metrics_sh)
            lower_args = (*lower_args, specs["streak"])
        # the round's carry slots (params / server_state / agg_state, plus
        # the telemetry streak) alias their outputs 1:1 — consumers jit with
        # these to keep one live (n, d) generation instead of two
        # (DESIGN.md §14).  Taus, batches and A are never donated.
        round_fn.donate_argnums = (0, 1, 2) + ((7,) if telemetry else ())
        return round_fn, lower_args, in_sh, out_sh

    if specs["kind"] == "prefill":

        def prefill_step(params, batch):
            # serving prefill: populate activations, emit last-position
            # logits only (the full (B, S, V) tensor is never needed).
            return bundle.forward(params, batch)[:, -1, :]

        psh = shard_rules.param_shardings(cfg, specs["params"], mesh, fsdp=fsdp)
        bsh = jax.tree.map(
            lambda s: shard_rules.serve_batch_sharding(mesh, s.shape), specs["batch"]
        )
        B, S = specs["batch"]["tokens"].shape
        caxis = ca if len(ca) > 1 else ca[0]
        logits_spec = [None, "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None]
        if B % n_clients(mesh) == 0 and B > 1:
            logits_spec[0] = caxis
        out_sh = NamedSharding(mesh, P(*logits_spec))
        return prefill_step, (specs["params"], specs["batch"]), (psh, bsh), out_sh

    # decode
    def serve_step(params, cache, token, pos):
        return bundle.decode_step(params, cache, token, pos)

    psh = shard_rules.param_shardings(cfg, specs["params"], mesh, fsdp=fsdp)
    csh = shard_rules.cache_shardings(cfg, mesh, specs["cache"])
    tsh = shard_rules.serve_batch_sharding(mesh, specs["token"].shape)
    rep = NamedSharding(mesh, P())
    B = specs["token"].shape[0]
    caxis = ca if len(ca) > 1 else ca[0]
    lspec = [None, "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None]
    if B % n_clients(mesh) == 0 and B > 1:
        lspec[0] = caxis
    out_sh = (NamedSharding(mesh, P(*lspec)), csh)
    in_sh = (psh, csh, tsh, rep)
    lower_args = (specs["params"], specs["cache"], specs["token"], specs["pos"])
    return serve_step, lower_args, in_sh, out_sh
