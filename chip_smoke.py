#!/usr/bin/env python3
"""On-chip smoke test: the paper's ResNet-20 ColRel trainer on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # client-sharded round on a 2x2 host

With no arguments, four phases run in this one process:

1. device check: the jax version and devices; anything but a TPU exits
   non-zero before any other work;
2. kernels vs. float64: the aggregation kernels of ``repro.kernels.ops``
   on seeded ``(10, d)`` inputs at ResNet-20 width, each checked against
   a NumPy float64 evaluation of its formula, with the partial last
   ``block_d`` tile checked on its own;
3. trainer: 16 rounds of ``build_experiment(ExperimentSpec(...))`` ->
   ``FLTrainer.run`` at paper width (fig2b, non-IID s=3, colrel with
   ``fused="kernel"``, bursty Markov channel, chunks of 8 rounds), with
   the Pallas call required in the compiled chunk program;
4. path agreement: one round from the same seeded state and taus with
   ``fused="kernel"`` and with the faithful ``fused=False`` path.

``--four-chips`` runs only the client-sharded round: 8 paper-width
clients over a ``(4, 1)`` ("data", "model") mesh, compared with the same
round on device 0.

Findings print line by line.  The last line of stdout is one JSON object
``{"ok": true, "device": {...}}``, printed only when every check passed;
a failed check or any exception exits non-zero.  The phases are
importable functions; ``tests/test_chip_smoke.py`` runs them on the CPU
at reduced width so they cannot rot between chip runs.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# Kernels accumulate in float32 at Precision.HIGHEST: against a float64
# evaluation only float32 rounding remains (~1e-7 of max|ref| for n=10).
KERNEL_BOUND = 1e-5
# The faithful path contracts with jnp.tensordot at the TPU's default
# matmul precision (bfloat16 passes, ~2^-8 relative per product); the
# kernel runs at HIGHEST.  On the CPU both are float32-exact.
PATH_BOUND = 1e-2
# A sharding fault (a client dropped, doubled or sent to the wrong shard)
# moves the server delta of n = 8 clients by O(1/n).  Both four-chip
# rounds are traced at "highest" matmul precision, yet they accumulate
# the convolutions in different orders, and 8 local SGD steps through
# ReLUs amplify float32 rounding: the phase prints that floor, the same
# round on device 0 from params scaled by 1 +- 2^-23, next to the check.
SHARD_BOUND = 1e-2
BLOCK_D = 2048  # RoundConfig.fused_block_d, the main path's tile


class SmokeFailure(RuntimeError):
    """A check of the smoke test did not hold."""


def check(name: str, value: float, bound: float) -> float:
    ok = bool(np.isfinite(value)) and value <= bound
    print(f"  {name}: {value:.3e} (bound {bound:.0e}) {'ok' if ok else 'FAIL'}")
    if not ok:
        raise SmokeFailure(f"{name} = {value!r} exceeds its bound {bound!r}")
    return value


def device_check(platform: str = "tpu") -> dict:
    """Print the jax version and devices; refuse any other platform."""
    devices = jax.devices()
    d0 = devices[0]
    print(f"jax {jax.__version__}; devices: {devices}")
    print(f"platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)}")
    if d0.platform != platform:
        raise SmokeFailure(f"platform is {d0.platform!r}, not {platform!r}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def paper_spec(**overrides):
    """The main path's experiment: paper ResNet-20, T=8, batch 64."""
    from repro.fl import ExperimentSpec

    spec = ExperimentSpec(
        model="cifar_cnn_full", topology="fig2b", non_iid_s=3,
        strategy="colrel", strategy_options={"fused": "kernel"},
        channel="markov", rounds=16, chunk=8, seed=0)
    return spec.replace(**overrides)


def model_segments(model: str = "cifar_cnn_full") -> list:
    """Per-leaf sizes of the model's flat update (one segment per leaf)."""
    from repro.configs import colrel_paper
    from repro.core.flatten import flat_spec
    from repro.models import build

    setup = (colrel_paper.full() if model == "cifar_cnn_full"
             else colrel_paper.reduced())
    bundle = build(setup.cnn)
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    return list(flat_spec(shapes).sizes)


def _rel_err(out, ref, cols=None) -> float:
    out = np.asarray(out, np.float64)
    if cols is not None:
        out, ref = out[..., cols], ref[..., cols]
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


def _tail(d: int, block_d: int = BLOCK_D) -> slice:
    """Columns of the last grid tile of a width-``d`` stream."""
    return slice((d - 1) // block_d * block_d, d)


def kernel_phase(segments, n: int = 10, seed: int = 0,
                 bound: float = KERNEL_BOUND) -> dict:
    """Each main-path kernel through its public ``ops`` wrapper against a
    float64 NumPy evaluation of the same formula; returns the errors."""
    from repro.kernels import ops

    d = int(sum(segments))
    print(f"[kernels] n={n} d={d} segments={len(segments)} "
          f"block_d={BLOCK_D} last tile={_tail(d)}")
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d), dtype=np.float32)
    buf = rng.standard_normal((n, d), dtype=np.float32)
    q = rng.integers(-127, 128, size=(n, d), dtype=np.int8)
    scale = rng.uniform(1e-3, 1e-2, size=n).astype(np.float32)
    A = rng.uniform(0.0, 1.0, size=(n, n)).astype(np.float32)
    tau_up = (rng.random(n) < 0.7).astype(np.float32)
    tau_dd = (rng.random((n, n)) < 0.6).astype(np.float32)
    tau_up[0] = 1.0  # at least one uplink, so no reference is all zeros

    mask = A.astype(np.float64) * tau_dd.astype(np.float64).T
    w = tau_up.astype(np.float64) @ mask / n
    X64 = X.astype(np.float64)
    tilde = mask @ X64
    t = tau_up.astype(np.float64)[:, None]
    contrib = t * tilde + (1.0 - t) * buf.astype(np.float64)
    dev = [jnp.asarray(a) for a in (A, tau_up, tau_dd)]
    Xd = jnp.asarray(X)
    errs = {}

    def report(name, out, ref, tail_cols):
        errs[name] = check(f"{name} rel err", _rel_err(out, ref), bound)
        errs[name + "/tail"] = check(
            f"{name} last-tile rel err", _rel_err(out, ref, tail_cols), bound)

    tail = _tail(d)
    report("fused_aggregate", ops.fused_aggregate(*dev, Xd), w @ X64, tail)

    # row_stream over the model's own leaf segments, against the f32 row
    # the kernel consumes, so the check isolates the stream
    w32 = ops.collapsed_weight_row(*dev)
    w32_64 = np.asarray(w32, np.float64)
    offs = np.cumsum([0] + list(segments))
    outs, tails = [], []
    for a, b in zip(offs[:-1], offs[1:]):
        outs.append(np.asarray(ops.row_stream(w32, Xd[:, a:b])))
        if (b - a) > BLOCK_D and (b - a) % BLOCK_D:
            tails.extend(range(a + _tail(b - a).start, b))
    ref = w32_64 @ X64
    errs["row_stream"] = check("row_stream rel err (all segments)",
                               _rel_err(np.concatenate(outs), ref), bound)
    if tails:
        errs["row_stream/tail"] = check(
            f"row_stream last-tile rel err ({len(tails)} cols)",
            _rel_err(np.concatenate(outs), ref, np.asarray(tails)), bound)

    delta, contrib_out = ops.fused_memory_update(*dev, Xd, jnp.asarray(buf))
    report("fused_memory_update.delta", delta, contrib.sum(0) / n, tail)
    report("fused_memory_update.contrib", contrib_out, contrib, tail)

    report("fused_dequant_aggregate",
           ops.fused_dequant_aggregate(*dev, jnp.asarray(q), jnp.asarray(scale)),
           (w * scale.astype(np.float64)) @ q.astype(np.float64), tail)

    # one cluster of all n clients: Mosaic needs a block's sublane dim to
    # be a multiple of 8 or the whole array dim, and no split of n = 10
    # into equal clusters gives either
    report("block_fused_aggregate",
           ops.block_fused_aggregate(dev[0][None], dev[1], dev[2][None], Xd),
           w @ X64, tail)
    return errs


def _flat(tree) -> np.ndarray:
    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.tree.leaves(tree)])


def trainer_phase(spec) -> dict:
    """``spec.rounds`` rounds through ``build_experiment`` -> ``run``;
    checks finite losses and, on a TPU, the Pallas call in the chunk
    program (the CPU contracts in jnp)."""
    from repro.fl import build_experiment

    print(f"[trainer] model={spec.model} topology={spec.topology} "
          f"strategy={spec.strategy} {dict(spec.strategy_options)} "
          f"channel={spec.channel} rounds={spec.rounds} chunk={spec.chunk}")
    exp = build_experiment(spec)
    tr = exp.trainer
    exp.run()
    exp.close()
    chunks = tr.meter.chunks
    print(f"  first chunk ({chunks[0]['rounds']} rounds): "
          f"{chunks[0]['seconds']:.3f} s, compile plus run")
    rate = None
    if len(chunks) > 1:
        rate = chunks[1]["rounds_per_sec"]
        label = ("first chip run, not a benchmark"
                 if jax.devices()[0].platform == "tpu"
                 else "not a device metric")
        print(f"  second chunk: {rate:.3f} rounds/s ({label})")
    else:
        print("  second chunk: not measured (one chunk)")
    losses = np.asarray(exp.log.loss, np.float64)
    for r, loss in enumerate(losses):
        print(f"  round {r + 1:2d} loss {loss:.6f}")
    if losses.size != spec.rounds or not np.all(np.isfinite(losses)):
        raise SmokeFailure(f"expected {spec.rounds} finite losses, got {losses}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"  peak_bytes_in_use: "
          f"{peak if peak is not None else 'not reported by this backend'}")

    t0 = time.perf_counter()
    hlo = tr.lower_chunk(spec.chunk).compile().as_text()
    has_pallas = "tpu_custom_call" in hlo
    print(f"  chunk program compiled again for inspection in "
          f"{time.perf_counter() - t0:.3f} s; tpu_custom_call in HLO: "
          f"{has_pallas}")
    if jax.devices()[0].platform == "tpu" and not has_pallas:
        raise SmokeFailure("the compiled chunk program holds no Pallas call")
    return {"losses": losses, "first_chunk_s": chunks[0]["seconds"],
            "rounds_per_s": rate, "peak_bytes": peak, "pallas": has_pallas}


def _one_round_delta(spec):
    from repro.fl import build_experiment

    exp = build_experiment(spec.replace(rounds=1, chunk=1))
    p0 = _flat(jax.device_get(exp.params))
    exp.run()
    exp.close()
    return _flat(jax.device_get(exp.params)) - p0, exp.log


def path_agreement_phase(spec, bound: float = PATH_BOUND) -> float:
    """One round from the same seeded state and taus with the kernel path
    and the faithful per-leaf path; relative L2 of the server deltas."""
    print("[path agreement] fused='kernel' vs fused=False, one round")
    dk, log_k = _one_round_delta(spec.replace(
        strategy_options={"fused": "kernel"}))
    df, log_f = _one_round_delta(spec.replace(
        strategy_options={"fused": False}))
    same = (log_k.participation == log_f.participation
            and log_k.weight_sums == log_f.weight_sums)
    print(f"  same taus (participation {log_k.participation}, weight sum "
          f"{log_k.weight_sums}): {same}; losses {log_k.loss} vs {log_f.loss}")
    if not same:
        raise SmokeFailure("the two runs did not see the same round")
    return check("server delta rel L2 (kernel vs faithful)",
                 float(np.linalg.norm(dk - df) / np.linalg.norm(df)), bound)


def four_chip_phase(model: str = "cifar_cnn_full", n: int = 8,
                    seed: int = 0, bound: float = SHARD_BOUND,
                    local_steps=None, batch_size=None) -> float:
    """One client-sharded round over a (4, 1) ("data", "model") mesh vs
    the same round on device 0 (paper T and batch unless overridden)."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import colrel_paper
    from repro.core import optimize_weights, sample_round, topology
    from repro.core.flatten import flat_spec
    from repro.data import synthetic_cifar
    from repro.dist import make_debug_mesh
    from repro.fl.round import RoundConfig, make_round_fn
    from repro.launch import sharding as shard_rules
    from repro.models import build
    from repro.optim import sgd, sgd_momentum
    from repro.strategies import ColRelStrategy

    devices = jax.devices()
    if len(devices) != 4:
        raise SmokeFailure(f"the four-chip phase needs 4 devices, "
                           f"found {len(devices)}")
    setup = (colrel_paper.full() if model == "cifar_cnn_full"
             else colrel_paper.reduced())
    T = local_steps or setup.local_steps
    B = batch_size or setup.batch_size
    bundle = build(setup.cnn)
    params = bundle.init(jax.random.PRNGKey(seed))
    d = flat_spec(params).d
    print(f"[four chips] model={model} n={n} d={d} T={T} batch={B} "
          f"mesh=(4, 1) ('data', 'model')")
    link = topology.fully_connected(n, 0.5, p_c=0.8)
    A = optimize_weights(link, sweeps=5, fine_tune_sweeps=5).A
    tau_up, tau_dd = sample_round(link, np.random.default_rng(seed))
    images, labels = synthetic_cifar(n=n * T * B, seed=seed + 1)
    batches = {"images": images.reshape(n, T, B, *images.shape[1:]),
               "labels": labels.reshape(n, T, B)}
    server = sgd_momentum(1.0, beta=setup.server_momentum)
    args = (params, server.init(params), (), batches,
            *(np.asarray(x, np.float32) for x in (tau_up, tau_dd, A)))
    rc = RoundConfig(n_clients=n, local_steps=T,
                     aggregation=ColRelStrategy(fused="kernel"))
    client_opt = sgd(setup.lr, weight_decay=setup.weight_decay)

    mesh = make_debug_mesh(data=4, model=1)
    rep = NamedSharding(mesh, P())
    psh = shard_rules.replicated(mesh, params)
    round_rule = shard_rules.fl_round_rule().shardings(
        mesh, {"tau_up": args[4], "tau_dd": args[5], "A": args[6]})
    in_sh = (psh, {"step": rep, "m": psh}, (),
             shard_rules.train_batch_shardings(mesh, "per_client", batches),
             round_rule["tau_up"], round_rule["tau_dd"], round_rule["A"])
    sharded_fn = make_round_fn(bundle.loss_fn, client_opt, server,
                               dataclasses.replace(rc, spmd_axes=("data",)))
    ref_fn = jax.jit(make_round_fn(bundle.loss_fn, client_opt, server, rc))
    signs = np.random.default_rng(seed)
    nudged = jax.tree.map(lambda x: x * (1 + np.float32(2**-23) * signs.choice(
        np.float32([-1, 1]), size=x.shape)), params)
    with jax.default_matmul_precision("highest"):
        ref = ref_fn(*jax.device_put(args, devices[0]))
        ref_nudged = ref_fn(*jax.device_put((nudged, *args[1:]), devices[0]))
        with jax.set_mesh(mesh):
            compiled = jax.jit(sharded_fn, in_shardings=in_sh).lower(
                *args).compile()
    out = compiled(*jax.device_put(args, in_sh))
    hlo = compiled.as_text()
    shard, full = f"f32[{n // 4},{d}]", f"f32[{n},{d}]"
    print(f"  all-reduce in sharded HLO: {'all-reduce' in hlo}; "
          f"stack per device {shard}: {shard in hlo}; "
          f"whole stack {full}: {full in hlo}")
    if "all-reduce" not in hlo:
        raise SmokeFailure("the sharded round holds no all-reduce")
    if shard not in hlo or full in hlo:
        raise SmokeFailure("the (n, d) update stack is not split over the "
                           "client axis")
    p0 = _flat(params)
    d_ref = _flat(jax.device_get(ref[0])) - p0
    d_out = _flat(jax.device_get(out[0])) - p0
    d_nudged = _flat(jax.device_get(ref_nudged[0])) - _flat(nudged)
    print(f"  loss device 0 {float(ref[3]['loss']):.6f} "
          f"sharded {float(out[3]['loss']):.6f}")
    print(f"  rounding floor, device 0 from params scaled by 1 +- 2^-23: "
          f"server delta rel L2 "
          f"{np.linalg.norm(d_nudged - d_ref) / np.linalg.norm(d_ref):.3e}")
    return check("server delta rel L2 (sharded vs device 0)",
                 float(np.linalg.norm(d_out - d_ref) / np.linalg.norm(d_ref)),
                 bound)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the client-sharded round on 4 chips")
    args = ap.parse_args(argv)
    try:
        device = device_check("tpu")
        from repro.compile_cache import enable_compile_cache

        print(f"compile cache: {enable_compile_cache()}")
        if args.four_chips:
            four_chip_phase()
        else:
            kernel_phase(model_segments())
            trainer_phase(paper_spec())
            path_agreement_phase(paper_spec())
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
