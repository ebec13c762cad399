"""FL training launcher for the zoo architectures.

Runs ColRel federated training of any assigned architecture in one
process on the default device — one TPU chip, or the CPU — without a
mesh: the round function is jitted unsharded (``jax.jit``, no
shardings).  ``--smoke`` swaps in the architecture's reduced config.
The client-sharded round is lowered by ``repro/launch/steps.py`` for the
dry-run and run across chips by ``chip_smoke.py --four-chips``.

Connectivity is served by a :class:`~repro.channel.ChannelProcess`
(``--channel`` selects any preset from ``repro/configs/channels.py`` —
static i.i.d., bursty Gilbert–Elliott, mobility), and ``--chunk K``
switches to the compiled multi-round scan engine: K rounds per device
program, channel taus delivered as one bulk trace per chunk, metrics
synced to the host once per chunk (DESIGN.md §9).  ``--no-trace`` goes
one further: connectivity is drawn *inside* the compiled scan through
the channel's ``scan_sampler()``, so no tau tensors ever cross the host
boundary — only the packed gate state and a PRNG key carry over.

Observability (DESIGN.md §11): ``--metrics-dir DIR`` turns on the full
telemetry stack — the instrumented round (per-client participation /
bits-on-air vectors, device-resident outage-streak carry), a structured
``events.jsonl`` + ``rounds.csv`` + ``manifest.json`` in DIR, and
``vectors.npz`` with the stacked per-client histories at exit.
``--profile-dir`` captures a ``jax.profiler`` trace over
``--profile-rounds`` rounds; ``--log-every N`` prints cumulative
rounds/sec to stderr every N rounds.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --rounds 10 --smoke
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --rounds 64 --chunk 16 --channel markov --smoke \
        --metrics-dir /tmp/colrel_metrics
"""

from __future__ import annotations

import argparse
import contextlib
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import strategies as strategy_registry
from repro.ckpt import (
    CKPT_VERSION,
    AsyncCheckpointer,
    CheckpointWriter,
    PreemptionGuard,
    rng_from_json,
    rng_state_to_json,
)
from repro.compile_cache import enable_compile_cache
from repro.configs.base import get_arch
from repro.configs.channels import CHANNEL_PRESETS, make_channel
from repro.core import optimize_weights, topology
from repro.core.flatten import flat_spec
from repro.fl.round import (
    RoundConfig,
    make_async_round_fn,
    make_async_scan_round_fn,
    make_round_fn,
    make_scan_round_fn,
)
from repro.models import build, count_params
from repro.optim import sgd, sgd_momentum
from repro.telemetry import (
    CsvSummarySink,
    JsonlSink,
    MetricsLogger,
    ProfileWindow,
    RunManifest,
    ThroughputMeter,
    init_streak,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--n-clients", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4, help="per-client batch")
    ap.add_argument("--smoke", action="store_true",
                    help="the architecture's reduced config, not its full one")
    ap.add_argument("--aggregation", default="colrel",
                    choices=sorted(strategy_registry.available()),
                    help="aggregation strategy (repro.strategies registry)")
    ap.add_argument("--fused-kernel", action="store_true",
                    help="flatten-once fused Pallas aggregation (colrel only)")
    ap.add_argument("--async-mode", action="store_true",
                    help="asynchronous opportunistic relaying: blocked "
                         "clients' last updates age in a device staging "
                         "buffer and the PS applies gamma^age staleness "
                         "weights (DESIGN.md §13); wraps --aggregation")
    ap.add_argument("--staleness-gamma", type=float, default=0.9,
                    help="staleness decay base gamma for --async-mode")
    ap.add_argument("--channel", default="static",
                    choices=sorted(CHANNEL_PRESETS),
                    help="connectivity dynamics preset (repro/configs/channels.py)")
    ap.add_argument("--chunk", type=int, default=1,
                    help="rounds per compiled scan chunk (1 = per-round loop)")
    ap.add_argument("--no-trace", action="store_true",
                    help="draw connectivity inside the compiled scan "
                         "(channel.scan_sampler; no tau tensors on host)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--p-up", type=float, default=0.3)
    ap.add_argument("--p-c", type=float, default=0.8)
    ap.add_argument("--metrics-dir", default=None,
                    help="telemetry output dir (events.jsonl, rounds.csv, "
                         "manifest.json, vectors.npz); also enables the "
                         "instrumented round")
    ap.add_argument("--log-every", type=int, default=0,
                    help="print cumulative rounds/sec every N rounds")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace into this dir")
    ap.add_argument("--profile-rounds", type=int, default=4,
                    help="profiler window length in rounds (with --profile-dir)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (async sha256-committed "
                         "ckpt_NNNNNNNN.msgpack snapshots; DESIGN.md §12)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint cadence in rounds (0 = final only; "
                         "must be a multiple of --chunk)")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="committed checkpoints retained (keep-last-k GC)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest committed checkpoint in "
                         "--ckpt-dir and continue to --rounds")
    ap.add_argument("--segment-d", type=int, default=0,
                    help="d threshold for segment-streaming aggregation "
                         "(0 = monolithic stack; DESIGN.md §14)")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable carry-buffer donation (keeps two live "
                         "(n, d) generations; for A/B memory measurement)")
    args = ap.parse_args()
    enable_compile_cache()

    # the fused kernel only exists on the colrel path; refuse the
    # silently-inert combination rather than measuring the wrong code.
    if args.fused_kernel and args.aggregation != "colrel":
        ap.error(f"--fused-kernel requires --aggregation colrel "
                 f"(got {args.aggregation})")
    if args.chunk < 1 or args.rounds % args.chunk != 0:
        ap.error(f"--chunk must be positive and divide --rounds "
                 f"(got chunk={args.chunk}, rounds={args.rounds})")
    if args.no_trace and args.chunk == 1:
        ap.error("--no-trace runs through the scan engine; pass --chunk K > 1")
    if (args.resume or args.ckpt_every) and not args.ckpt_dir:
        ap.error("--resume and --ckpt-every require --ckpt-dir")
    if args.ckpt_every and args.ckpt_every % args.chunk != 0:
        # the scan engine only reaches the host at chunk boundaries, so a
        # misaligned cadence cannot be honored — refuse, don't approximate
        ap.error(f"--ckpt-every must be a multiple of --chunk (got "
                 f"ckpt_every={args.ckpt_every}, chunk={args.chunk}): "
                 f"checkpoints commit at chunk boundaries only")
    strategy = strategy_registry.get(
        args.aggregation,
        **({"fused": "kernel"} if args.fused_kernel
           else {"fused": "collapse"} if args.aggregation == "colrel" else {}),
    )
    if args.async_mode:
        if getattr(strategy, "is_async", False):
            ap.error(f"--aggregation {args.aggregation} is already "
                     f"asynchronous; drop --async-mode")
        strategy = strategy_registry.AsyncRelayStrategy(
            inner=strategy, gamma=args.staleness_gamma)
    # async strategies (via --async-mode or --aggregation async_colrel)
    # route through the age-carrying round builders
    is_async = getattr(strategy, "is_async", False)
    mk_round = make_async_round_fn if is_async else make_round_fn
    mk_scan = make_async_scan_round_fn if is_async else make_scan_round_fn

    arch = get_arch(args.arch)
    cfg = arch.smoke() if args.smoke else arch.full()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    print(f"{cfg.name}: {count_params(params):,} params on "
          f"{len(jax.devices())} device(s)")

    n = args.n_clients
    link_model = topology.fully_connected(n, args.p_up, p_c=args.p_c, rho=1.0)
    channel = make_channel(args.channel, link_model, n=n, seed=args.seed)
    res = optimize_weights(link_model, sweeps=20, fine_tune_sweeps=20)
    print(f"COPT-alpha: S {res.S_init:.2f} -> {res.S:.2f}")
    A = jnp.asarray(res.A, jnp.float32)

    rc = RoundConfig(n_clients=n, local_steps=args.local_steps,
                     mode="per_client", aggregation=strategy,
                     segment_d=args.segment_d)
    # carry-slot donation (DESIGN.md §14): params / server_state /
    # agg_state (and the telemetry streak / no-trace channel carry) alias
    # their outputs, so one (n, d) generation stays live instead of two.
    donate = not args.no_donate
    server_opt = sgd_momentum(1.0, beta=0.9)
    sstate = server_opt.init(params)
    agg_state = strategy.init_state(n, flat_spec(params).d)

    # checkpoint discovery (DESIGN.md §12): find the restore source up
    # front so the telemetry sinks open in the right mode and the
    # manifest can record provenance
    resume_state = None
    resume_path = None
    if args.resume:
        reader = CheckpointWriter(args.ckpt_dir, keep=args.ckpt_keep)
        step = reader.latest_step()
        if step is None:
            ap.error(f"--resume: no committed checkpoint in {args.ckpt_dir}")
        resume_path = reader.path_for(step)
        resume_state = reader.load(step)
        print(f"resuming from {resume_path} (round {step})")

    # observability wiring (DESIGN.md §11)
    telemetry = args.metrics_dir is not None
    logger = None
    if telemetry:
        mdir = pathlib.Path(args.metrics_dir)
        logger = MetricsLogger([JsonlSink(mdir / "events.jsonl",
                                          resume=args.resume),
                                CsvSummarySink(mdir / "rounds.csv",
                                               resume=args.resume)])
        RunManifest.collect(
            vars(args), strategy=strategy.name, channel=args.channel,
            codec=getattr(getattr(strategy, "codec", None), "name", None),
            arch=cfg.name, n_clients=n,
            resumed_from=resume_path,
        ).write(mdir)
        print(f"telemetry -> {mdir}")
    profile = (ProfileWindow(args.profile_dir, rounds=args.profile_rounds)
               if args.profile_dir else None)
    meter = ThroughputMeter()
    streak = init_streak(n) if telemetry else None
    last_tlog = 0

    def tick(r0: int, k: int, metrics) -> None:
        """Per-block telemetry: fence + clock, log, throughput line."""
        nonlocal last_tlog
        dt = meter.stop(k, fence=metrics)
        if profile is not None:
            profile.maybe_stop(r0 + k)
        if logger is not None:
            logger.log_timing(r0, k, dt)
            logger.log_rounds(r0, metrics, k)
        if args.log_every and r0 + k - last_tlog >= args.log_every:
            last_tlog = r0 + k
            print(f"[telemetry] round {r0 + k}: "
                  f"{meter.rounds_per_sec():.2f} rounds/s", file=sys.stderr)

    def finish() -> None:
        _stack.close()  # reinstall the original signal handlers
        if profile is not None:
            profile.close()
        if logger is not None:
            logger.save_vectors(pathlib.Path(args.metrics_dir) / "vectors.npz")
            logger.close()
            print(f"telemetry: {meter.total_rounds} rounds in "
                  f"{meter.total_seconds:.2f}s "
                  f"({meter.rounds_per_sec():.2f} rounds/s)")

    rng = np.random.default_rng(args.seed)
    V, S, B, T = cfg.vocab_size, args.seq_len, args.batch, args.local_steps

    # apply the restored state: model/optimizer/strategy tensors, the
    # channel generator, the batch rng, and the telemetry cursors
    r_start = 0
    ch_state = ch_rng = None  # no-trace scan carry (set below when used)
    if resume_state is not None:
        if resume_state.get("version") != CKPT_VERSION:
            sys.exit(f"checkpoint version {resume_state.get('version')!r} != "
                     f"supported {CKPT_VERSION}")
        for field, want in (("kind", "launch"), ("strategy", strategy.name),
                            ("arch", cfg.name)):
            got = resume_state.get(field)
            if got != want:
                sys.exit(f"checkpoint {field} mismatch: saved {got!r}, "
                         f"launching {want!r}")
        if (resume_state.get("no_trace") is not None) != args.no_trace:
            sys.exit("checkpoint --no-trace mode does not match this launch; "
                     "resume with the same connectivity flags")
        params = jax.tree.map(jnp.asarray, resume_state["params"])
        sstate = jax.tree.map(jnp.asarray, resume_state["server_state"])
        agg_state = strategy.restore_state(resume_state["agg_state"])
        rng = rng_from_json(resume_state["rng"])
        channel.restore_state(resume_state["channel"])
        if telemetry:
            if resume_state.get("streak") is None:
                sys.exit("checkpoint carries no telemetry state but "
                         "--metrics-dir is set; resume with matching flags")
            streak = jnp.asarray(resume_state["streak"], jnp.int32)
            if logger is not None and resume_state.get("metrics") is not None:
                logger.restore_state(resume_state["metrics"])
        r_start = int(resume_state["round"])
        if r_start >= args.rounds:
            print(f"checkpoint already at round {r_start} >= --rounds "
                  f"{args.rounds}; nothing to do")
            return
        if r_start % args.chunk != 0:
            sys.exit(f"checkpoint round {r_start} is not a --chunk "
                     f"{args.chunk} boundary")
        last_tlog = r_start

    # async checkpointing + preemption safety (DESIGN.md §12): snapshots
    # enqueue at round boundaries and serialize on the writer thread,
    # overlapped with the next block's device compute; SIGTERM/SIGINT
    # latches and the loop drains + commits a final checkpoint at the
    # next boundary instead of dying mid-write.
    ckpt = (AsyncCheckpointer(args.ckpt_dir, keep=args.ckpt_keep,
                              copy_arrays=donate)
            if args.ckpt_dir else None)
    ckpt_last = -1
    _stack = contextlib.ExitStack()
    guard = _stack.enter_context(PreemptionGuard())

    def capture(r_next: int) -> dict:
        """The launcher's complete run state at round boundary r_next."""
        return {
            "version": CKPT_VERSION, "kind": "launch",
            "round": int(r_next), "strategy": strategy.name,
            "arch": cfg.name,
            "params": params, "server_state": sstate,
            "agg_state": strategy.checkpoint_state(agg_state),
            "rng": rng_state_to_json(rng),
            "channel": channel.checkpoint_state(),
            "no_trace": ({"state": ch_state, "rng": ch_rng}
                         if args.no_trace else None),
            "streak": streak,
            "metrics": logger.checkpoint_state() if logger else None,
        }

    def boundary(r_next: int) -> bool:
        """Periodic checkpoint + preemption check at a round boundary;
        True = stop the loop (``final_ckpt`` commits the last state)."""
        nonlocal ckpt_last
        if (ckpt is not None and args.ckpt_every
                and r_next % args.ckpt_every == 0 and r_next != ckpt_last):
            ckpt.save(r_next, capture(r_next))
            ckpt_last = r_next
        if guard.triggered:
            print(f"[ckpt] preempted (signal {guard.signum}) at round "
                  f"{r_next}; committing final checkpoint", file=sys.stderr)
            return True
        return False

    def final_ckpt(r_next: int) -> None:
        """Drain the async writer; commit a final checkpoint if the last
        boundary was not already saved."""
        nonlocal ckpt_last
        if ckpt is None:
            return
        if r_next != ckpt_last:
            ckpt.save(r_next, capture(r_next))
            ckpt_last = r_next
        ckpt.close()
        print(f"[ckpt] committed round {r_next} -> {args.ckpt_dir}")

    def make_batches(lead: tuple) -> dict:
        toks = rng.integers(0, V, size=(*lead, S + 1), dtype=np.int32)
        batches = {"tokens": jnp.asarray(toks[..., :-1]),
                   "labels": jnp.asarray(toks[..., 1:])}
        if cfg.frontend_tokens:
            batches["prefix"] = jnp.asarray(
                rng.normal(size=(*lead, cfg.frontend_tokens, cfg.d_model)),
                cfg.jdtype)
        return batches

    don_traced = ((0, 1, 2) + ((7,) if telemetry else ())) if donate else ()
    don_sampled = ((0, 1, 2, 4, 5) + ((7,) if telemetry else ())) if donate else ()

    if args.chunk == 1:
        round_fn = jax.jit(mk_round(bundle.loss_fn, sgd(0.25), server_opt,
                                    rc, telemetry=telemetry),
                           donate_argnums=don_traced)
        done = r_start
        for r in range(r_start, args.rounds):
            if profile is not None:
                profile.maybe_start(r)
            meter.start()
            tau_up, tau_dd = channel.tau_for_round(r)
            batches = make_batches((n, T, B))
            t0 = time.perf_counter()
            fn_args = (params, sstate, agg_state, batches,
                       jnp.asarray(tau_up, jnp.float32),
                       jnp.asarray(tau_dd, jnp.float32), A)
            if telemetry:
                params, sstate, agg_state, streak, metrics = round_fn(
                    *fn_args, streak)
            else:
                params, sstate, agg_state, metrics = round_fn(*fn_args)
            jax.block_until_ready(metrics["loss"])
            tick(r, 1, metrics)
            stale = (f"stale={float(metrics['stale_frac']):.2f}  "
                     if "stale_frac" in metrics else "")
            print(f"round {r:3d}  loss={float(metrics['loss']):.4f}  "
                  f"participants={int(metrics['participation'])}/{n}  "
                  f"|delta|={float(metrics['delta_norm']):.3f}  {stale}"
                  f"({time.perf_counter() - t0:.2f}s)")
            done = r + 1
            if boundary(done):
                break
        final_ckpt(done)
        finish()
        return

    # chunked scan engine: K rounds per device program, one host sync per
    # chunk; taus come from the channel's bulk trace service — or, with
    # --no-trace, are drawn inside the compiled scan (channel gate state +
    # PRNG key carried across chunks; no tau tensors ever on host)
    K = args.chunk
    if args.no_trace:
        if not hasattr(channel, "scan_sampler"):
            ap.error(f"--no-trace needs a channel with scan_sampler() "
                     f"(--channel {args.channel} cannot sample in-scan)")
        init_fn, sample_fn = channel.scan_sampler()
        scan_fn = jax.jit(mk_scan(
            bundle.loss_fn, sgd(0.25), server_opt, rc,
            channel_sampler=sample_fn, telemetry=telemetry),
            donate_argnums=don_sampled)
        ch_rng, sub = jax.random.split(jax.random.PRNGKey(args.seed))
        ch_state = init_fn(sub)
        if resume_state is not None:
            nt = resume_state["no_trace"]
            ch_state = jax.tree.map(jnp.asarray, nt["state"])
            ch_rng = jnp.asarray(nt["rng"])
    else:
        scan_fn = jax.jit(mk_scan(bundle.loss_fn, sgd(0.25),
                                  server_opt, rc,
                                  telemetry=telemetry),
                          donate_argnums=don_traced)
    done = r_start
    for c in range(r_start // K, args.rounds // K):
        r0 = c * K
        if profile is not None:
            profile.maybe_start(r0)
        meter.start()
        batches = make_batches((K, n, T, B))
        t0 = time.perf_counter()
        if args.no_trace:
            if telemetry:
                (params, sstate, agg_state, ch_state, ch_rng, streak,
                 metrics) = scan_fn(params, sstate, agg_state, batches,
                                    ch_state, ch_rng, A, streak)
            else:
                params, sstate, agg_state, ch_state, ch_rng, metrics = scan_fn(
                    params, sstate, agg_state, batches, ch_state, ch_rng, A)
        else:
            tau_up, tau_dd = channel.trace(r0, K)
            fn_args = (params, sstate, agg_state, batches,
                       jnp.asarray(tau_up, jnp.float32),
                       jnp.asarray(tau_dd, jnp.float32), A)
            if telemetry:
                params, sstate, agg_state, streak, metrics = scan_fn(
                    *fn_args, streak)
            else:
                params, sstate, agg_state, metrics = scan_fn(*fn_args)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        tick(r0, K, metrics)
        loss = np.asarray(metrics["loss"])
        part = np.asarray(metrics["participation"])
        bits = float(np.sum(np.asarray(metrics["uplink_bits"])))
        print(f"rounds {r0:3d}-{r0 + K - 1:3d}  "
              f"loss={loss[0]:.4f}->{loss[-1]:.4f}  "
              f"participants(mean)={part.mean():.1f}/{n}  "
              f"uplink={bits / 8e6:.1f} MB  "
              f"({dt:.2f}s, {K / dt:.1f} rounds/s)")
        done = r0 + K
        if boundary(done):
            break
    final_ckpt(done)
    finish()


if __name__ == "__main__":
    main()
