import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against ShapeDtypeStruct inputs, capture memory / cost /
collective analyses, and emit the JSON records the roofline report reads.

MUST be invoked as its own process (the XLA flag above must precede any
jax initialization):

    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import math
import time
import traceback
from pathlib import Path

import jax

from repro.configs.base import CLI_ALIASES, INPUT_SHAPES, get_arch, supported_shapes
from repro import strategies as strategy_registry
from repro.launch.mesh import make_production_mesh, n_clients
from repro.launch.roofline import (
    collective_bytes,
    count_params_split,
    model_flops,
    roofline_terms,
)
from repro.launch.specs import DRYRUN_LOCAL_STEPS
from repro.launch.steps import build_step

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def _arg_bytes(lower_args, in_sh) -> tuple:
    """(global, per-shard) argument bytes from the lowering specs and the
    requested input shardings.  XLA's ``memory_analysis`` prices arguments
    at their unpartitioned size on some backends, so client-axis-sharded
    inputs (the (n, d) stacks, tau traces, block tensors) would read as
    fully replicated; ``Sharding.shard_shape`` gives the true per-device
    slice."""
    args = jax.tree.leaves(lower_args)
    shs = jax.tree.leaves(in_sh)
    assert len(args) == len(shs), (len(args), len(shs))
    total = per_shard = 0
    for a, s in zip(args, shs):
        nbytes = math.prod(a.shape) * a.dtype.itemsize
        total += nbytes
        per_shard += math.prod(s.shard_shape(a.shape)) * a.dtype.itemsize
    return int(total), int(per_shard)


def _tokens_for(shape_name: str, fl_mode: str) -> float:
    s = INPUT_SHAPES[shape_name]
    if s.kind == "train":
        t = 1 if fl_mode == "weighted_grad" else DRYRUN_LOCAL_STEPS
        return float(t * s.global_batch * s.seq_len)
    if s.kind == "prefill":
        return float(s.global_batch * s.seq_len)
    return float(s.global_batch)  # decode: one token per sequence


# ---------------------------------------------------------------------------
# Depth probes: XLA's cost_analysis counts a while-loop body ONCE, so rolled
# layer scans undercount FLOPs/bytes/collectives by ~n_layers.  Every arch
# here is linear in depth, so two shallow lowerings (1 and 2 depth units)
# give the exact per-unit increment:  cost(L) = cost(2) + (L-2) * delta.
# ---------------------------------------------------------------------------


def _depth_units(cfg) -> int:
    if cfg.arch_type == "hybrid":
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers


def _with_depth(cfg, k: int):
    # scan_unroll=True: probe lowerings unroll every structural scan so
    # cost_analysis sees each body exactly once per execution.
    if cfg.arch_type == "hybrid":
        return cfg.replace(n_layers=k * cfg.attn_every, scan_unroll=True)
    if cfg.arch_type in ("encdec", "audio"):
        return cfg.replace(n_layers=k, n_encoder_layers=k, scan_unroll=True)
    return cfg.replace(n_layers=k, scan_unroll=True)


def _probe_costs(arch_id, shape_name, mesh, aggregation, fl_mode, cfg, k) -> dict:
    step, lower_args, in_sh, out_sh = build_step(
        arch_id, shape_name, mesh, aggregation=aggregation, fl_mode=fl_mode,
        cfg_override=_with_depth(cfg, k),
    )
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            step, in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=getattr(step, "donate_argnums", ()),
        ).lower(*lower_args).compile()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(sum(coll.values())),
        "coll_by_op": coll,
    }


def run_one(arch_id: str, shape_name: str, *, multi_pod: bool,
            aggregation: str = "colrel",
            fl_mode: str | None = None, tag: str = "",
            probe: bool = True, static_window: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    cfg_override = None
    if static_window:
        cfg_override = get_arch(arch_id).full().replace(static_window_pattern=True)
    t0 = time.time()
    step, lower_args, in_sh, out_sh = build_step(
        arch_id, shape_name, mesh, aggregation=aggregation, fl_mode=fl_mode,
        cfg_override=cfg_override,
    )
    with jax.set_mesh(mesh):
        # donate the carry slots the step declares (train rounds): the
        # lowering then prices params/server_state/agg_state once via
        # input-output aliasing instead of twice (alias_size_in_bytes
        # shows the reclaimed residency)
        lowered = jax.jit(
            step, in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=getattr(step, "donate_argnums", ()),
        ).lower(*lower_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    coll = collective_bytes(hlo)
    coll_total = float(sum(coll.values()))
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))

    # depth-probe correction for rolled scans
    cfg0 = cfg_override if cfg_override is not None else get_arch(arch_id).full()
    probe_info = None
    if probe:
        p1 = _probe_costs(arch_id, shape_name, mesh, aggregation, fl_mode, cfg0, 1)
        p2 = _probe_costs(arch_id, shape_name, mesh, aggregation, fl_mode, cfg0, 2)
        L = _depth_units(cfg0)
        # clamp: XLA may choose different collective strategies at different
        # depths, which can make a raw difference negative; the extrapolation
        # is never allowed below the 2-layer measurement itself.
        ext = lambda a, b: max(b + (L - 2) * (b - a), b)
        flops = ext(p1["flops"], p2["flops"])
        byts = ext(p1["bytes"], p2["bytes"])
        coll = {
            op: ext(p1["coll_by_op"][op], p2["coll_by_op"][op])
            for op in p2["coll_by_op"]
        }
        coll_total = float(sum(coll.values()))
        probe_info = {"units": L, "probe1": p1, "probe2": p2,
                      "rolled_flops": float(cost.get("flops", 0.0))}

    terms = roofline_terms(flops, byts, coll_total)

    from repro.models import build as build_model

    pcounts = count_params_split(
        jax.eval_shape(lambda k: build_model(cfg0).init(k), jax.random.PRNGKey(0)),
        cfg0.n_experts, cfg0.top_k,
    )
    kind = INPUT_SHAPES[shape_name].kind
    mflops = model_flops("train" if kind == "train" else "serve",
                         pcounts["active"],
                         _tokens_for(shape_name, fl_mode or cfg0.fl_mode))
    mflops_per_chip = mflops / chips

    mem_attrs = {}
    for a in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(mem, a, None)
        if v is not None:
            mem_attrs[a] = int(v)
    arg_global, arg_shard = _arg_bytes(lower_args, in_sh)
    mem_attrs["argument_bytes_global"] = arg_global
    mem_attrs["argument_bytes_per_shard"] = arg_shard
    raw = mem_attrs.get("argument_size_in_bytes")
    if raw is not None and arg_shard < arg_global and raw >= arg_global:
        # XLA counted sharded arguments at full (replicated) size — report
        # the true per-shard residency; the raw figure stays for auditing.
        mem_attrs["argument_size_in_bytes_reported"] = raw
        mem_attrs["argument_size_in_bytes"] = arg_shard

    record = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "n_clients": n_clients(mesh),
        "aggregation": strategy_registry.canonical_name(aggregation),
        "fl_mode": fl_mode or cfg0.fl_mode,
        "tag": tag,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": byts,
        "collective_bytes_per_chip": coll_total,
        "collectives": coll,
        "roofline": terms,
        "params_total": pcounts["total"],
        "params_active": pcounts["active"],
        "model_flops_per_chip": mflops_per_chip,
        "useful_flop_ratio": (mflops_per_chip / flops) if flops else None,
        "memory_analysis": mem_attrs,
        "probe": probe_info,
    }
    print(f"== {arch_id} x {shape_name} x {record['mesh']} "
          f"(agg={record['aggregation']}, mode={record['fl_mode']}{', ' + tag if tag else ''})")
    print(f"   memory_analysis: {mem_attrs}")
    print(f"   cost_analysis: flops/chip={flops:.3e} bytes/chip={byts:.3e}")
    print(f"   collectives/chip: {coll_total:.3e} B  breakdown={ {k: f'{v:.2e}' for k, v in coll.items() if v} }")
    print(f"   roofline: compute={terms['compute_s']:.4f}s memory={terms['memory_s']:.4f}s "
          f"collective={terms['collective_s']:.4f}s -> {terms['bottleneck']}")
    print(f"   useful_flop_ratio={record['useful_flop_ratio'] and round(record['useful_flop_ratio'], 3)} "
          f"lower={t_lower:.1f}s compile={t_compile:.1f}s", flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (CLI form) or 'all'")
    ap.add_argument("--shape", default=None, help="input shape name or 'all'")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="every supported arch x shape")
    ap.add_argument("--aggregation", default="colrel",
                    choices=sorted(strategy_registry.available()))
    ap.add_argument("--fl-mode", default=None,
                    choices=[None, "per_client", "client_sequential",
                             "weighted_grad", "weighted_flat"])
    ap.add_argument("--tag", default="", help="label recorded for perf iterations")
    ap.add_argument("--static-window", action="store_true",
                    help="unrolled static local/global pattern (banded attention)")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = list(CLI_ALIASES) if (args.all or args.arch in (None, "all")) else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_fail = 0
    for arch in archs:
        shapes = supported_shapes(arch) if (args.all or args.shape in (None, "all")) \
            else [args.shape]
        for shape in shapes:
            if shape not in supported_shapes(arch):
                print(f"-- skipping unsupported {arch} x {shape}")
                continue
            for mp in meshes:
                mesh_tag = "2x16x16" if mp else "16x16"
                suffix = f"_{args.tag}" if args.tag else ""
                fname = out_dir / f"{arch}_{shape}_{mesh_tag}_{args.aggregation}{suffix}.json"
                if args.skip_existing and fname.exists():
                    print(f"-- cached {fname.name}")
                    n_ok += 1
                    continue
                try:
                    # cost probes only on the single-pod mesh (the roofline
                    # table is single-pod; multi-pod proves lowering+memory)
                    rec = run_one(arch, shape, multi_pod=mp,
                                  aggregation=args.aggregation,
                                  fl_mode=args.fl_mode, tag=args.tag,
                                  probe=not mp, static_window=args.static_window)
                    fname.write_text(json.dumps(rec, indent=1))
                    n_ok += 1
                except Exception:
                    n_fail += 1
                    print(f"!! FAILED {arch} x {shape} x {mesh_tag}")
                    traceback.print_exc()
    print(f"\nDRY-RUN SUMMARY: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
