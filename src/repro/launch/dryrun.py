"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) case.

MUST run as its own process: the first two lines force 512 host platform
devices before jax initialises.  Never import this from tests/benches (they
need the real 1-device view).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --out results.json
"""
import os

_FLAG = "--xla_force_host_platform_device_count=512"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " " + _FLAG

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs.base import SHAPES                      # noqa: E402
from repro.configs.registry import ARCHS, shape_applicable  # noqa: E402
from repro.launch import hlo_analysis as ha                # noqa: E402
from repro.launch import specs as case_specs               # noqa: E402
from repro.launch.mesh import make_production_mesh         # noqa: E402


def param_counts(cfg):
    """(total, active) parameter counts from the spec tree (no alloc)."""
    from repro.models import model_zoo
    tree = model_zoo.init_params_spec(cfg)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    total = expert = 0
    for path, leaf in flat:
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if any(getattr(p, "key", None) == "experts" for p in path):
            expert += n
    active = total - expert
    if cfg.num_experts:
        active += expert * cfg.experts_per_token / cfg.num_experts
    return total, active


def run_case(arch: str, shape_name: str, *, multi_pod: bool = False,
             verbose: bool = True, variant: str = "baseline",
             hi: bool = False, capacity_factor: float = 0.5) -> dict:
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.perf_counter()
    if hi:
        if not shape.is_decode:
            return {"arch": arch, "shape": shape_name,
                    "mesh": "multi" if multi_pod else "single",
                    "status": "skipped",
                    "reason": "HI cascade case lowers serve_step only"}
        fn, args, in_sh, out_sh = case_specs.make_hi_decode_case(
            cfg, shape, mesh, capacity_factor=capacity_factor)
        donate = (3, 4)
    else:
        fn, args, in_sh, out_sh = case_specs.make_case(cfg, shape, mesh,
                                                       variant)
        donate = case_specs.donate_for(shape)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # older jax returns [dict]
        cost = cost[0] if cost else {}
    hlo_text = compiled.as_text()

    # loop-aware accounting (cost_analysis counts while bodies ONCE — with
    # scan-over-layers that understates by ~num_layers x; see hlo_loop.py)
    from repro.launch import hlo_loop
    coll = {k: int(v) for k, v in
            hlo_loop.collective_bytes_loop_aware(hlo_text).items()}
    fc = hlo_loop.stablehlo_flops(lowered.as_text())

    total_p, active_p = param_counts(cfg)
    tokens = shape.global_batch * (1 if shape.is_decode else
                                   case_specs.text_len(cfg, shape))
    mf = ha.model_flops(active_p, tokens, shape.mode)

    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    roof = ha.Roofline(
        arch=arch, shape=shape_name,
        mesh="multi" if multi_pod else "single", chips=chips,
        hlo_flops=fc.flops / chips,          # per-chip, loop-aware
        hlo_bytes=fc.dot_bytes / chips,      # per-chip dot-operand traffic
        coll_bytes=float(sum(coll.values())), coll_breakdown=coll,
        model_flops=mf / chips, peak_memory_bytes=ha.parse_memory_analysis(mem))

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "variant": variant, "hi": hi,
        "capacity_factor": capacity_factor if hi else None,
        "status": "ok", "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "total_params": total_p, "active_params": active_p,
        "memory": {
            "argument_gb": getattr(mem, "argument_size_in_bytes", 0) / 1e9,
            "output_gb": getattr(mem, "output_size_in_bytes", 0) / 1e9,
            "temp_gb": getattr(mem, "temp_size_in_bytes", 0) / 1e9,
            "peak_gb_per_device": roof.peak_memory_bytes / 1e9,
        },
        "cost": {"loop_aware_flops_per_chip": roof.hlo_flops,
                 "loop_aware_dot_bytes_per_chip": roof.hlo_bytes,
                 "raw_cost_analysis_flops": raw_flops,
                 "raw_cost_analysis_bytes": raw_bytes},
        "collectives": coll,
        "roofline": roof.row(),
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {result['mesh']}] OK "
              f"compile={t_compile:.0f}s "
              f"peak={result['memory']['peak_gb_per_device']:.2f}GB/dev "
              f"dominant={roof.dominant} "
              f"(c={roof.compute_s:.4f}s m={roof.memory_s:.4f}s "
              f"coll={roof.collective_s:.4f}s)")
        print("  memory_analysis:", {k: f"{v:.2f}GB"
                                     for k, v in result["memory"].items()})
        print("  per-chip loop-aware: flops=%.3e dot_bytes=%.3e "
              "(raw cost_analysis: %.3e / %.3e)"
              % (roof.hlo_flops, roof.hlo_bytes, raw_flops, raw_bytes))
        print("  collectives:", {k: f"{v/1e9:.2f}GB" for k, v in coll.items()
                                 if v})
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "zero_dp", "ep_dp", "lean_opt", "zero_lean", "ep_lean", "split_cache"])
    ap.add_argument("--hi", action="store_true",
                    help="lower the HI cascade serve_step (decode shapes)")
    ap.add_argument("--capacity-factor", type=float, default=0.5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_case(
                        arch, shape, multi_pod=mp, variant=args.variant,
                        hi=args.hi, capacity_factor=args.capacity_factor))
                except Exception as e:  # noqa: BLE001 — report, keep going
                    traceback.print_exc()
                    results.append({"arch": arch, "shape": shape,
                                    "mesh": "multi" if mp else "single",
                                    "status": "error", "error": repr(e)})
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(results)}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print("wrote", args.out)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
