import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks device count on first init.

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh x mode)
cell on the production mesh (16x16 single-pod / 2x16x16 multi-pod) with
ShapeDtypeStruct inputs — no allocation.  Prints memory_analysis (fits) and
cost_analysis (FLOPs/bytes) and extracts the collective schedule from the
compiled HLO for the roofline (benchmarks/roofline.py reads the JSON this
writes).

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k \
      --mesh pod --mode baseline [--out artifacts/dryrun]
  python -m repro.launch.dryrun --all --mesh multipod   # every cell
"""
import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro.distributed.taskgraph import SHAPES
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as steps_mod

# long_500k needs sub-quadratic attention: run for SSM/hybrid and the
# sliding-window-dominant gemmas; skip pure full-attention archs +
# whisper (DESIGN.md §4)
LONG_OK = {"zamba2-7b", "rwkv6-1.6b", "gemma2-27b", "gemma3-12b"}


def cells_for(arch: str) -> list[str]:
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if arch in LONG_OK:
        out.append("long_500k")
    return out


def run_cell(arch: str, shape: str, mesh_kind: str, mode: str,
             out_dir: str | None = None, seed: int = 0,
             unroll: bool = False) -> dict:
    cfg = configs.get(arch)
    cell = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    t0 = time.time()
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind, "mode": mode,
           "chips": int(mesh.devices.size), "unroll": unroll}
    with mesh:
        if cell.kind == "train":
            if mode == "tapa":
                step, args, ins, outs, plan = steps_mod.build_tapa_train(
                    cfg, mesh, cell, seed=seed, unroll=unroll)
                rec["plan"] = {
                    "n_stages": plan.n_stages,
                    "stage_slots": plan.stage_slots,
                    "boundary_depth": plan.boundary_depth,
                    "crossing_cost": plan.crossing_cost,
                }
            else:
                step, args, ins, outs = steps_mod.build_baseline_train(
                    cfg, mesh, cell, unroll=unroll)
        else:
            step, args, ins, outs = steps_mod.build_baseline_serve(
                cfg, mesh, cell, unroll=unroll)
            rec["mode"] = mode = "baseline"   # serving lowers GSPMD path
        donate = (0, 1) if cell.kind == "train" else ()
        lowered = jax.jit(step, in_shardings=ins, out_shardings=outs,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    rec.update(
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        flops=float(cost.get("flops", 0.0)),
        bytes_accessed=float(cost.get("bytes accessed", 0.0)),
        arg_bytes=int(mem.argument_size_in_bytes),
        out_bytes=int(mem.output_size_in_bytes),
        temp_bytes=int(mem.temp_size_in_bytes),
        alias_bytes=int(mem.alias_size_in_bytes),
        peak_bytes_per_device=int(mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - mem.alias_size_in_bytes),
    )
    coll = hlo_analysis.collective_summary(
        compiled.as_text(), pod_size=256 if mesh_kind == "multipod" else 1 << 30)
    rec["collectives"] = coll
    print(f"dryrun,{arch},{shape},{mesh_kind},{mode},"
          f"flops={rec['flops']:.3e},"
          f"peakGB={rec['peak_bytes_per_device']/1e9:.2f},"
          f"collMB_ici={coll['ici_bytes']/1e6:.1f},"
          f"collMB_dcn={coll['dcn_bytes']/1e6:.1f},"
          f"compile={t_compile:.0f}s", flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}__{mode}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
    del compiled, lowered
    jax.clear_caches()   # compiled executables would accumulate across cells
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--mode", default="baseline", choices=["baseline", "tapa"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--unroll", action="store_true",
                    help="unroll scans so cost_analysis counts every layer")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.all:
        ok = fail = 0
        for arch in configs.ARCHS:
            for shape in cells_for(arch):
                fn = os.path.join(args.out,
                                  f"{arch}__{shape}__{args.mesh}__{args.mode}"
                                  ".json")
                if args.skip_existing and os.path.exists(fn):
                    ok += 1
                    continue
                try:
                    run_cell(arch, shape, args.mesh, args.mode, args.out,
                             args.seed, unroll=args.unroll)
                    ok += 1
                except Exception:
                    traceback.print_exc()
                    print(f"dryrun,{arch},{shape},{args.mesh},{args.mode},"
                          f"FAILED", flush=True)
                    fail += 1
        print(f"dryrun,SUMMARY,{args.mesh},{args.mode},ok={ok},fail={fail}")
        raise SystemExit(1 if fail else 0)
    run_cell(args.arch, args.shape, args.mesh, args.mode, args.out,
             args.seed, unroll=args.unroll)


if __name__ == "__main__":
    main()
