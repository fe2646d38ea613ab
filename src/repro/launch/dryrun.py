import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import (device count locks at first init). This
# module is the ONLY place the 512-device platform is forced — tests and
# benchmarks see the real 1-device CPU. Pinning the CPU keeps the --sweep
# parent and its per-cell children off an attached TPU, which only one
# process at a time may hold.

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from repro.launch.cells import all_cells, build_cell  # noqa: E402
from repro.launch.hlo_stats import analyze  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def run_cell(arch: str, shape: str, multi_pod: bool, optimizer: str,
             overrides=None, tag: str = "", accum: int = 1) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    cell = build_cell(arch, shape, mesh, optimizer=optimizer, overrides=overrides,
                      accum=accum)

    t0 = time.time()
    with mesh:
        jitted = jax.jit(
            cell.fn, in_shardings=cell.in_shardings, out_shardings=cell.out_shardings,
            donate_argnums=cell.donate,
        )
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        print(f"memory_analysis: {mem}", flush=True)  # proves it fits
        print(f"cost_analysis: flops={cost.get('flops')} "
              f"bytes={cost.get('bytes accessed')} (per-device, loop bodies "
              f"counted once — see hlo_stats for trip-count-corrected totals)",
              flush=True)
        hlo = compiled.as_text()
    stats = analyze(hlo)  # trip-count-aware (cost_analysis counts loop bodies once)

    rec = {
        "cell": cell.name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "optimizer": optimizer,
        "tag": tag,
        "meta": cell.meta,
        "flops": stats["flops"],
        "bytes_accessed": stats["bytes_hbm"],
        "raw_cost_analysis": {
            "flops": cost.get("flops", -1.0),
            "bytes_accessed": cost.get("bytes accessed", -1.0),
        },
        "collectives": stats["collectives"],
        "collective_counts": stats["collective_counts"],
        "top_computations": stats["top_computations"],
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
            "output_bytes": getattr(mem, "output_size_in_bytes", -1),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", -1),
        },
        "timings": {"lower_s": t_lower, "compile_s": t_compile},
        "hlo_bytes": len(hlo),
    }
    return rec


def cell_path(arch: str, shape: str, multi_pod: bool, tag: str = "") -> Path:
    mesh_tag = "multi" if multi_pod else "single"
    suffix = f"__{tag}" if tag else ""
    return RESULTS / f"{arch}__{shape}__{mesh_tag}{suffix}.json"


def main() -> int:
    ap = argparse.ArgumentParser(description="multi-pod AOT dry-run")
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--sweep", action="store_true", help="run all 40 cells")
    ap.add_argument("--tag", default="", help="variant tag for the output file")
    ap.add_argument("--overrides", default="", help="JSON dict of config overrides")
    ap.add_argument("--force", action="store_true", help="re-run cached cells")
    ap.add_argument("--accum", type=int, default=1, help="gradient accumulation")
    args = ap.parse_args()
    RESULTS.mkdir(parents=True, exist_ok=True)

    if args.sweep:
        # one subprocess per cell: a pathological compile cannot kill the sweep
        cells = all_cells()
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        failures = 0
        for mesh_kind in meshes:
            for arch, shape in cells:
                out = cell_path(arch, shape, mesh_kind == "multi", args.tag)
                if out.exists() and not args.force:
                    continue
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                    "--optimizer", args.optimizer,
                ]
                if args.tag:
                    cmd += ["--tag", args.tag]
                if args.overrides:
                    cmd += ["--overrides", args.overrides]
                print(f"[sweep] {arch}:{shape} ({mesh_kind})", flush=True)
                r = subprocess.run(cmd)
                failures += r.returncode != 0
        print(f"[sweep] done, {failures} failures", flush=True)
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch/--shape required outside --sweep"
    multi = args.mesh == "multi"
    overrides = json.loads(args.overrides) if args.overrides else None
    out = cell_path(args.arch, args.shape, multi, args.tag)
    try:
        rec = run_cell(args.arch, args.shape, multi, args.optimizer,
                       overrides=overrides, tag=args.tag, accum=args.accum)
        out.write_text(json.dumps(rec, indent=2, default=str))
        print(
            f"OK {rec['cell']} [{rec['mesh']}] flops={rec['flops']:.3e} "
            f"bytes={rec['bytes_accessed']:.3e} "
            f"coll={rec['collectives']['total']:.3e} "
            f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB "
            f"compile={rec['timings']['compile_s']:.1f}s",
            flush=True,
        )
        return 0
    except Exception:
        err = {"cell": f"{args.arch}:{args.shape}", "mesh": args.mesh,
               "error": traceback.format_exc()}
        out.with_suffix(".err.json").write_text(json.dumps(err, indent=2))
        print(f"FAIL {args.arch}:{args.shape} [{args.mesh}]", flush=True)
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
