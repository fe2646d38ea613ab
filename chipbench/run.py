"""Run one cell of the benchmark once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout; the program under test is `src/`.
With `--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics. The last line of standard output is the
result object; the last lines of standard error are the numbers that
decide `correct`, each beside its limit. Without a TPU, with fewer chips
than the cell asks for, or without the program, it exits 1 and prints no
result.

JAX's persistent compilation cache lives in `<checkout>/.jax_cache`, also
where `JAX_COMPILATION_CACHE_DIR` names another directory, so that only a
cell's first run in a checkout compiles and two checkouts share nothing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def use_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    # small serve steps compile in under a second; cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    try:
        import repro
    except ImportError:
        print("chipbench: the program under test (src/repro) is not in this checkout",
              file=sys.stderr)
        return 1
    where = [Path(p).resolve() for p in repro.__path__]
    if not all(p.is_relative_to(ROOT) for p in where):
        print(f"chipbench: repro imported from {where}, outside {ROOT}", file=sys.stderr)
        return 1
    from chipbench import harness

    use_cache()
    spec = harness.load_spec()
    cell, cfg, mix = harness.load_cell(spec, args.workload)
    metrics = harness.cell_metrics(spec, args.workload,
                                   "per_layer" if args.trace else "end_to_end")
    try:
        result = harness.run_cell(cell, cfg, mix, metrics, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]  # in place of chipbench/ itself
    sys.exit(main())
