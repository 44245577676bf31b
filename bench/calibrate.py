"""Readings of the numbers that decide ``correct``, over many seeds in one
process: the program's own path, or with ``--control`` its bfloat16 reference
backend in place of the kernels.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5 [--control]

One JSON line per seed on standard output.  The limits in each
configuration file are set from these readings (see PERF.md).
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = harness.run(args.workload, seed, args.seconds, False,
                          control=args.control)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": out["correct"], "failed": out["failed"],
                          "attempted": out["attempted"],
                          "checks": out["checks"], "metrics": out["metrics"],
                          "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
