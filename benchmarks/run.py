"""Benchmark runner: one module per paper table/figure + system benches.
Prints ``name,value,derived`` CSV rows."""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

from repro import compile_cache

MODULES = (
    "table1",            # Table I: optimal allocations
    "fig2_fit",          # Fig 2: accuracy-curve calibration
    "fig3_policies",     # Fig 3: uniform vs optimal
    "fig4_sensitivity",  # Fig 4: GSM8K budget sweep + eq-41 bound
    "integer_gap",       # Sec III-E sandwich across loads
    "convergence",       # Sec III-C/D solver behaviour + certificates
    "solver_grid_bench",  # vmapped grid solver vs scalar loop (100 cells)
    "serving_bench",     # end-to-end server + ablations + M/G/c
    "engine_bench",      # CPU decode microbench (reduced archs)
    "calibration_bridge",  # roofline -> (t0,c) -> re-solve loop
    "roofline",          # dry-run roofline table (reads results/)
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark modules")
    args = ap.parse_args()
    mods = args.only.split(",") if args.only else MODULES
    failures = 0
    for name in mods:
        print(f"# --- {name} ---", flush=True)
        t0 = time.time()
        try:
            importlib.import_module(f"benchmarks.{name}").main()
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"# {name} FAILED", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    compile_cache.enable()
    main()
