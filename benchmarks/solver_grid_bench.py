"""Micro-benchmark: vmapped grid solver vs the scalar solver loop.

Workload: a ``(lambda x alpha x l_max)`` operating grid on the paper's
calibrated instance — the capacity-planning sweep every benchmark used to
run one scalar ``core.allocator.solve`` per cell for. Full mode solves a
>= 100-cell grid on the device path, re-solves a scalar reference subset,
checks per-cell agreement (continuous optima to 1e-6, identical integer
budgets), and measures cells/sec both ways. Acceptance: the grid path is
>= 10x the scalar loop's throughput.

    PYTHONPATH=src python -m benchmarks.solver_grid_bench [--smoke]

``--smoke`` shrinks the grid (12 cells, 4-cell scalar reference) and
enforces a wall-clock budget, for CI. Either mode writes a
``BENCH_solver_grid.json`` artifact (``--json-out`` to relocate) recording
the throughputs for the perf trajectory.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro import compile_cache
from repro.core import ServerParams, Problem, paper_problem, solve
from repro.sweeps import solve_grid

from .common import emit


def _grid(smoke: bool):
    if smoke:
        lams = np.linspace(0.05, 0.5, 3)
        alphas = np.array([15.0, 30.0])
        lmaxs = np.array([1024.0, 32768.0])
    else:
        lams = np.linspace(0.05, 0.5, 10)
        alphas = np.array([10.0, 20.0, 30.0, 45.0, 60.0])
        lmaxs = np.array([1024.0, 32768.0])
    return np.meshgrid(lams, alphas, lmaxs, indexing="ij")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + wall-clock budget (CI)")
    ap.add_argument("--budget-s", type=float, default=60.0,
                    help="smoke-mode wall-clock budget for the grid solve")
    ap.add_argument("--json-out", default="BENCH_solver_grid.json",
                    help="perf-trajectory artifact path")
    ap.add_argument("--scalar-cells", type=int, default=None,
                    help="scalar reference subset size (default 4 smoke / "
                         "12 full)")
    args = ap.parse_args(argv)

    prob0 = paper_problem()
    tasks = prob0.tasks
    lam_g, alpha_g, lmax_g = _grid(args.smoke)
    n_cells = lam_g.size
    emit("solver_grid_bench.grid", "x".join(map(str, lam_g.shape)),
         f"{n_cells} cells")

    # --- vmapped grid path: cold (includes trace+compile) and steady state
    t0 = time.perf_counter()
    sol = solve_grid(tasks, lam_g, alpha_g, lmax_g)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    sol = solve_grid(tasks, lam_g, alpha_g, lmax_g)
    t_warm = time.perf_counter() - t0
    assert bool(np.all(sol.stable)), "grid produced unstable cells"

    # --- scalar reference loop over a subset, extrapolated to cells/sec ---
    n_ref = args.scalar_cells or (4 if args.smoke else 12)
    flat = sol.ravel()
    ref_idx = np.linspace(0, n_cells - 1, n_ref).astype(int)
    worst_cont, worst_int = 0.0, 0
    t0 = time.perf_counter()
    for i in ref_idx:
        s = solve(Problem(tasks=tasks,
                          server=ServerParams(float(flat.lam[i]),
                                              float(flat.alpha[i]),
                                              float(flat.l_max[i]))))
        worst_cont = max(worst_cont, float(
            np.max(np.abs(s.lengths_cont - flat.lengths_cont[i]))))
        worst_int = max(worst_int, int(
            np.max(np.abs(s.lengths_int - flat.lengths_int[i]))))
    t_scalar_ref = time.perf_counter() - t0
    scalar_cps = n_ref / max(t_scalar_ref, 1e-12)
    grid_cps_warm = n_cells / max(t_warm, 1e-12)
    grid_cps_cold = n_cells / max(t_cold, 1e-12)
    speedup = grid_cps_warm / max(scalar_cps, 1e-12)

    emit("solver_grid_bench.agree_cont", f"{worst_cont:.2e}",
         f"max |l*_grid - l*_scalar| over {n_ref} reference cells")
    emit("solver_grid_bench.agree_int", worst_int,
         "max integer-budget deviation (must be 0)")
    emit("solver_grid_bench.scalar_cells_per_s", f"{scalar_cps:.2f}",
         f"{n_ref} scalar solves in {t_scalar_ref:.2f}s")
    emit("solver_grid_bench.grid_cells_per_s", f"{grid_cps_warm:.1f}",
         f"{n_cells} cells in {t_warm:.3f}s (steady state)")
    emit("solver_grid_bench.grid_cells_per_s_cold", f"{grid_cps_cold:.1f}",
         f"incl. trace+compile ({t_cold:.2f}s)")
    emit("solver_grid_bench.speedup", f"{speedup:.1f}",
         "grid vs scalar loop, cells/sec")
    emit("solver_grid_bench.speedup_ok", bool(speedup >= 10.0),
         "acceptance: >= 10x over the scalar solver loop")

    assert worst_cont < 1e-6, (
        f"grid/scalar continuous optima disagree: {worst_cont:.2e}")
    assert worst_int == 0, "grid/scalar integer budgets disagree"
    if not args.smoke:
        assert n_cells >= 100, "full-mode grid must cover >= 100 cells"
        assert speedup >= 10.0, (
            f"grid path only {speedup:.1f}x the scalar loop")
    if args.smoke:
        assert t_warm <= args.budget_s, (
            f"smoke budget blown: {t_warm:.2f}s > {args.budget_s}s")

    artifact = {
        "bench": "solver_grid",
        "mode": "smoke" if args.smoke else "full",
        "grid_shape": list(lam_g.shape),
        "n_cells": int(n_cells),
        "n_scalar_reference_cells": int(n_ref),
        "scalar_cells_per_s": scalar_cps,
        "grid_cells_per_s": grid_cps_warm,
        "grid_cells_per_s_cold": grid_cps_cold,
        "speedup_vs_scalar": speedup,
        "grid_solve_s_cold": t_cold,
        "grid_solve_s_warm": t_warm,
        "scalar_reference_s": t_scalar_ref,
        "max_abs_cont_deviation": worst_cont,
        "max_int_deviation": int(worst_int),
        "fp_converged_cells": int(np.sum(flat.fp_converged)),
        "pga_fallback_cells": int(np.sum(flat.used_pga)),
    }
    with open(args.json_out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    emit("solver_grid_bench.artifact", args.json_out, "")


if __name__ == "__main__":
    compile_cache.enable()
    main()
