"""Paper Table I: optimal reasoning-token allocation on the calibrated
Qwen3-8B instance (lam=0.1, alpha=30, l_max=32768, pi=1/6).

The table is produced by the vmapped grid solver (one-cell grid); the
scalar facade is re-run as the reference implementation and must agree
bitwise-tight (continuous to 1e-6, identical integers)."""
from __future__ import annotations

import numpy as np

from repro import compile_cache
from repro.core import (PAPER_TABLE1_LSTAR, paper_problem, solve,
                        solve_fixed_point, solve_pga_backtracking)
from repro.sweeps import reference_check, solve_grid

from .common import emit, timed
from repro.compat import enable_x64


def main() -> None:
    prob = paper_problem()
    sp = prob.server
    gsol, us_grid = timed(
        lambda: solve_grid(prob.tasks, sp.lam, sp.alpha, sp.l_max), repeat=3)
    sol, us = timed(lambda: solve(prob), repeat=3)
    agree = reference_check(prob.tasks, gsol)
    emit("table1.grid_vs_scalar_lstar", f"{agree:.2e}",
         "grid path vs reference scalar solve")
    names = prob.tasks.names
    paper = np.asarray(PAPER_TABLE1_LSTAR)
    for i, n in enumerate(names):
        emit(f"table1.lstar.{n}", f"{gsol.lengths_cont[i]:.1f}",
             f"paper={paper[i]:.1f}")
        emit(f"table1.lint.{n}", int(gsol.lengths_int[i]), "")
    err = float(np.max(np.abs(sol.lengths_cont - paper)))
    emit("table1.solve", f"{us:.0f}", f"max_abs_dev_vs_paper={err:.2f}")
    emit("table1.solve_grid_1cell", f"{us_grid:.0f}",
         "us per one-cell grid solve (incl. retrace)")
    emit("table1.J_continuous", f"{sol.value_cont:.6f}", "")
    emit("table1.J_integer", f"{sol.value_int:.6f}", "")
    emit("table1.J_lower_bound", f"{sol.value_lower_bound:.6f}", "eq41")
    emit("table1.method", sol.method, f"iters={sol.iterations}")

    import jax
    with enable_x64():
        _, us_fp = timed(lambda: solve_fixed_point(prob).lengths.block_until_ready())
        _, us_pga = timed(lambda: solve_pga_backtracking(prob)
                          .lengths.block_until_ready())
    emit("table1.fixed_point", f"{us_fp:.0f}", "us_per_solve")
    emit("table1.pga_backtracking", f"{us_pga:.0f}", "us_per_solve")


if __name__ == "__main__":
    compile_cache.enable()
    main()
