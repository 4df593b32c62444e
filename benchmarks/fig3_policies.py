"""Paper Fig 3: J under uniform allocations {0,100,500} vs the optimal
heterogeneous l*, analytically AND through the DES.

Runs device-resident end to end: the optimum comes from the vmapped grid
solver (``repro.sweeps.solve_grid``; the scalar ``core.allocator.solve``
stays as the cross-checked reference), the analytic J column for all four
policies is one batched ``objective`` call, and the DES column is a single
batched Lindley sweep (all policies x 8 seeds x 10k queries), so it
carries a 95% CI for free.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.compat import enable_x64
from repro.core import objective, paper_problem
from repro.queueing_sim import sweep
from repro.sweeps import reference_check, solve_grid

from .common import emit


def main() -> None:
    prob = paper_problem()
    sp = prob.server
    grid = solve_grid(prob.tasks, sp.lam, sp.alpha, sp.l_max)
    # scalar reference path must agree with the grid cell
    agree = reference_check(prob.tasks, grid)
    emit("fig3.grid_vs_scalar_lstar", f"{agree:.2e}",
         "|l*_grid - l*_scalar|_inf (reference check)")

    policies = {
        "uniform_0": np.zeros(6),
        "uniform_100": np.full(6, 100.0),
        "uniform_500": np.full(6, 500.0),
        "optimal": np.asarray(grid.lengths_int),
    }
    stack = np.stack(list(policies.values()))
    with enable_x64():
        j_analytic_all = np.asarray(objective(prob, jnp.asarray(stack)))
    res = sweep(prob, policies, lams=[sp.lam], n_seeds=8,
                n_queries=10_000, seed=0)
    j_opt = None
    for p, name in enumerate(res.policy_names):
        j_analytic = float(j_analytic_all[p])
        emit(f"fig3.J_analytic.{name}", f"{j_analytic:.4f}", "")
        emit(f"fig3.J_des.{name}", f"{res.objective[0, p]:.4f}",
             f"+-{res.ci_objective[0, p]:.4f}, "
             f"mean_sys={res.mean_system_time[0, p]:.3f}")
        if name == "optimal":
            j_opt = j_analytic
    for p, name in enumerate(res.policy_names):
        if name != "optimal":
            gap = j_opt - float(j_analytic_all[p])
            emit(f"fig3.optimal_gain_over.{name}", f"{gap:.4f}", "J units")


if __name__ == "__main__":
    compile_cache.enable()
    main()
