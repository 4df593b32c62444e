"""Sec III-C/D: solver convergence behaviour.

Fixed-point iterations vs PGA (global-step and backtracking) across load,
plus the Lemma 2 certificate values — documenting the reproduction finding
that the paper-form certificate is vacuous (always > 1) while the map
empirically contracts. The per-load diagnostics (iterations, KKT
residuals, both certificate variants, PGA-fallback mask) now also come out
of ONE vmapped grid solve (``repro.sweeps.solve_grid``), cross-checked
against the scalar solvers below."""
from __future__ import annotations

import jax
import numpy as np

from repro import compile_cache
from repro.core import (ServerParams, Problem, contraction_certificate,
                        paper_problem, safe_step_size, solve_fixed_point,
                        solve_pga, solve_pga_backtracking)
from repro.core.fixed_point import empirical_contraction_estimate
from repro.sweeps import reference_check, solve_grid

from .common import emit
from repro.compat import enable_x64

LAMS = (0.05, 0.1, 0.3)


def main() -> None:
    base = paper_problem()
    sp = base.server
    grid = solve_grid(base.tasks, np.asarray(LAMS), sp.alpha, sp.l_max)
    reference_check(base.tasks, grid)
    for i, lam in enumerate(LAMS):
        emit(f"conv.grid.lam_{lam}.fp_iters", int(grid.fp_iterations[i]),
             f"converged={bool(grid.fp_converged[i])}, "
             f"kkt={grid.kkt_residual[i]:.2e}, "
             f"pga_fallback={bool(grid.used_pga[i])}")
        emit(f"conv.grid.lam_{lam}.L_inf_slab",
             f"{grid.contraction_Linf_slab[i]:.3g}",
             "Lemma 2 certificate, batched")
    for lam in LAMS:
        prob = Problem(tasks=base.tasks,
                       server=ServerParams(lam, 30.0, 32768.0))
        with enable_x64():
            fp = solve_fixed_point(prob, tol=1e-10)
            pgb = solve_pga_backtracking(prob, tol=1e-10)
            emit(f"conv.lam_{lam}.fp_iters", int(fp.iterations),
                 f"converged={bool(fp.converged)}")
            emit(f"conv.lam_{lam}.pga_bt_iters", int(pgb.iterations),
                 f"converged={bool(pgb.converged)}")
            cert = float(contraction_certificate(prob))
            cert_slab = float(contraction_certificate(prob, 5e-2))
            emp = float(empirical_contraction_estimate(prob, n_samples=24))
            # local modulus at the fixed point = asymptotic FP rate
            from repro.core.fixed_point import fixed_point_map
            jac = jax.jacfwd(lambda v: fixed_point_map(prob, v))(fp.lengths)
            local = float(np.max(np.sum(np.abs(np.asarray(jac)), axis=1)))
            emit(f"conv.lam_{lam}.L_inf_paper", f"{cert:.3g}",
                 "eq26; >1 always (vacuous-by-construction)")
            emit(f"conv.lam_{lam}.L_inf_slab", f"{cert_slab:.3g}", "")
            emit(f"conv.lam_{lam}.slab_sup_modulus", f"{emp:.3g}",
                 "sampled sup ||J_lhat||_inf over the slab")
            emit(f"conv.lam_{lam}.local_modulus_at_lstar", f"{local:.3g}",
                 "asymptotic FP rate (<1 explains fast convergence)")
            eta = float(safe_step_size(prob))
            emit(f"conv.lam_{lam}.safe_eta", f"{eta:.3g}", "eq38 (slab)")
    # plain PGA with the guaranteed step on the paper instance: the bound is
    # conservative, so measure the J-gap after a fixed budget, not residuals
    from repro.core import objective
    prob = paper_problem()
    with enable_x64():
        ref = solve_fixed_point(prob, tol=1e-12)
        pg = solve_pga(prob, tol=1e-7, max_iters=100_000)
        jgap = float(objective(prob, ref.lengths)
                     - objective(prob, pg.lengths))
    emit("conv.plain_pga_100k_iters_J_gap", f"{jgap:.2e}",
         f"eta={float(pg.eta):.3g} (guaranteed step; conservative)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
