"""Paper Fig 4: J(l) as a function of the GSM8K budget with all other
budgets at optimum — unimodal with maximizer ~ 340; plus the eq-41 lower
bound and DES cross-check points.

Device-resident end to end: the base optimum comes from the vmapped grid
solver (scalar ``solve`` as cross-checked reference), the whole J / eq-41
budget sweep is ONE batched ``objective`` / ``rounding_lower_bound`` call
over a ``[G, N]`` stack of allocations, the DES cross-check is one batched
Lindley sweep, and the beyond-paper (lambda x alpha) sensitivity now
re-SOLVES the optimum per cell through ``solve_grid`` (12 operating points,
one device pass) in addition to reweighting the common-random-number
simulations.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.compat import enable_x64
from repro.core import objective, paper_problem, rounding_lower_bound
from repro.queueing_sim import sweep
from repro.sweeps import reference_check, solve_grid

from .common import emit

GSM8K = 1


def main() -> None:
    prob = paper_problem()
    sp = prob.server
    gsol = solve_grid(prob.tasks, sp.lam, sp.alpha, sp.l_max)
    reference_check(prob.tasks, gsol)
    base = np.asarray(gsol.lengths_cont)

    grid = np.arange(0, 1001, 25)
    stack = np.repeat(base[None, :], grid.shape[0], axis=0)
    stack[:, GSM8K] = grid
    with enable_x64():
        vals = np.asarray(objective(prob, jnp.asarray(stack)))
        bounds = np.asarray(rounding_lower_bound(prob, jnp.asarray(stack)))
    argmax = grid[int(np.argmax(vals))]
    emit("fig4.argmax_gsm8k", int(argmax), f"paper~340, J={vals.max():.4f}")
    # unimodality: strictly increasing then strictly decreasing
    d = np.diff(vals)
    switch = int(np.argmax(d < 0))
    unimodal = bool(np.all(d[:switch] > 0) and np.all(d[switch:] < 0))
    emit("fig4.unimodal", unimodal, "")
    emit("fig4.bound_below_J", bool(np.all(bounds <= vals + 1e-9)),
         "eq41 holds on the sweep")

    # DES cross-check over the whole grid in one batched call
    policies = {}
    for g in grid:
        l = np.round(base.copy())
        l[GSM8K] = g
        policies[f"gsm8k_{int(g)}"] = l
    res = sweep(prob, policies, lams=[sp.lam], n_seeds=16,
                n_queries=10_000, seed=1)
    des_vals = res.objective[0]
    des_argmax = int(grid[int(np.argmax(des_vals))])
    emit("fig4.des_argmax_gsm8k", des_argmax,
         f"analytic argmax {int(argmax)}")
    for g in (0, 200, 600, 1000):
        p = list(res.policy_names).index(f"gsm8k_{g}")
        jv = vals[int(np.argmax(grid == g))]
        emit(f"fig4.J_des.gsm8k_{g}", f"{des_vals[p]:.4f}",
             f"+-{res.ci_objective[0, p]:.4f}, analytic={jv:.4f}")
    emit("fig4.des_within_ci",
         bool(np.all(np.abs(des_vals - vals) <= 4 * res.ci_objective[0]
                     + 0.05)),
         "DES grid tracks analytic J")

    # Beyond paper: (lambda x alpha) sensitivity. The grid solver re-solves
    # the full optimum at every operating point in one device pass...
    lams = np.array([0.05, 0.1, 0.15])
    alphas = np.array([15.0, 30.0, 60.0])
    sens = solve_grid(prob.tasks, lams[:, None], alphas[None, :], sp.l_max)
    for i, lam in enumerate(lams):
        for j, alpha in enumerate(alphas):
            emit(f"fig4.lstar_gsm8k.lam_{lam}.alpha_{int(alpha)}",
                 f"{sens.lengths_cont[i, j, GSM8K]:.1f}",
                 f"J*={sens.value_cont[i, j]:.4f}, "
                 f"rho={sens.rho_cont[i, j]:.3f}")
    # ...and the DES argmax over the FIXED fig4 policy stack rides on the
    # same common-random-number simulations via post-hoc reweighting.
    for lam in lams:
        r = sweep(prob, policies, lams=[float(lam)], n_seeds=8,
                  n_queries=10_000, seed=2)
        for alpha in alphas:
            j = r.objective_at(float(alpha))[0]
            emit(f"fig4.argmax.lam_{lam}.alpha_{int(alpha)}",
                 int(grid[int(np.argmax(j))]),
                 f"J={j.max():.4f}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
