"""Sec III-E: integer-projection quality — the eq-39/40/41 sandwich, plus
the beyond-paper coordinate refinement, across operating points."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.core import (ServerParams, Problem, paper_problem, sandwich,
                        solve, solve_fixed_point)

from .common import emit
from repro.compat import enable_x64


def main() -> None:
    base = paper_problem()
    for lam in (0.05, 0.1, 0.2, 0.4):
        prob = Problem(tasks=base.tasks,
                       server=ServerParams(lam, 30.0, 32768.0))
        sol = solve(prob)
        with enable_x64():
            s = sandwich(prob, jnp.asarray(sol.lengths_cont))
        gap_round = s["J_continuous"] - s["J_int_round"]
        gap_bound = s["J_continuous"] - s["J_bar_lower_bound"]
        emit(f"integer.lam_{lam}.J_cont", f"{s['J_continuous']:.6f}", "")
        emit(f"integer.lam_{lam}.round_gap", f"{gap_round:.2e}",
             f"bound_gap={gap_bound:.2e}")
        assert s["J_continuous"] >= s["J_int_exhaustive"] >= \
            s["J_int_round"] >= s["J_bar_lower_bound"] - 1e-12
        emit(f"integer.lam_{lam}.sandwich_holds", True, "")


if __name__ == "__main__":
    compile_cache.enable()
    main()
