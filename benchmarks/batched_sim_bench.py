"""Micro-benchmark: batched Lindley FIFO vs the legacy heapq event loop.

Workload is a fig4-style sensitivity cell: 16 seeds x 10k queries at the
paper's operating point, simulated under a stack of GSM8K-budget policies.
The acceptance bar for the batched subsystem is >= 20x wall-clock speedup
over running the scalar heapq DES over the same (seed x policy) grid; in
practice the numpy cumulative pass lands around three orders of magnitude.

    PYTHONPATH=src python -m benchmarks.batched_sim_bench [--smoke]

``--smoke`` shrinks the grid (4 seeds x 2k queries) and enforces a
wall-clock budget, for CI.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro import compile_cache
from repro.core import paper_problem
from repro.queueing_sim import (generate_stream, generate_streams, simulate,
                                simulate_fifo_batch)

from .common import emit

LSTAR = np.array([0.0, 340.0, 0.0, 0.0, 345.0, 30.0])
GSM8K = 1


def _policy_stack() -> np.ndarray:
    """fig4-style: GSM8K budget swept with the other budgets at optimum."""
    policies = []
    for g in (0.0, 200.0, 340.0, 600.0, 1000.0):
        l = LSTAR.copy()
        l[GSM8K] = g
        policies.append(l)
    return np.stack(policies)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + wall-clock budget (CI)")
    ap.add_argument("--budget-s", type=float, default=60.0,
                    help="smoke-mode wall-clock budget for the batched path")
    args = ap.parse_args(argv)

    n_seeds, n_queries = (4, 2000) if args.smoke else (16, 10_000)
    prob = paper_problem()
    lam = prob.server.lam
    policies = _policy_stack()
    grid = policies.shape[0] * n_seeds * n_queries
    emit("batched_bench.grid", f"{policies.shape[0]}x{n_seeds}x{n_queries}",
         f"{grid} simulated queries")

    # --- legacy pipeline: scalar streams + one heapq DES call per cell -----
    t0 = time.perf_counter()
    streams = [generate_stream(prob.tasks, lam, n_queries, seed=i)
               for i in range(n_seeds)]
    ref_sys = np.array([[simulate(prob, l, s).mean_system_time
                         for s in streams] for l in policies])
    t_legacy = time.perf_counter() - t0
    t0 = time.perf_counter()
    _ = [simulate(prob, policies[0], s) for s in streams]
    t_heapq_row = time.perf_counter() - t0

    # --- batched pipeline: one RNG batch + one Lindley pass for the grid ---
    t0 = time.perf_counter()
    batch = generate_streams(prob.tasks, lam, n_seeds, n_queries, seed=100)
    stats = simulate_fifo_batch(prob, policies, batch, backend="numpy")
    t_numpy = time.perf_counter() - t0

    # --- jax scan backend (first call pays compile; report steady state) ---
    simulate_fifo_batch(prob, policies, batch, backend="jax")  # warmup
    t0 = time.perf_counter()
    stats_jax = simulate_fifo_batch(prob, policies, batch, backend="jax")
    t_jax = time.perf_counter() - t0

    # correctness anchors: both backends agree with each other to 1e-9, and
    # with the heapq DES statistically (different seeds, same law)
    np.testing.assert_allclose(stats.mean_system_time,
                               stats_jax.mean_system_time, atol=1e-9)
    rel = abs(stats.mean_system_time.mean() - ref_sys.mean()) / ref_sys.mean()
    assert rel < 0.25, f"batched and heapq pipelines disagree: {rel:.3f}"

    speedup_np = t_legacy / max(t_numpy, 1e-12)
    speedup_jax = t_legacy / max(t_jax, 1e-12)
    emit("batched_bench.legacy_s", f"{t_legacy:.3f}",
         "scalar streams + heapq DES over the grid")
    emit("batched_bench.heapq_sim_only_s", f"{t_heapq_row * len(policies):.3f}",
         "extrapolated DES-only time, excluding stream build")
    emit("batched_bench.numpy_s", f"{t_numpy:.4f}",
         f"end-to-end, speedup {speedup_np:.0f}x")
    emit("batched_bench.jax_s", f"{t_jax:.4f}",
         f"sim-only steady-state, speedup {speedup_jax:.0f}x")
    emit("batched_bench.qps_numpy", f"{grid / max(t_numpy, 1e-12):,.0f}",
         "simulated queries / wall-second")
    emit("batched_bench.speedup_ok", bool(speedup_np >= 20.0),
         "acceptance: >= 20x over the legacy pipeline")
    if not args.smoke:
        assert speedup_np >= 20.0, (
            f"batched path only {speedup_np:.1f}x faster than legacy")
    if args.smoke:
        assert t_numpy <= args.budget_s, (
            f"smoke budget blown: {t_numpy:.2f}s > {args.budget_s}s")


if __name__ == "__main__":
    compile_cache.enable()
    main()
