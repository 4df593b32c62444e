"""Discipline ablation: (lambda x discipline x policy) grid with CIs.

How much of the optimal allocation's gain survives when the server is not
FIFO? This benchmark sweeps the three disciplines (FIFO — the paper's
M/G/1 setting, eqs 3-6 — plus the beyond-paper SJF and marginal-utility
priority ablations) over an (arrival-rate x policy x seed) grid twice:

* **batched**: one ``sweep_disciplines`` call — the masked-argmin engine
  of ``queueing_sim.disciplines`` riding the shared Lindley/busy-period
  pass, all disciplines on common random numbers;
* **legacy**: the scalar pipeline this repo used before — one
  ``generate_stream`` per (rate, seed) and one heapq ``mg1.simulate`` per
  grid cell.

Both produce the same table (the per-cell agreement of the two paths is
pinned by ``tests/test_disciplines.py`` at ~1e-10 per query; here the
stream seeds differ, so cells are compared statistically). The headline
is throughput: the batched path must clear ``--min-speedup`` (default
20x on the smoke grid, mirroring the FIFO fast path's acceptance bar; the
full grid adds a rho=0.8 heavy-traffic row whose longer busy periods cost
the engine more, so its default floor is 10x).

    PYTHONPATH=src python -m benchmarks.discipline_ablation [--smoke]

Either mode writes a ``BENCH_disciplines.json`` artifact (``--json-out``
to relocate) with the full ablation table, overflow diagnostics, and the
timing trajectory. ``--smoke`` shrinks the grid and enforces a
wall-clock budget, for CI.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro import compile_cache
from repro.core import paper_problem
from repro.queueing_sim import (DISCIPLINES, generate_stream, simulate,
                                simulate_batch, sweep_disciplines)

from .common import emit

LSTAR = np.array([0.0, 340.0, 0.0, 0.0, 345.0, 30.0])  # ~ paper Table I l*


def _grid(prob, smoke: bool):
    """Arrival rates from target utilizations of the uniform-300 policy."""
    t = np.asarray(prob.tasks.t0) + np.asarray(prob.tasks.c) * 300.0
    es300 = float(np.sum(np.asarray(prob.tasks.pi) * t))
    if smoke:
        rhos = (0.45, 0.6)
        n_seeds, n_queries = 96, 500
    else:
        rhos = (0.5, 0.65, 0.8)
        n_seeds, n_queries = 16, 10_000
    lams = [r / es300 for r in rhos]
    policies = {
        "optimal": LSTAR,
        "uniform_100": np.full(6, 100.0),
        "uniform_300": np.full(6, 300.0),
    }
    return rhos, lams, policies, n_seeds, n_queries


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + wall-clock budget (CI)")
    ap.add_argument("--budget-s", type=float, default=60.0,
                    help="smoke-mode wall-clock budget for the batched path")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="required batched-vs-heapq speedup "
                         "(default: 20 smoke / 10 full)")
    ap.add_argument("--json-out", default="BENCH_disciplines.json",
                    help="perf-trajectory artifact path")
    args = ap.parse_args(argv)
    min_speedup = args.min_speedup
    if min_speedup is None:
        min_speedup = 20.0 if args.smoke else 10.0

    prob = paper_problem()
    rhos, lams, policies, n_seeds, n_queries = _grid(prob, args.smoke)
    P, D, Lg = len(policies), len(DISCIPLINES), len(lams)
    cells = Lg * D * P
    grid_queries = cells * n_seeds * n_queries
    emit("disciplines.grid", f"{Lg}x{D}x{P}x{n_seeds}x{n_queries}",
         f"{grid_queries} simulated queries, rho(u300)={rhos}")

    # --- batched pipeline: sweep_disciplines (steady state, best of 4) ----
    res = sweep_disciplines(prob, policies, lams, n_seeds=n_seeds,
                            n_queries=n_queries, seed=0)  # warm jit caches
    t_batched = np.inf
    for _ in range(4):
        t0 = time.perf_counter()
        res = sweep_disciplines(prob, policies, lams, n_seeds=n_seeds,
                                n_queries=n_queries, seed=0)
        t_batched = min(t_batched, time.perf_counter() - t0)

    # --- legacy pipeline: scalar streams + one heapq DES per cell ---------
    # (also steady-state: best of 2, symmetric with the batched timing)
    t_legacy = np.inf
    for _ in range(2):
        t0 = time.perf_counter()
        legacy_sys = np.empty((Lg, D, P))
        for i, lam in enumerate(lams):
            streams = [generate_stream(prob.tasks, lam, n_queries, seed=s)
                       for s in range(n_seeds)]
            for d, disc in enumerate(DISCIPLINES):
                for p, budgets in enumerate(policies.values()):
                    lengths = res[disc].lengths[i, p]  # same clipped budgets
                    legacy_sys[i, d, p] = np.mean(
                        [simulate(prob, lengths, st,
                                  discipline=disc).mean_system_time
                         for st in streams])
        t_legacy = min(t_legacy, time.perf_counter() - t0)
    speedup = t_legacy / max(t_batched, 1e-12)

    # correctness anchors: the two pipelines sample the same law (different
    # stream seeds), so cell means must agree statistically; and on ONE
    # shared batch the engine must reproduce the heapq DES to float noise.
    for d, disc in enumerate(DISCIPLINES):
        rel = np.abs(legacy_sys[:, d, :] - res[disc].mean_system_time)
        rel /= np.maximum(res[disc].mean_system_time, 1e-9)
        assert np.all(rel < 0.25), f"{disc}: pipelines disagree ({rel})"
    from repro.queueing_sim import generate_streams
    anchor = generate_streams(prob.tasks, lams[-1], 2, min(n_queries, 2000),
                              seed=123)
    for disc in ("sjf", "priority"):
        fast = simulate_batch(prob, LSTAR, anchor, discipline=disc)
        ref = [simulate(prob, LSTAR, anchor.stream(s), discipline=disc)
               for s in range(2)]
        err = max(abs(fast.mean_system_time[s] - ref[s].mean_system_time)
                  for s in range(2))
        assert err < 1e-9, f"{disc} anchor err {err}"
    emit("disciplines.anchor", "ok",
         "engine == heapq on shared streams (1e-9); pipelines agree <25%")

    # --- ablation table ---------------------------------------------------
    table = []
    fifo = res["fifo"]
    for i, (rho, lam) in enumerate(zip(rhos, lams)):
        for disc in DISCIPLINES:
            r = res[disc]
            for p, name in enumerate(r.policy_names):
                table.append({
                    "rho_u300": rho, "lam": lam, "discipline": disc,
                    "policy": name,
                    "rho_analytic": float(r.rho_analytic[i, p]),
                    "mean_wait": float(r.mean_wait[i, p]),
                    "mean_system_time": float(r.mean_system_time[i, p]),
                    "ci_system_time": float(r.ci_system_time[i, p]),
                    "objective": float(r.objective[i, p]),
                    "ci_objective": float(r.ci_objective[i, p]),
                    "wait_vs_fifo": float(r.mean_wait[i, p]
                                          - fifo.mean_wait[i, p]),
                    "overflow_frac": float(r.overflow_frac[i, p]),
                })
    for disc in ("sjf", "priority"):
        gain = fifo.mean_wait - res[disc].mean_wait
        emit(f"disciplines.wait_cut.{disc}",
             f"{float(gain.max()):.3f}",
             "max mean-wait reduction vs FIFO (s), CRN-paired")
    # SJF must never wait longer than FIFO on paired streams
    assert np.all(res["sjf"].mean_wait <= fifo.mean_wait + 1e-9)

    qps = grid_queries / max(t_batched, 1e-12)
    emit("disciplines.legacy_s", f"{t_legacy:.2f}",
         "scalar streams + heapq DES over the grid")
    emit("disciplines.batched_s", f"{t_batched:.3f}",
         f"sweep_disciplines steady state, speedup {speedup:.0f}x")
    emit("disciplines.qps", f"{qps:,.0f}", "simulated queries / wall-second")
    emit("disciplines.speedup_ok", bool(speedup >= min_speedup),
         f"acceptance: >= {min_speedup:.0f}x over the heapq loop")

    payload = {
        "mode": "smoke" if args.smoke else "full",
        "grid": {"rhos_u300": list(rhos), "lams": list(map(float, lams)),
                 "policies": {k: list(map(float, v))
                              for k, v in policies.items()},
                 "disciplines": list(DISCIPLINES),
                 "n_seeds": n_seeds, "n_queries": n_queries},
        "timings": {"legacy_s": t_legacy, "batched_s": t_batched,
                    "speedup": speedup, "queries_per_s": qps,
                    "min_speedup": min_speedup},
        "cells": table,
    }
    with open(args.json_out, "w") as fh:
        json.dump(payload, fh, indent=1)
    emit("disciplines.json", args.json_out, "ablation artifact written")

    if args.smoke:
        assert t_batched <= args.budget_s, (
            f"smoke budget blown: {t_batched:.2f}s > {args.budget_s}s")
    assert speedup >= min_speedup, (
        f"batched path only {speedup:.1f}x faster than the heapq loop "
        f"(need {min_speedup:.0f}x)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
