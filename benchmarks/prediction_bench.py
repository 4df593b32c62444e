"""Prediction-aware scheduling: zero-error pins + the robustness frontier.

The predicted disciplines (SPJF/SPRPT, ``queueing_sim.disciplines``) only
earn their keep if (a) at zero prediction error they are *exactly* the
known-size SJF/SRPT schedulers — pinned here bitwise on the NumPy and JAX
lanes against the heapq oracles — and (b) their advantage over size-blind
FIFO degrades gracefully as prediction error grows. This benchmark runs
both checks and produces the robustness frontier
(``sweeps.sweep_prediction_error``) on a heavy-tailed operating point
(all reasoning budget on one task, service CV^2 ~ 4.7), where the
documented structure is:

* the **mean-wait** advantage of SPJF/SPRPT over FIFO survives every
  error level swept (with CV^2 > 1, even size-blind preemption beats
  FIFO in the mean);
* the **p99-wait** advantage dies at a finite error level: SPRPT's tail
  crosses FIFO at sigma ~ 0.3-0.7 (underestimated long jobs monopolize
  the server; short jobs queue behind them), the headline
  ``fifo_crossover_sigma`` gated in CI against this artifact.

The frontier's FIFO/SJF/SRPT reference lanes are cross-checked against
``sweep_disciplines`` (the batched discipline engine) on common random
numbers to float noise — same streams, two independent drivers.

    PYTHONPATH=src python -m benchmarks.prediction_bench [--smoke]

Either mode writes ``BENCH_prediction.json`` (``--json-out`` to
relocate); ``--smoke`` shrinks the grid and enforces a wall-clock
budget, for CI (gated by ``benchmarks.report --check``).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro import compile_cache
from repro.core import paper_problem
from repro.data.predictor import LengthPredictor
from repro.queueing_sim import generate_streams, sweep_disciplines
from repro.queueing_sim.disciplines import (discipline_keys,
                                            sprpt_start_finish,
                                            srpt_start_finish,
                                            windowed_start_finish)
from repro.queueing_sim.mg1 import (event_loop, sprpt_event_loop,
                                    srpt_event_loop)
from repro.sweeps.prediction import (fifo_crossover_sigma, service_cv2,
                                     sweep_prediction_error)

from .common import emit

# heavy-tailed operating point: the whole reasoning budget on one task
# (CV^2 ~ 4.7) — the regime where size-based scheduling wins the tail at
# zero error, so the error level that *loses* the tail is identifiable
HEAVY = np.array([2000.0, 0.0, 0.0, 0.0, 0.0, 0.0])


def _grid(smoke: bool):
    if smoke:
        sigmas = np.array([0.0, 0.3, 0.6, 1.0, 2.0])
        rhos = (0.8,)
        n_seeds, n_queries = 8, 1500
    else:
        sigmas = np.array([0.0, 0.2, 0.4, 0.6, 0.8, 1.0, 1.5, 2.0, 2.5, 3.0])
        rhos = (0.5, 0.8)
        n_seeds, n_queries = 16, 4000
    return sigmas, rhos, n_seeds, n_queries


def _zero_error_pins(prob, n: int = 1200) -> None:
    """SPJF==SJF and SPRPT==SRPT bitwise at zero error, every lane."""
    batch = generate_streams(prob.tasks, 0.19, 2, n, seed=7)
    t = prob.tasks
    svc = (np.asarray(t.t0) + np.asarray(t.c) * HEAVY)[batch.types]
    arr = batch.arrivals
    oracle = LengthPredictor().predict(svc)          # bitwise identity
    k_sjf = discipline_keys("sjf", services=svc)
    k_spjf = discipline_keys("spjf", services=svc, predicted=oracle)
    for backend in ("numpy", "jax"):
        st1, f1, _ = windowed_start_finish(arr, svc, k_sjf, backend=backend)
        st2, f2, _ = windowed_start_finish(arr, svc, k_spjf, backend=backend)
        assert np.array_equal(f1, f2) and np.array_equal(st1, st2), (
            f"spjf != sjf bitwise at zero error ({backend} lane)")
    _, f_srpt, _ = srpt_start_finish(arr, svc)
    _, f_sprpt, _ = sprpt_start_finish(arr, svc, oracle)
    assert np.array_equal(f_srpt, f_sprpt), \
        "sprpt != srpt bitwise at zero error (panel kernel)"
    # heapq oracles: kernels vs event loops per stream, and the zero-error
    # event-loop identity itself
    for s in range(batch.n_seeds):
        a_s, s_s = arr[s], svc[s]
        assert np.array_equal(srpt_event_loop(a_s, s_s),
                              sprpt_event_loop(a_s, s_s, s_s.copy())), \
            "sprpt_event_loop != srpt_event_loop at zero error"
        _, f_ref = event_loop(a_s, s_s, s_s)
        assert np.abs(f1[s] - f_ref).max() < 1e-9, "sjf lane vs heapq"
        assert np.abs(f_sprpt[s]
                      - sprpt_event_loop(a_s, s_s, s_s.copy())).max() < 1e-9
    emit("prediction.zero_error_pins", "ok",
         "spjf==sjf, sprpt==srpt bitwise (numpy+jax lanes, heapq oracles)")


def _crn_crosscheck(prob, lams, n_seeds, n_queries) -> float:
    """Frontier reference lanes vs sweep_disciplines on the same streams."""
    fr = sweep_prediction_error(prob, HEAVY, lams, np.array([0.0]),
                                n_seeds=n_seeds, n_queries=n_queries, seed=0)
    res = sweep_disciplines(prob, {"heavy": HEAVY}, lams,
                            disciplines=("fifo", "sjf", "srpt"),
                            n_seeds=n_seeds, n_queries=n_queries, seed=0,
                            clip_unstable=False)
    worst = 0.0
    for d in ("fifo", "sjf", "srpt"):
        a = fr.mean_wait[d]
        b = res[d].mean_wait[:, 0]
        worst = max(worst, float(np.max(np.abs(a - b)
                                        / np.maximum(np.abs(b), 1e-12))))
    assert worst < 1e-8, f"frontier vs sweep_disciplines CRN gap {worst}"
    return worst


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + wall-clock budget (CI)")
    ap.add_argument("--budget-s", type=float, default=60.0,
                    help="smoke-mode wall-clock budget for the frontier")
    ap.add_argument("--json-out", default="BENCH_prediction.json",
                    help="frontier artifact path")
    args = ap.parse_args(argv)

    prob = paper_problem()
    sigmas, rhos, n_seeds, n_queries = _grid(args.smoke)
    t = np.asarray(prob.tasks.t0) + np.asarray(prob.tasks.c) * HEAVY
    es = float(np.sum(np.asarray(prob.tasks.pi) * t))
    lams = np.array([r / es for r in rhos])
    cv2 = service_cv2(prob, HEAVY)
    emit("prediction.grid",
         f"{len(lams)}x{len(sigmas)}x{n_seeds}x{n_queries}",
         f"rho={rhos}, cv2={cv2:.2f}")

    _zero_error_pins(prob)
    crn_gap = _crn_crosscheck(prob, lams, min(n_seeds, 8),
                              min(n_queries, 2000))
    emit("prediction.crn_gap", f"{crn_gap:.2e}",
         "frontier refs vs sweep_disciplines, common random numbers")

    # --- the frontier (steady state, best of 2) ---------------------------
    run = lambda: sweep_prediction_error(prob, HEAVY, lams, sigmas,
                                         n_seeds=n_seeds,
                                         n_queries=n_queries, seed=0)
    fr = run()  # warm caches
    t_frontier = np.inf
    for _ in range(2):
        t0 = time.perf_counter()
        fr = run()
        t_frontier = min(t_frontier, time.perf_counter() - t0)
    # lanes simulated: fifo + sjf + srpt references + G spjf + G sprpt
    lanes = 3 + 2 * len(sigmas)
    grid_queries = len(lams) * lanes * n_seeds * n_queries
    qps = grid_queries / max(t_frontier, 1e-12)

    # frontier structure at the heaviest load (last lambda):
    xover = {
        "sprpt_p99": fifo_crossover_sigma(fr, "sprpt", "p99_wait", -1),
        "spjf_p99": fifo_crossover_sigma(fr, "spjf", "p99_wait", -1),
        "sprpt_mean": fifo_crossover_sigma(fr, "sprpt", "mean_wait", -1),
        "spjf_mean": fifo_crossover_sigma(fr, "spjf", "mean_wait", -1),
    }
    # (a) at zero error the frontier's left edge IS the reference lane
    assert np.array_equal(fr.mean_wait["spjf"][0], fr.mean_wait["sjf"])
    assert np.array_equal(fr.mean_wait["sprpt"][0], fr.mean_wait["srpt"])
    # (b) the SPRPT tail crossover is finite and in the documented band:
    # prediction error costs the tail long before it costs the mean
    assert np.isfinite(xover["sprpt_p99"]), \
        "no FIFO p99 crossover found for sprpt — frontier structure lost"
    assert 0.05 < xover["sprpt_p99"] < 2.5, \
        f"sprpt p99 crossover {xover['sprpt_p99']:.3f} outside [0.05, 2.5]"
    # (c) the mean-wait advantage survives the whole sweep (CV^2 > 1)
    assert np.all(fr.mean_wait["spjf"] < fr.mean_wait["fifo"][None, :]), \
        "spjf mean wait crossed FIFO on a CV^2>1 workload"
    assert np.all(fr.mean_wait["sprpt"] < fr.mean_wait["fifo"][None, :]), \
        "sprpt mean wait crossed FIFO on a CV^2>1 workload"
    emit("prediction.crossover.sprpt_p99", f"{xover['sprpt_p99']:.3f}",
         "error level where SPRPT's tail advantage over FIFO dies")
    emit("prediction.mean_advantage", "ok",
         "SPJF/SPRPT mean wait < FIFO at every swept sigma")
    emit("prediction.frontier_s", f"{t_frontier:.3f}",
         f"{grid_queries} simulated queries, {qps:,.0f}/s")

    payload = {
        "mode": "smoke" if args.smoke else "full",
        "grid": {"rhos": list(rhos), "lams": lams.tolist(),
                 "sigmas": sigmas.tolist(), "lengths": HEAVY.tolist(),
                 "cv2": cv2, "n_seeds": n_seeds, "n_queries": n_queries},
        "crossover": {k: (v if np.isfinite(v) else None)
                      for k, v in xover.items()},
        "crn_gap": crn_gap,
        "timings": {"frontier_s": t_frontier, "queries_per_s": qps},
        "frontier": fr.summary(),
    }
    with open(args.json_out, "w") as fh:
        json.dump(payload, fh, indent=1)
    emit("prediction.json", args.json_out, "frontier artifact written")

    if args.smoke:
        assert t_frontier <= args.budget_s, (
            f"smoke budget blown: {t_frontier:.2f}s > {args.budget_s}s")


if __name__ == "__main__":
    compile_cache.enable()
    main()
