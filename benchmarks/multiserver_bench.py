"""Multi-server + preemptive queueing benchmark: M/G/c and SRPT fast paths.

Two throughput lanes, each against its scalar heapq reference, plus the
Erlang-C/Lee-Longton validation grid:

* **M/G/c**: the batched next-free-server kernel
  (``queueing_sim.multiserver.free_server_numpy``) sweeps a whole
  (c x rho x policy x seed) panel in one call; the legacy path runs one
  ``mg1.event_loop_mgc`` heapq loop per stream. Per-query agreement with
  the heapq oracle is asserted at 1e-9 on an anchor batch, and every
  (c, rho) cell's mean wait must fall within the DES 95% CI plus the
  documented Lee-Longton allowance (``core.mgc``: heavy-traffic exact,
  up to ~15% under-prediction at moderate load) of the analytic
  prediction — the per-cell relative errors are recorded in the artifact.
* **SRPT**: the preemptive ring kernel (``disciplines.srpt_numpy``)
  against one ``mg1.srpt_event_loop`` per stream, pinned per query at
  1e-9, with the pathwise-optimality check (SRPT mean system time never
  above FIFO's on paired streams).

    PYTHONPATH=src python -m benchmarks.multiserver_bench [--smoke]

Either mode writes ``BENCH_multiserver.json`` (``--json-out`` to
relocate) with the validation grid, timings, and speedups. ``--smoke``
shrinks the grid and enforces a wall-clock budget for CI; like the other
smoke lanes, its speedup floor is relaxed relative to the committed
full-run numbers (shared runners are noisy and the smoke grid amortizes
less Python-loop overhead per batched step).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro import compile_cache
from repro.core import paper_problem
from repro.core.mgc import mgc_wait_np
from repro.queueing_sim import (event_loop_mgc, free_server_numpy,
                                generate_streams, srpt_event_loop,
                                srpt_numpy)
from repro.queueing_sim.batched import _service_table, lindley_numpy
from repro.queueing_sim.stats import ci95

from .common import emit

LSTAR = np.array([0.0, 340.0, 0.0, 0.0, 345.0, 30.0])  # ~ paper Table I l*

#: Documented Lee-Longton allowance by load regime (see ``core.mgc``).
LL_RTOL = {0.6: 0.15, 0.9: 0.05}


def _grid(smoke: bool):
    cs = (2, 4)
    rhos = (0.6, 0.9)
    if smoke:
        # streams must still be long enough for the rho = 0.9 cells to mix
        # past the finite-horizon bias, or the validation gate is testing
        # warmup error instead of the approximation
        n_seeds, n_queries, warm_frac = 16, 5000, 0.3
    else:
        n_seeds, n_queries, warm_frac = 16, 10_000, 0.25
    return cs, rhos, n_seeds, n_queries, warm_frac


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + wall-clock budget (CI)")
    ap.add_argument("--budget-s", type=float, default=60.0,
                    help="smoke-mode wall-clock budget for the batched path")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="required batched-vs-heapq speedup on the M/G/c "
                         "lane (default: 8 full / 3 smoke)")
    ap.add_argument("--json-out", default="BENCH_multiserver.json",
                    help="perf/validation artifact path")
    args = ap.parse_args(argv)
    min_speedup = args.min_speedup
    if min_speedup is None:
        min_speedup = 3.0 if args.smoke else 8.0

    prob = paper_problem()
    t_tab = _service_table(prob, LSTAR)
    pi = np.asarray(prob.tasks.pi)
    es = float(np.sum(pi * t_tab))
    cs, rhos, n_seeds, n_queries, warm_frac = _grid(smoke=args.smoke)
    warm = int(warm_frac * n_queries)
    cells = [(c, rho) for c in cs for rho in rhos]
    emit("multiserver.grid", f"{len(cells)}x{n_seeds}x{n_queries}",
         f"c={cs}, rho={rhos}, {len(cells) * n_seeds * n_queries} queries")

    # one batch per cell (its own lam), generated once and reused by both
    # pipelines so the speedup compares identical work
    batches = {}
    for c, rho in cells:
        lam = rho * c / es
        batches[(c, rho)] = generate_streams(prob.tasks, lam, n_seeds,
                                             n_queries, seed=0)

    # --- batched M/G/c pipeline (steady state, best of 4) -----------------
    # the whole (cell x seed) panel rides ONE kernel call: the free-time
    # panel supports per-stream server counts, so cells with different c
    # coexist in the batch and the per-query Python step amortizes over
    # every stream of the grid at once
    arr_all = np.stack([batches[cell].arrivals for cell in cells])
    svc_all = t_tab[np.stack([batches[cell].types for cell in cells])]
    c_all = np.array([c for c, _ in cells])[:, None]       # [cells, 1]

    def run_batched():
        start, finish = free_server_numpy(arr_all, svc_all, c_all)
        return {cell: (start[i], finish[i])
                for i, cell in enumerate(cells)}

    traj = run_batched()          # warm caches
    t_batched = np.inf
    for _ in range(4):
        t0 = time.perf_counter()
        traj = run_batched()
        t_batched = min(t_batched, time.perf_counter() - t0)

    # --- legacy pipeline: one heapq c-server loop per stream --------------
    t_legacy = np.inf
    for _ in range(2):
        t0 = time.perf_counter()
        legacy_wait = {}
        for (c, rho), batch in batches.items():
            svc = t_tab[batch.types]
            waits = np.empty(n_seeds)
            for s in range(n_seeds):
                st, _ = event_loop_mgc(batch.arrivals[s], svc[s],
                                       batch.arrivals[s], c)
                waits[s] = (st - batch.arrivals[s])[warm:].mean()
            legacy_wait[(c, rho)] = waits
        t_legacy = min(t_legacy, time.perf_counter() - t0)
    speedup = t_legacy / max(t_batched, 1e-12)

    # --- correctness: exact anchor + Erlang-C/Lee-Longton validation ------
    anchor_c, anchor_rho = cells[-1]
    batch = batches[(anchor_c, anchor_rho)]
    svc = t_tab[batch.types]
    st_b, fi_b = traj[(anchor_c, anchor_rho)]
    worst = 0.0
    for s in range(min(n_seeds, 4)):
        st_r, fi_r = event_loop_mgc(batch.arrivals[s], svc[s],
                                    batch.arrivals[s], anchor_c)
        worst = max(worst, float(np.max(np.abs(st_b[s] - st_r))),
                    float(np.max(np.abs(fi_b[s] - fi_r))))
    assert worst <= 1e-9, f"batched/heapq anchor deviation {worst:.2e}"
    emit("multiserver.anchor", f"{worst:.1e}",
         "max per-query |batched - heapq| on the anchor cell")

    validation = []
    for (c, rho), batch in batches.items():
        st, _ = traj[(c, rho)]
        waits = (st - batch.arrivals)[:, warm:].mean(axis=1)
        lam = rho * c / es
        pred = float(mgc_wait_np(prob.tasks, LSTAR, lam, c))
        ci = float(ci95(waits))
        gap = float(waits.mean() - pred)
        ok = abs(gap) <= ci + LL_RTOL[rho] * pred
        assert ok, (f"c={c} rho={rho}: DES {waits.mean():.4f}+-{ci:.4f} vs "
                    f"Lee-Longton {pred:.4f}")
        # the legacy pipeline saw the same streams: means must agree
        assert abs(waits.mean() - legacy_wait[(c, rho)].mean()) <= 1e-9
        validation.append({
            "c": c, "rho": rho, "lam": lam,
            "des_mean_wait": float(waits.mean()), "ci95": ci,
            "lee_longton_wait": pred, "gap": gap,
            "rel_error": gap / pred, "allowance_rel": LL_RTOL[rho],
        })
        emit(f"multiserver.validate.c{c}_rho{rho}",
             f"{gap / pred:+.3f}",
             f"DES-vs-Lee-Longton relative gap (ci {ci / pred:.3f})")

    # --- SRPT lane --------------------------------------------------------
    # sweep-shaped batch: the busy-period kernel amortizes over streams,
    # so its lane runs the seed count a discipline sweep would use
    lam1 = 0.8 / es
    srpt_seeds = 96
    sbatch = generate_streams(prob.tasks, lam1, srpt_seeds,
                              min(n_queries, 2000), seed=1)
    ssvc = t_tab[sbatch.types]
    fin_s, ovf = srpt_numpy(sbatch.arrivals, ssvc)      # warm
    t_srpt = np.inf
    for _ in range(3):
        t0 = time.perf_counter()
        fin_s, ovf = srpt_numpy(sbatch.arrivals, ssvc)
        t_srpt = min(t_srpt, time.perf_counter() - t0)
    assert not ovf.any()
    t_srpt_ref = np.inf
    for _ in range(2):
        t0 = time.perf_counter()
        ref_fins = [srpt_event_loop(sbatch.arrivals[s], ssvc[s])
                    for s in range(sbatch.n_seeds)]
        t_srpt_ref = min(t_srpt_ref, time.perf_counter() - t0)
    worst_srpt = max(float(np.max(np.abs(fin_s[s] - ref_fins[s])))
                     for s in range(sbatch.n_seeds))
    assert worst_srpt <= 1e-9, f"srpt anchor deviation {worst_srpt:.2e}"
    srpt_speedup = t_srpt_ref / max(t_srpt, 1e-12)
    # pathwise optimality vs FIFO on the same streams
    _, fifo_fin = lindley_numpy(sbatch.arrivals, ssvc)
    srpt_sys = (fin_s - sbatch.arrivals).mean()
    fifo_sys = (fifo_fin - sbatch.arrivals).mean()
    assert srpt_sys <= fifo_sys + 1e-9
    emit("multiserver.srpt_anchor", f"{worst_srpt:.1e}",
         f"pinned vs heapq; sys cut vs FIFO {fifo_sys - srpt_sys:.3f}s")
    emit("multiserver.srpt_speedup", f"{srpt_speedup:.1f}x",
         f"busy-period kernel vs heapq ({t_srpt:.3f}s vs {t_srpt_ref:.3f}s)")

    grid_queries = len(cells) * n_seeds * n_queries
    qps = grid_queries / max(t_batched, 1e-12)
    emit("multiserver.legacy_s", f"{t_legacy:.2f}", "heapq loops, full grid")
    emit("multiserver.batched_s", f"{t_batched:.3f}",
         f"next-free-server kernel, speedup {speedup:.1f}x")
    emit("multiserver.qps", f"{qps:,.0f}", "simulated queries / wall-second")
    emit("multiserver.speedup_ok", bool(speedup >= min_speedup),
         f"acceptance: >= {min_speedup:.0f}x over the heapq loop")

    payload = {
        "mode": "smoke" if args.smoke else "full",
        "grid": {"cs": list(cs), "rhos": list(rhos), "n_seeds": n_seeds,
                 "n_queries": n_queries, "warmup": warm,
                 "policy": list(map(float, LSTAR))},
        "timings": {"legacy_s": t_legacy, "batched_s": t_batched,
                    "speedup": speedup, "queries_per_s": qps,
                    "min_speedup": min_speedup,
                    "srpt_kernel_s": t_srpt, "srpt_heapq_s": t_srpt_ref,
                    "srpt_speedup": srpt_speedup},
        "validation": validation,
        "srpt": {"lam": lam1, "mean_system_time": float(srpt_sys),
                 "fifo_mean_system_time": float(fifo_sys),
                 "anchor_max_abs": worst_srpt},
        "anchor_max_abs": worst,
    }
    with open(args.json_out, "w") as fh:
        json.dump(payload, fh, indent=1)
    emit("multiserver.json", args.json_out, "artifact written")

    if args.smoke:
        assert t_batched <= args.budget_s, (
            f"smoke budget blown: {t_batched:.2f}s > {args.budget_s}s")
    assert speedup >= min_speedup, (
        f"batched M/G/c path only {speedup:.1f}x faster than the heapq "
        f"loop (need {min_speedup:.0f}x)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
