"""Overload-resilience benchmark: the degradation ladder vs a naive
(no-admission) loop under an arrival burst, plus the retry-storm
metastability curve pinned to the analytic effective-arrival-rate fixed
point.

Two lanes:

* **burst** — the paper problem re-rated to rho = 0.6 at its own oracle
  budgets, hit with a compressed-arrival burst that lifts the offered
  load to 2x capacity, with stragglers, poisoned observations and
  dropped completions riding along (``repro.faults``). The same trace
  and fault bank run twice: once through the guarded stack
  (``AdmissionController`` degradation ladder + drift-gated re-solve),
  once through a naive FIFO that serves every request at the static
  oracle budgets. The guarded stack must win on BOTH deadline-goodput
  and p99 wait, and recover to the steady-state wait level no later
  than the naive loop.
* **retry** — M/G/1 with deadlines and orphaned-service retries
  (``queueing_sim.impatience``): sweeps client patience at rho = 0.95
  and scores the goodput collapse (metastability), pins the batched
  NumPy lane bitwise against the heapq reference, and checks the
  ``core.queueing.retry_fixed_point`` effective arrival rate against
  the DES at a stable operating point (and its lam * (K + 1) pin at an
  unstable one).

    PYTHONPATH=src python -m benchmarks.resilience_bench [--smoke]

Writes ``BENCH_resilience.json`` (``--json-out`` to relocate). The
committed artifact is a full run; CI runs ``--smoke`` and gates the
machine-independent ratios through ``benchmarks/report.py --check``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

from repro import compile_cache
from repro.core import paper_problem, retry_fixed_point
from repro.core.allocator import solve
from repro.faults import (ArrivalBurst, DroppedCompletions, FaultSet,
                          ObservationCorruption, StragglerDecode)
from repro.obs.monitor import DriftMonitor
from repro.queueing_sim import (RetryPolicy, Segment, generate_drift_trace,
                                impatience_event_loop, impatience_numpy,
                                summarize_impatience)
from repro.serving import (AdmissionConfig, AdmissionController,
                           ReplayConfig, ReplayHarness)

from .common import emit

#: a completion is "timely" when it finishes within this many seconds of
#: arrival — roughly 10x the steady-state system time of the burst-lane
#: operating point, so steady traffic always makes it and burst-bloated
#: waits do not
DEADLINE_S = 10.0


def _recovery_s(blocks, burst_t0: float, burst_t1: float,
                horizon: float) -> float:
    """Seconds after the burst until block mean waits return to twice the
    pre-burst steady level; the full remaining horizon when they never do."""
    pre = [b.mean_wait for b in blocks[2:] if b.t_end < burst_t0]
    steady = float(np.mean(pre)) if pre else 0.0
    bar = max(2.0 * steady, 0.5)
    for b in blocks:
        if b.t_start >= burst_t1 and b.mean_wait <= bar:
            return float(b.t_start - burst_t1)
    return float(horizon - burst_t1)


def burst_lane(prob, n_queries: int, overload_rho: float = 2.0) -> dict:
    """Ladder vs naive on the same burst trace and fault bank."""
    oracle = np.asarray(solve(prob).lengths_int, dtype=np.int64)
    t0v = np.asarray(prob.tasks.t0)
    cv = np.asarray(prob.tasks.c)
    pi = np.asarray(prob.tasks.pi)
    es = float(np.sum(pi * (t0v + cv * oracle)))
    rho0 = 0.6
    lam0 = rho0 / es
    hot = dataclasses.replace(
        prob, server=dataclasses.replace(prob.server, lam=lam0))
    oracle_hot = np.asarray(solve(hot).lengths_int, dtype=np.int64)
    es_hot = float(np.sum(pi * (t0v + cv * oracle_hot)))
    factor = overload_rho / (lam0 * es_hot)

    trace = generate_drift_trace(hot.tasks, [Segment(n_queries, lam0)],
                                 seed=13)
    # burst window in ORIGINAL arrival time: queries [30%, 65%] of the
    # trace; after gap compression it spans [t_b0, t_b0 + dt / factor]
    t_b0 = float(trace.arrivals[int(0.30 * n_queries)])
    t_b1 = float(trace.arrivals[int(0.65 * n_queries)])
    burst_end = t_b0 + (t_b1 - t_b0) / factor

    def fault_bank():
        return FaultSet(ArrivalBurst(t_b0, t_b1, factor),
                        StragglerDecode(0.02, 2.0, seed=1),
                        ObservationCorruption(0.02, "nan", seed=2),
                        DroppedCompletions(0.02, seed=3))

    cfg = ReplayConfig(block_size=256, resolve_mode="drift",
                       est_halflife=128.0)
    arms = {}
    for name, adm, fixed in (
            ("ladder", AdmissionController(
                oracle_hot, hot.server.l_max,
                AdmissionConfig(rho_high=0.85, rho_low=0.6,
                                dwell_down=800.0)), None),
            # naive FIFO: every request served at the static oracle
            # budgets — no ladder, no re-solve, no shedding
            ("naive", None, oracle_hot)):
        t_wall = time.perf_counter()
        res = ReplayHarness(hot, cfg, monitor=DriftMonitor(),
                            admission=adm,
                            faults=fault_bank()).run_virtual(
                                trace, fixed_lengths=fixed)
        elapsed = time.perf_counter() - t_wall
        sm = res.served_mask()
        gp = res.goodput(DEADLINE_S)
        rec = _recovery_s(res.blocks, t_b0, burst_end,
                          float(res.arrivals[-1]))
        arms[name] = {
            "elapsed_s": elapsed,
            "queries_per_s": n_queries / elapsed,
            "goodput": gp["goodput"],
            "n_good": gp["n_good"],
            "shed_fraction": gp["shed_fraction"],
            "p99_wait": float(np.percentile(res.waits[sm], 99)),
            "mean_wait": float(res.waits[sm].mean()),
            "recovery_s": rec,
            "n_resolves": res.n_resolves,
            "max_level": (max(b.level for b in res.blocks)
                          if adm is not None else 0),
            "final_level": (res.admission["level"]
                            if adm is not None else 0),
            "degradation_occupancy":
                ({str(k): v for k, v in
                  res.admission["occupancy"].items()}
                 if adm is not None else None),
            "budget_linf_gap":
                int(np.max(np.abs(res.final_budgets - oracle_hot))),
        }
        emit(f"resilience.burst.{name}.goodput",
             f"{gp['goodput']:.4f}",
             f"p99_wait={arms[name]['p99_wait']:.2f}s, "
             f"recovery={rec:.0f}s")

    lad, nai = arms["ladder"], arms["naive"]
    out = {
        "n_queries": n_queries, "lam0": lam0, "rho0": rho0,
        "burst_factor": factor, "overload_rho": overload_rho,
        "deadline_s": DEADLINE_S,
        "burst_window_s": [t_b0, burst_end],
        "ladder": lad, "naive": nai,
        "goodput_ratio": lad["goodput"] / max(nai["goodput"], 1e-12),
        "p99_wait_ratio": lad["p99_wait"] / max(nai["p99_wait"], 1e-12),
        "recovery_ratio": lad["recovery_s"] / max(nai["recovery_s"], 1e-9),
    }
    emit("resilience.burst.goodput_ratio", f"{out['goodput_ratio']:.3f}",
         "ladder vs naive under overload; must be > 1")
    emit("resilience.burst.p99_wait_ratio", f"{out['p99_wait_ratio']:.3f}",
         "ladder vs naive; must be < 1")
    # the headline claim, asserted in both modes: under overload the
    # ladder sustains strictly higher goodput AND lower p99 wait
    assert out["goodput_ratio"] > 1.0, \
        f"ladder goodput did not beat naive: {out['goodput_ratio']:.3f}"
    assert out["p99_wait_ratio"] < 1.0, \
        f"ladder p99 wait did not beat naive: {out['p99_wait_ratio']:.3f}"
    assert lad["recovery_s"] <= nai["recovery_s"], \
        "ladder recovered later than naive"
    assert lad["max_level"] >= 1 and lad["final_level"] == 0, \
        "ladder never engaged or never de-escalated"
    return out


def retry_lane(n: int, rho: float = 0.95,
               taus=(200.0, 50.0, 20.0, 10.0, 5.0, 2.0)) -> dict:
    """Metastability curve + lane pin + analytic fixed-point check."""
    rng = np.random.default_rng(11)
    a = np.cumsum(rng.exponential(1.0 / rho, size=n))
    s = rng.exponential(1.0, size=n)
    lam = 1.0 / float(np.diff(a).mean())
    es, es2 = float(s.mean()), float((s ** 2).mean())

    # lane pin: the batched NumPy lane must match the heapq reference
    # bitwise on a retrying policy before any of its numbers are trusted
    pin_pol = RetryPolicy(patience=taus[-1], max_retries=3, backoff0=0.5)
    n_pin = min(n, 1500)
    ref = impatience_event_loop(a[:n_pin], s[:n_pin], pin_pol)
    got = impatience_numpy(a[:n_pin], s[:n_pin], pin_pol)
    pin_ok = (np.array_equal(got.served, ref.served)
              and np.array_equal(got.wait, ref.wait, equal_nan=True))
    assert pin_ok, "impatience NumPy lane diverged from heapq reference"

    curve = []
    t_wall = time.perf_counter()
    for tau in taus:
        pol = RetryPolicy(patience=float(tau), max_retries=3, backoff0=0.5)
        res = impatience_numpy(a, s, pol)
        summ = summarize_impatience(res, a, s, pol)
        fp = retry_fixed_point(lam, es, es2, patience=float(tau),
                               max_retries=3)
        curve.append({
            "patience": float(tau),
            "goodput": summ["goodput"],
            "timeout_frac": summ["timeout_frac"],
            "lam_eff_measured": summ["lam_eff"],
            "lam_eff_analytic": fp.lam_eff,
            "stable_analytic": bool(fp.stable),
        })
        emit(f"resilience.retry.tau{tau:g}.goodput",
             f"{summ['goodput']:.4f}",
             f"lam_eff={summ['lam_eff']:.3f} "
             f"(analytic {fp.lam_eff:.3f}, "
             f"{'stable' if fp.stable else 'UNSTABLE'})")
    elapsed = time.perf_counter() - t_wall
    good = [r["goodput"] for r in curve]
    collapse = good[-1] / max(good[0], 1e-12)
    # the metastability curve: goodput monotone non-increasing as
    # patience tightens, ending in collapse — not graceful degradation
    assert all(g0 >= g1 - 1e-9 for g0, g1 in zip(good, good[1:])), \
        f"goodput not monotone along the patience sweep: {good}"
    assert collapse < 0.3, \
        f"no retry-storm collapse: goodput ratio {collapse:.3f}"
    # impatient retries pin the attempt rate at lam * (K + 1)
    assert curve[-1]["lam_eff_measured"] > 0.85 * lam * 4

    # fixed point vs DES at a STABLE operating point (rho = 0.7,
    # patient): the analytic effective rate must match the measured one
    a2 = np.cumsum(rng.exponential(1.0 / 0.7, size=n))
    s2 = rng.exponential(1.0, size=n)
    lam2 = 1.0 / float(np.diff(a2).mean())
    pol2 = RetryPolicy(patience=30.0, max_retries=3, backoff0=0.5)
    meas2 = summarize_impatience(impatience_numpy(a2, s2, pol2),
                                 a2, s2, pol2)["lam_eff"]
    fp2 = retry_fixed_point(lam2, float(s2.mean()), float((s2 ** 2).mean()),
                            patience=30.0, max_retries=3)
    rel_err = abs(fp2.lam_eff - meas2) / meas2
    emit("resilience.retry.lam_eff_rel_err", f"{rel_err:.4f}",
         f"analytic={fp2.lam_eff:.4f} vs DES={meas2:.4f} at rho=0.7")
    assert fp2.stable and fp2.converged
    assert rel_err < 0.1, \
        f"fixed point off the DES by {rel_err:.3f} at a stable point"
    return {
        "n": n, "rho": rho, "lam": lam, "elapsed_s": elapsed,
        "attempts_per_s": n * len(taus) / elapsed,
        "curve": curve,
        "collapse_ratio": collapse,
        "lane_pin_ok": bool(pin_ok),
        "fixed_point": {
            "rho": 0.7, "patience": 30.0,
            "lam_eff_analytic": fp2.lam_eff,
            "lam_eff_measured": meas2,
            "lam_eff_rel_err": rel_err,
            "stable": bool(fp2.stable),
        },
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small lanes + relaxed floors (CI)")
    ap.add_argument("--json-out", default="BENCH_resilience.json")
    args = ap.parse_args(argv)

    n_burst, n_retry = (12_000, 4_000) if args.smoke else (40_000, 20_000)

    prob = paper_problem()
    out = {
        "mode": "smoke" if args.smoke else "full",
        "burst": burst_lane(prob, n_burst),
        "retry": retry_lane(n_retry),
    }
    with open(args.json_out, "w") as f:
        json.dump(out, f, indent=1)
    emit("resilience.artifact", args.json_out, out["mode"])


if __name__ == "__main__":
    compile_cache.enable()
    main()
