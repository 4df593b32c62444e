"""Closed-loop replay benchmark: the allocator<->engine digital twin.

Four lanes, each exercising one claim of ``serving.replay``:

* **virtual** — the full closed loop (estimate -> re-solve -> serve) over a
  long stationary trace through the virtual plant; times the loop and
  checks the converged budgets land next to the oracle solution that a
  clairvoyant solver (true lambda / pi / latency curve) produces.
* **crn** — fixed-policy virtual replay against the batched Lindley DES on
  common random numbers at rho in {0.6, 0.9}: per-query waits must agree
  to float round-off, and the P-K prediction must fall inside the DES 95%
  CI over the seed batch (millions of simulated queries; the acceptance
  gate of the twin's queueing kernel).
* **drift** — piecewise-stationary lambda and pi shifts; scores end-of-
  segment tracking error of the online estimators and confirms the
  deployed budgets actually move when the operating point does.
* **engine** — the REAL chunked-scan decode path (reduced model): per-
  request wall-clock services replayed through the same Lindley recursion,
  measured accuracy/system time compared against the twin's own P-K
  prediction at its estimated operating point via
  ``sweeps.frontier_comparison`` (zero oracle latency parameters).

    PYTHONPATH=src python -m benchmarks.replay_bench [--smoke]

Writes ``BENCH_replay.json`` (``--json-out`` to relocate). The committed
artifact is a full run; CI runs ``--smoke`` and gates it against the
committed numbers through ``benchmarks/report.py --check``.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro import compile_cache
from repro.core import paper_problem
from repro.core.allocator import solve
from repro.queueing_sim import (Segment, ci95, generate_drift_trace,
                                generate_streams, trace_from_stream_batch)
from repro.queueing_sim.batched import lindley_numpy
from repro.serving import ReplayConfig, ReplayHarness
from repro.sweeps import frontier_comparison, saturation_rate

from .common import emit


def virtual_lane(prob, n_queries: int) -> dict:
    """Closed loop on a stationary trace; converged budgets vs oracle."""
    lam = prob.server.lam
    trace = generate_drift_trace(prob.tasks, [Segment(n_queries, lam)],
                                 seed=7)
    h = ReplayHarness(prob, ReplayConfig(block_size=512))
    t0 = time.perf_counter()
    res = h.run_virtual(trace)
    elapsed = time.perf_counter() - t0
    oracle = np.asarray(solve(prob).lengths_int, dtype=np.int64)
    gap = int(np.max(np.abs(res.final_budgets - oracle)))
    est = res.estimator_state
    lam_acc = 1.0 - abs(est["lam"] - lam) / lam
    c_rel = float(np.max(np.abs(np.asarray(est["c"])
                                - np.asarray(prob.tasks.c))
                         / np.asarray(prob.tasks.c)))
    m = res.measured()
    pred = h.predicted(lam)
    # tail scoring: measured wait percentiles (exact, from the report)
    # vs the M/G/1 exponential-tail prediction at the deployed budgets
    rep = res.report(prob)
    comp = frontier_comparison(
        [m["accuracy_prob"]], [m["mean_system_time"]],
        [pred["accuracy"]], [pred["mean_system_time"]],
        measured_percentiles=rep.wait_percentiles,
        predicted_percentiles=pred["wait_percentiles"],
        drift=rep.drift)
    emit("replay.virtual.queries_per_s", f"{n_queries / elapsed:.0f}",
         f"n={n_queries}, resolves={res.n_resolves}")
    emit("replay.virtual.budget_linf_gap", gap,
         f"final={list(res.final_budgets)}, oracle={list(oracle)}")
    emit("replay.virtual.lam_accuracy", f"{lam_acc:.4f}",
         f"lam_hat={est['lam']:.5f}, true={lam}")
    emit("replay.virtual.p90_wait_rel_gap",
         f"{comp['rel_gap_percentiles'].get('p90', 0.0):.3f}",
         f"measured={rep.wait_percentiles.get('p90', 0.0):.3f}s, "
         f"exp-tail={pred['wait_percentiles'].get('p90', 0.0):.3f}s")
    return {
        "n_queries": n_queries,
        "elapsed_s": elapsed,
        "queries_per_s": n_queries / elapsed,
        "n_resolves": res.n_resolves,
        "final_budgets": [int(v) for v in res.final_budgets],
        "oracle_budgets": [int(v) for v in oracle],
        "budget_linf_gap": gap,
        "measured_system_time": m["mean_system_time"],
        "predicted_system_time": pred["mean_system_time"],
        "measured_accuracy_prob": m["accuracy_prob"],
        "predicted_accuracy": pred["accuracy"],
        "measured_wait_percentiles": rep.wait_percentiles,
        "predicted_wait_percentiles": pred["wait_percentiles"],
        "rel_gap_percentiles": comp["rel_gap_percentiles"],
        "estimation": {
            "lam_hat": est["lam"], "lam_true": lam,
            "lam_accuracy": lam_acc,
            "c_max_rel_err": c_rel,
            "pi_linf_err": float(np.max(np.abs(
                np.asarray(est["pi"]) - np.asarray(prob.tasks.pi)))),
        },
    }


def crn_lane(prob, rhos, n_seeds: int, n_queries: int) -> dict:
    """Fixed-policy replay vs batched DES on common random numbers."""
    lengths = np.asarray(solve(prob).lengths_int, dtype=np.int64)
    t0 = np.asarray(prob.tasks.t0)
    c = np.asarray(prob.tasks.c)
    es = float(np.sum(np.asarray(prob.tasks.pi) * (t0 + c * lengths)))
    out = {}
    for rho in rhos:
        lam = rho / es
        batch = generate_streams(prob.tasks, lam, n_seeds, n_queries,
                                 seed=11)
        # DES: every replicate in one vectorized Lindley pass
        s = t0[batch.types] + c[batch.types] * lengths[batch.types]
        start, _ = lindley_numpy(batch.arrivals, s)
        waits = start - batch.arrivals
        warm = int(0.25 * n_queries)
        per_seed = waits[:, warm:].mean(axis=1)
        des_mean = float(per_seed.mean())
        des_ci = float(ci95(per_seed))
        # replay: replicate 0 through the harness (identical randomness)
        res = ReplayHarness(prob).run_virtual(
            trace_from_stream_batch(batch, 0), fixed_lengths=lengths)
        max_diff = float(np.max(np.abs(res.waits - waits[0])))
        es2 = float(np.sum(np.asarray(prob.tasks.pi)
                           * (t0 + c * lengths) ** 2))
        pk_wait = lam * es2 / (2 * (1 - lam * es))
        in_ci = bool(abs(pk_wait - des_mean) <= des_ci)
        emit(f"replay.crn.rho{rho}.max_abs_wait_diff", f"{max_diff:.2e}",
             f"pk={pk_wait:.3f} vs des={des_mean:.3f}+-{des_ci:.3f}")
        assert max_diff < 1e-8, \
            f"replay/DES CRN divergence at rho={rho}: {max_diff}"
        out[str(rho)] = {
            "lam": lam, "max_abs_wait_diff": max_diff,
            "des_mean_wait": float(des_mean), "des_ci95": float(des_ci),
            "pk_mean_wait": float(pk_wait), "pk_in_ci": in_ci,
            "n_seeds": n_seeds, "n_queries": n_queries,
        }
    return out


def drift_lane(prob, n_per_segment: int) -> dict:
    """Piecewise-stationary lambda and pi shifts; end-of-segment tracking."""
    lam0 = prob.server.lam
    sat = saturation_rate(prob.tasks)
    n = prob.tasks.n_tasks
    pi_shift = np.full(n, 0.4 / (n - 1))
    pi_shift[1] = 0.6                      # mass onto GSM8K
    segments = [
        Segment(n_per_segment, lam0),
        Segment(n_per_segment, min(3.0 * lam0, 0.5 * sat)),
        Segment(n_per_segment, lam0, pi=tuple(pi_shift)),
    ]
    trace = generate_drift_trace(prob.tasks, segments, seed=13)
    cfg = ReplayConfig(block_size=256, est_halflife=512.0)
    h = ReplayHarness(prob, cfg)
    res = h.run_virtual(trace)
    seg_rows = []
    budgets_per_seg = []
    for s_idx, seg in enumerate(segments):
        # last block whose requests all belong to this segment
        lo = s_idx * n_per_segment
        hi = lo + n_per_segment
        blk = [b for i, b in enumerate(res.blocks)
               if (i + 1) * cfg.block_size <= hi] or [res.blocks[0]]
        est = blk[-1].estimator
        rel = abs(est["lam"] - seg.lam) / seg.lam
        budgets_per_seg.append(blk[-1].budgets)
        seg_rows.append({
            "lam_true": seg.lam, "lam_hat_end": est["lam"],
            "lam_rel_err_end": rel,
            "pi_hat_end": est["pi"],
        })
        emit(f"replay.drift.seg{s_idx}.lam_rel_err", f"{rel:.3f}",
             f"true={seg.lam:.4f}, hat={est['lam']:.4f}")
    moved = bool(np.any(budgets_per_seg[0] != budgets_per_seg[1]))
    emit("replay.drift.budgets_moved", moved,
         f"seg0={list(budgets_per_seg[0])}, seg1={list(budgets_per_seg[1])}")
    return {"segments": seg_rows, "budgets_moved": moved,
            "budgets_per_segment": [[int(v) for v in b]
                                    for b in budgets_per_seg],
            "n_resolves": res.n_resolves}


def engine_lane(prob, n_decodes: int, rho_target: float = 0.6) -> dict:
    """Real chunked-scan decodes through the twin; measured point vs the
    twin's own P-K prediction at its ESTIMATED operating point."""
    import jax

    from repro.configs import get_config
    from repro.core import Problem, ServerParams
    from repro.models import init_params, reduced
    from repro.serving.engine import DecodeEngine

    cfg = reduced(get_config("qwen3-0.6b"), d_model=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, cache_capacity=128, chunk=16)

    l_max = 48.0
    small = Problem(tasks=prob.tasks,
                    server=ServerParams(prob.server.lam, 2.0, l_max))
    rcfg = ReplayConfig(block_size=max(16, n_decodes // 8), l_init=16,
                        est_halflife=128.0, explore_frac=0.25,
                        explore_min_spread=8, min_services=8)
    h = ReplayHarness(small, rcfg, engine=eng)

    # probe the wall-clock service scale (post-compile) to pick an arrival
    # rate at the target utilization — no oracle latency curve involved
    prompt = (np.arange(8) % 97 + 1).astype(np.int32)[None, :]
    eng.generate(prompt, [rcfg.l_init], max_extra_tokens=0)
    probes = []
    for _ in range(3):
        w0 = time.perf_counter()
        eng.generate(prompt, [rcfg.l_init], max_extra_tokens=0)
        probes.append(time.perf_counter() - w0)
    es_probe = float(np.median(probes))
    lam = rho_target / es_probe
    trace = generate_drift_trace(prob.tasks, [Segment(n_decodes, lam)],
                                 seed=17, prompt_len_range=(8, 8))
    t0 = time.perf_counter()
    res = h.run_engine(trace, prompt_len=8, max_extra_tokens=0)
    elapsed = time.perf_counter() - t0
    m = res.measured(warmup_frac=0.25)
    est = res.estimator_state
    # the twin's prediction: P-K at the ESTIMATED moments + the analytic
    # accuracy curve at the deployed budgets (no plant parameters)
    pred_wait = est["pk_wait"]
    pred_sys = pred_wait + est["es"]
    A = np.asarray(small.tasks.A)
    b = np.asarray(small.tasks.b)
    D = np.asarray(small.tasks.D)
    pi = np.asarray(est["pi"])
    lb = res.final_budgets
    pred_acc = float(np.sum(pi * (A * (1 - np.exp(-b * lb)) + D)))
    comp = frontier_comparison(
        [m["accuracy_prob"]], [m["mean_system_time"]],
        [pred_acc], [pred_sys], ci_system_time=[m["ci95_system_time"]])
    tok = int(res.budgets.sum())
    emit("replay.engine.tok_per_s", f"{tok / elapsed:.0f}",
         f"decodes={n_decodes}, real chunked-scan services")
    emit("replay.engine.rel_gap_system_time",
         f"{comp['max_rel_gap_system_time']:.3f}",
         f"measured={m['mean_system_time']:.3f}s, twin={pred_sys:.3f}s")
    return {
        "n_decodes": n_decodes, "elapsed_s": elapsed,
        "tokens_generated": tok, "tok_per_s": tok / elapsed,
        "lam": lam, "rho_target": rho_target,
        "n_resolves": res.n_resolves,
        "final_budgets": [int(v) for v in res.final_budgets],
        "measured": m,
        "predicted_system_time": pred_sys,
        "predicted_accuracy": pred_acc,
        "rel_gap_system_time": comp["max_rel_gap_system_time"],
        "gap_accuracy": comp["max_gap_accuracy"],
        "ci_covered": bool(comp["covered"][0]),
        "estimator": {"lam_hat": est["lam"], "es_hat": est["es"],
                      "t0_hat": est["t0"], "c_hat": est["c"]},
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small lanes + relaxed floors (CI)")
    ap.add_argument("--json-out", default="BENCH_replay.json")
    args = ap.parse_args(argv)

    if args.smoke:
        n_virtual, n_seeds, n_crn, n_seg, n_dec = 20_000, 8, 20_000, 4000, 96
    else:
        n_virtual, n_seeds, n_crn, n_seg, n_dec = 200_000, 32, 60_000, \
            20_000, 600

    prob = paper_problem()
    out = {
        "mode": "smoke" if args.smoke else "full",
        "virtual": virtual_lane(prob, n_virtual),
        "crn": crn_lane(prob, (0.6, 0.9), n_seeds, n_crn),
        "drift": drift_lane(prob, n_seg),
        "engine": engine_lane(prob, n_dec),
    }
    out["estimation"] = out["virtual"]["estimation"]

    lam_floor = 0.6 if args.smoke else 0.8
    assert out["estimation"]["lam_accuracy"] >= lam_floor, \
        f"lambda estimation accuracy {out['estimation']['lam_accuracy']:.3f}"
    gap_cap = 32 if args.smoke else 16
    assert out["virtual"]["budget_linf_gap"] <= gap_cap, \
        f"converged budgets {out['virtual']['budget_linf_gap']} tokens off"
    assert out["drift"]["budgets_moved"], "budgets never reacted to drift"
    assert out["drift"]["segments"][-1]["lam_rel_err_end"] < 0.35, \
        "post-drift lambda tracking too slow"

    with open(args.json_out, "w") as f:
        json.dump(out, f, indent=1)
    emit("replay.artifact", args.json_out, out["mode"])


if __name__ == "__main__":
    compile_cache.enable()
    main()
