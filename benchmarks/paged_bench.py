"""Paged-KV serving benchmark: admission density, step-latency fit, and
the occupancy-corrected analytics loop.

The paged tentpole claim measured here: at EQUAL total KV memory, paged
admission (token-granular block reservations over a shared pool) sustains
strictly higher concurrent tokens-in-use than the dense slot path, whose
admission is gated by worst-case per-slot capacity. The workload is many
short requests — each needs ~a third of a dense slot — so the slot engine
strands the rest of every slot's capacity while the paged engine turns it
into admitted concurrency. Greedy token-for-token equality between the
two engines is asserted on the same workload, so the density is never
bought with drift.

Also measured, closing the engine -> analytics loop:

* decode step latency at pinned occupancies b in {1, 2, 4, 8}, fed to
  ``core.batch_service.fit_step_latency`` — the measurement the
  occupancy-corrected queueing model calibrates from,
* the corrected analytics (``batch_service_wait``) vs the
  occupancy-dependent DES (``queueing_sim.simulate_batch_service``) under
  the FITTED step model at moderate load: mean system time must agree
  within the documented envelope,
* KV bytes per pool token for f32 vs int8 pools (machine-independent).

    PYTHONPATH=src python -m benchmarks.paged_bench [--smoke]

Either mode writes ``BENCH_paged.json`` (``--json-out`` to relocate);
``--smoke`` shrinks the workload for CI runners. The committed JSON comes
from a full run on a quiet machine; ``benchmarks/report.py --check``
gates the occupancy ratio (floor_rel) and the analytics error (ceil_abs).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import compile_cache
from repro.configs import get_config
from repro.core.batch_service import batch_service_wait, fit_step_latency
from repro.core.params import paper_tasks
from repro.models import init_params, reduced
from repro.models.attention import init_paged_cache
from repro.queueing_sim.batch_service import simulate_batch_service
from repro.serving.continuous import ContinuousBatchingEngine

from .common import emit, timed

# equal-memory comparison point: both engines own 512 pool tokens; the
# slot engine can hold 8 concurrent requests (one per dense 64-token
# slot), the paged engine up to 16 rows drawing 24-token reservations
# from the same 512-token pool
GRID = dict(capacity=64, slot_slots=8, paged_slots=16, block_size=8,
            n_blocks=64, chunk=4, prompt_len=8, budget=8, max_extra=2)


def _requests(n: int, grid: dict) -> list:
    rng = np.random.default_rng(0)
    return [(i,
             rng.integers(1, 97, size=grid["prompt_len"]).astype(np.int32),
             grid["budget"], grid["max_extra"]) for i in range(n)]


def _drain_measured(eng, reqs):
    """Serve the whole workload, sampling tokens-in-use each fused step."""
    pending = list(reqs)
    done = {}
    samples = []
    t0 = time.perf_counter()
    while pending or eng.n_active:
        if pending:
            ok = eng.admit_many(pending)
            pending = [r for r, f in zip(pending, ok) if not f]
        for s in eng.step_chunk():
            done[s.rid] = s.tokens
        samples.append(eng.tokens_in_use)
    wall = time.perf_counter() - t0
    toks = sum(len(t) for t in done.values())
    return done, {
        "mean_tokens_in_use": float(np.mean(samples)),
        "peak_tokens_in_use": int(np.max(samples)),
        "pool_tokens": int(eng.pool_tokens),
        "requests": len(done),
        "wall_s": wall,
        "req_per_s": len(done) / wall,
        "tok_per_s": toks / wall,
    }


def bench_occupancy(cfg, params, n_requests: int, grid: dict) -> dict:
    reqs = _requests(n_requests, grid)
    slot = ContinuousBatchingEngine(
        cfg, params, max_slots=grid["slot_slots"], capacity=grid["capacity"],
        chunk=grid["chunk"])
    paged = ContinuousBatchingEngine(
        cfg, params, max_slots=grid["paged_slots"],
        capacity=grid["capacity"], chunk=grid["chunk"], paged=True,
        block_size=grid["block_size"], n_blocks=grid["n_blocks"])
    assert slot.pool_tokens == paged.pool_tokens, "not an equal-memory run"
    done_s, stats_s = _drain_measured(slot, reqs)
    done_p, stats_p = _drain_measured(paged, reqs)
    assert done_p == done_s, "paged tokens drifted from the slot path"
    ratio = stats_p["mean_tokens_in_use"] / stats_s["mean_tokens_in_use"]
    # THE tentpole assertion: equal memory, strictly denser admission
    assert ratio > 1.0, (
        f"paged mean tokens-in-use {stats_p['mean_tokens_in_use']:.1f} not "
        f"above slot path {stats_s['mean_tokens_in_use']:.1f}")
    return {"slot": stats_s, "paged": stats_p,
            "paged_vs_slot_mean_ratio": ratio,
            "tokens_equal": True}


def bench_step_latency(cfg, params, grid: dict, repeat: int) -> dict:
    """Measure one fused decode step at pinned occupancies and fit the
    affine step model the batch-service analytics consume."""
    batch_sizes = [1, 2, 4, 8]
    step_us = []
    for b in batch_sizes:
        eng = ContinuousBatchingEngine(
            cfg, params, max_slots=8, capacity=grid["capacity"],
            chunk=grid["chunk"], paged=True,
            block_size=grid["block_size"])
        # long budgets so nobody retires while we time
        eng.admit_many([(i, np.full(4, 5 + i, np.int32), 40, 0)
                        for i in range(b)])
        _, us = timed(lambda: eng.step_chunk(), repeat=repeat, best=True)
        step_us.append(float(us))
        emit(f"paged.step_us.b{b}", f"{float(us):.0f}",
             f"fused {grid['chunk']}-token chunk at occupancy {b}")
    # per-chunk -> per-step seconds
    secs = [u / grid["chunk"] * 1e-6 for u in step_us]
    model = fit_step_latency(batch_sizes, secs)
    return {"batch_sizes": batch_sizes, "step_chunk_us": step_us,
            "d0": model.d0, "d1": model.d1,
            "ratio_at_8": float(model.ratio(8))}, model


def bench_analytics(model, n_sim: int, max_err: float) -> dict:
    """Corrected analytics vs occupancy-dependent DES under the fitted
    step model, at moderate load (rho/c ~ 0.5-0.7)."""
    tasks = paper_tasks()
    lengths = np.full(tasks.n_tasks, 120.0)
    lam, max_batch = 1.5, 8
    pred = batch_service_wait(tasks, lengths, lam, model, max_batch)
    sim = simulate_batch_service(tasks, lengths, lam, model, max_batch,
                                 n=n_sim, seed=0)
    rel_err = abs(pred.mean_system_time - sim.mean_system_time) \
        / sim.mean_system_time
    assert rel_err <= max_err, (
        f"corrected analytics off DES by {rel_err:.2%} > {max_err:.0%}")
    return {"lam": lam, "max_batch": max_batch,
            "b_bar": pred.b_bar, "ratio": pred.ratio,
            "pred_system_s": pred.mean_system_time,
            "des_system_s": sim.mean_system_time,
            "des_exp_occupancy": sim.exp_occupancy,
            "rel_err": float(rel_err), "max_err": max_err}


def bench_bytes_per_token(cfg, grid: dict) -> dict:
    """KV pool bytes per token, f32 vs int8 (layer-stacked, incl. scales)."""
    import dataclasses as dc

    def bpt(c):
        pc = init_paged_cache(c, batch=2, n_blocks=grid["n_blocks"],
                              block_size=grid["block_size"], n_bt=8)
        total = sum(int(leaf.nbytes) for leaf in
                    (pc.k, pc.v, pc.k_scale, pc.v_scale)
                    if leaf is not None)
        return total / (grid["n_blocks"] * grid["block_size"])

    f32 = bpt(cfg)
    i8 = bpt(dc.replace(cfg, kv_cache_dtype="int8"))
    assert i8 < f32, "int8 pool must be smaller than f32 per token"
    return {"f32_bytes_per_token": f32, "int8_bytes_per_token": i8,
            "compression": f32 / i8}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload + relaxed envelope (CI)")
    ap.add_argument("--requests", type=int, default=None,
                    help="workload size (default: 64 full / 24 smoke)")
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--budget-s", type=float, default=None,
                    help="smoke-mode wall-clock budget")
    ap.add_argument("--json-out", default="BENCH_paged.json")
    args = ap.parse_args(argv)
    n_requests = args.requests or (24 if args.smoke else 64)
    n_sim = 1500 if args.smoke else 6000
    max_err = 0.35 if args.smoke else 0.30

    cfg = reduced(get_config("qwen3-0.6b"))
    params = init_params(cfg, jax.random.PRNGKey(0))

    t_start = time.perf_counter()
    occ = bench_occupancy(cfg, params, n_requests, GRID)
    emit("paged.mean_tokens_in_use",
         f"{occ['paged']['mean_tokens_in_use']:.1f}",
         f"slot={occ['slot']['mean_tokens_in_use']:.1f}, "
         f"ratio={occ['paged_vs_slot_mean_ratio']:.2f}x at equal "
         f"{occ['slot']['pool_tokens']}-token memory")
    emit("paged.tok_per_s", f"{occ['paged']['tok_per_s']:.0f}",
         f"slot={occ['slot']['tok_per_s']:.0f} (CPU debug figures)")

    step, model = bench_step_latency(cfg, params, GRID, repeat=args.repeat)
    emit("paged.step_fit", f"d0={step['d0']:.2e},d1={step['d1']:.2e}",
         f"r(8)={step['ratio_at_8']:.2f}")

    analytics = bench_analytics(model, n_sim, max_err)
    emit("paged.analytics_rel_err", f"{analytics['rel_err']:.3f}",
         f"corrected system time vs DES, envelope {max_err:.0%}")

    bpt = bench_bytes_per_token(cfg, GRID)
    emit("paged.int8_bytes_per_token", f"{bpt['int8_bytes_per_token']:.1f}",
         f"f32={bpt['f32_bytes_per_token']:.1f}, "
         f"{bpt['compression']:.2f}x")

    wall_s = time.perf_counter() - t_start
    payload = {
        "grid": GRID,
        "mode": "smoke" if args.smoke else "full",
        "n_requests": n_requests,
        "wall_s": wall_s,
        "occupancy": occ,
        "step_latency": step,
        "analytics": analytics,
        "bytes_per_token": bpt,
    }
    with open(args.json_out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    emit("paged.wall_s", f"{wall_s:.1f}", "")
    if args.smoke and args.budget_s is not None:
        assert wall_s <= args.budget_s, (
            f"smoke bench took {wall_s:.1f}s > budget {args.budget_s}s")


if __name__ == "__main__":
    compile_cache.enable()
    main()
