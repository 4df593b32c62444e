"""Close the loop: dry-run roofline -> (t0, c) recalibration -> re-solve.

The paper calibrated t_k(l) = t0_k + c_k l on an A100. Our TPU substrate
changes the service constants; the §Perf serving fix changes them again.
This benchmark rebuilds the allocation problem with service constants
scaled by the measured decode step time (qwen3-8b, the paper's model) for
(a) the paper-faithful baseline engine and (b) the optimized engine
(kv_repeat=2), and shows what the queueing-aware allocator does with the
recovered slack: budgets and utility both rise.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

from repro import compile_cache
from repro.core import ServerParams, Problem, TaskSet, paper_problem, solve

from .common import emit


def _dominant(path):
    r = json.load(open(path))["roofline"]
    return max(r["compute_s"], r["memory_s"], r["collective_s"])


def main() -> None:
    res = pathlib.Path("results")
    base_p = res / "roofline" / "qwen3-8b__decode_32k__pod__roofline.json"
    opt_p = res / "perf" / "qwen3-8b__decode_32k__pod__roofline__kvrep2.json"
    if not (base_p.exists() and opt_p.exists()):
        emit("bridge.note", "missing-artifacts", "run the dry-run sweeps")
        return
    # decode_32k serves 128 concurrent streams one token per step
    c_base = _dominant(base_p)          # s per token per stream batch
    c_opt = _dominant(opt_p)
    emit("bridge.decode_step_s.baseline", f"{c_base:.4f}", "per 128-stream step")
    emit("bridge.decode_step_s.optimized", f"{c_opt:.4f}",
         f"gain={c_base / c_opt:.1f}x")

    paper = paper_problem()
    mean_paper_c = float(np.mean(np.asarray(paper.tasks.c)))
    for label, step_s in (("baseline", c_base), ("optimized", c_opt)):
        scale = step_s / mean_paper_c
        tasks = TaskSet(names=paper.tasks.names, A=paper.tasks.A,
                        b=paper.tasks.b, D=paper.tasks.D,
                        t0=np.asarray(paper.tasks.t0) * scale,
                        c=np.asarray(paper.tasks.c) * scale,
                        pi=paper.tasks.pi)
        # keep the same utilization-pressure as the paper: scale lambda
        # inversely so lam * E[S(0)] matches the paper's operating point
        prob = Problem(tasks=tasks,
                       server=ServerParams(paper.server.lam / scale,
                                           paper.server.alpha,
                                           paper.server.l_max))
        sol = solve(prob)
        emit(f"bridge.{label}.budgets",
             "|".join(str(int(v)) for v in sol.lengths_int),
             f"J={sol.value_cont:.4f}")
    # and at FIXED arrival rate, the faster engine buys budget headroom:
    scale_b = c_base / mean_paper_c
    scale_o = c_opt / mean_paper_c
    lam_fixed = paper.server.lam / scale_b      # stable under the baseline
    js = {}
    for label, scale in (("baseline", scale_b), ("optimized", scale_o)):
        tasks = TaskSet(names=paper.tasks.names, A=paper.tasks.A,
                        b=paper.tasks.b, D=paper.tasks.D,
                        t0=np.asarray(paper.tasks.t0) * scale,
                        c=np.asarray(paper.tasks.c) * scale,
                        pi=paper.tasks.pi)
        prob = Problem(tasks=tasks, server=ServerParams(
            lam_fixed, paper.server.alpha, paper.server.l_max))
        sol = solve(prob)
        js[label] = sol.value_cont
        emit(f"bridge.fixed_lam.{label}.budgets",
             "|".join(str(int(v)) for v in sol.lengths_int),
             f"J={sol.value_cont:.4f}")
    emit("bridge.fixed_lam.utility_gain",
         f"{js['optimized'] - js['baseline']:.4f}",
         "J units bought by the §Perf serving fix at equal load")
    emit("bridge.note", "single-stream-M/G/1",
         "a TPU pod serves 128 concurrent streams; dividing c by the "
         "batch concurrency or using the M/G/c extension recovers "
         "paper-scale budgets (see serve.mgc.* in serving_bench)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
