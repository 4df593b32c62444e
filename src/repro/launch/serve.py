"""Serving driver: the paper's system end-to-end.

Streams Poisson arrivals through the allocator-driven FIFO server. Without
--real-engine the calibrated latency model drives the virtual clock (the
paper's simulation, at production scale). With it, the served path runs
on the wall clock: ``LLMServer`` over the paged continuous-batching
engine, which generates budget-enforced tokens from the model at its
published widths in bf16, with random weights from --seed. --reduced
swaps in the 2-layer float32 variant that the CPU tests use.

    PYTHONPATH=src python -m repro.launch.serve --queries 2000
    PYTHONPATH=src python -m repro.launch.serve --real-engine --queries 16
    PYTHONPATH=src python -m repro.launch.serve --real-engine --reduced \
        --queries 8
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json

import jax

from repro import compile_cache
from repro.configs import get_config
from repro.core import paper_problem
from repro.core.params import Problem
from repro.models import init_params, reduced
from repro.queueing_sim import generate_stream, pk_prediction
from repro.serving import ContinuousBatchingEngine, LLMServer, ServerConfig

# Decode rows of the engine, and KV tokens each may hold. Table I's
# budgets are at most ~345 tokens and prompts at most 128, so a request
# needs at most ~480 (prompt + budget + extras); at Qwen3-0.6B's widths
# the pool of 8 x 1024 tokens is 0.94 GB of bf16 KV.
SLOTS = 8
CAPACITY = 1024


def build_server(problem: Problem, server_cfg: ServerConfig, *,
                 arch: str = "qwen3-0.6b", seed: int = 0,
                 reduced_model: bool = False) -> LLMServer:
    """The served path: ``LLMServer`` over a paged
    ``ContinuousBatchingEngine`` holding ``arch`` (published widths, or
    ``reduced`` ones) with random weights from ``seed``. On the TPU the
    decode attends through the Pallas paged kernel; elsewhere through the
    gather reference (the interpreted kernel is far too slow to serve)."""
    cfg = get_config(arch)
    if reduced_model:
        cfg = reduced(cfg)
    params = jax.jit(functools.partial(init_params, cfg))(
        jax.random.PRNGKey(seed))
    engine = ContinuousBatchingEngine(
        cfg, params, max_slots=SLOTS, capacity=CAPACITY, paged=True,
        use_decode_kernel=jax.default_backend() == "tpu")
    return LLMServer(problem,
                     dataclasses.replace(server_cfg, generate_tokens=True),
                     engine=engine)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=2000)
    ap.add_argument("--lam", type=float, default=0.1)
    ap.add_argument("--alpha", type=float, default=30.0)
    ap.add_argument("--discipline", default="fifo",
                    choices=("fifo", "sjf", "priority"))
    ap.add_argument("--batch-size", type=int, default=None,
                    help="requests served together (default: 1 on the "
                         "virtual clock, the engine's slots with "
                         "--real-engine)")
    ap.add_argument("--online", action="store_true")
    ap.add_argument("--real-engine", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="with --real-engine: the small float32 variant")
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    prob = paper_problem(lam=args.lam, alpha=args.alpha)
    stream = generate_stream(prob.tasks, args.lam, args.queries,
                             seed=args.seed)
    batch = args.batch_size or (SLOTS if args.real_engine else 1)
    scfg = ServerConfig(discipline=args.discipline, batch_size=batch,
                        online_adaptation=args.online)
    if args.real_engine:
        srv = build_server(prob, dataclasses.replace(scfg, mode="wall"),
                           arch=args.arch, seed=args.seed,
                           reduced_model=args.reduced)
    else:
        srv = LLMServer(prob, scfg)
    sol = srv.allocator.solution
    print("allocation:", dict(zip(prob.tasks.names,
                                  sol.lengths_int.astype(int))))
    print("J(l*) =", round(sol.value_cont, 4),
          "| J_int =", round(sol.value_int, 4),
          "| J_bar =", round(sol.value_lower_bound, 4))
    rep = srv.run(stream)
    pred = pk_prediction(prob, list(sol.lengths_int))
    out = {
        "n": rep.n,
        "mean_wait": rep.mean_wait,
        "mean_system_time": rep.mean_system_time,
        "pk_predicted_system_time": pred["mean_system_time"],
        "p99_system_time": rep.p99_system_time,
        "utilization": rep.utilization,
        "accuracy_realized": rep.accuracy,
        "accuracy_model": rep.mean_accuracy_prob,
        "objective": rep.objective,
        "per_task_budget": rep.per_task_budget,
        "tokens_generated": rep.tokens_generated,
        "allocator_resolves": rep.n_resolves,
    }
    if args.real_engine:
        eng = srv.engine
        out["engine"] = {"arch": eng.cfg.arch_id, "paged": eng.paged,
                         "d_model": eng.cfg.d_model,
                         "n_layers": eng.cfg.n_layers,
                         "dtype": eng.cfg.dtype, "slots": eng.max_slots,
                         "capacity": eng.capacity,
                         "decode_kernel": eng.cfg.use_decode_kernel,
                         "device": jax.devices()[0].platform}
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
