"""End-to-end serving benchmark: the real server (allocator + scheduler +
virtual clock) under the paper workload, plus beyond-paper modes
(SJF/priority disciplines, batched service, online adaptation, M/G/c).

The FIFO row is cross-checked against two independent predictions: the
Pollaczek-Khinchine formula and a seed-averaged batched Lindley DES
(``queueing_sim.sweep``) at the allocator's integer budgets."""
from __future__ import annotations

import numpy as np

from repro import compile_cache
from repro.core import paper_problem, solve_mgc
from repro.queueing_sim import generate_stream, pk_prediction, sweep
from repro.serving import LLMServer, ServerConfig

from .common import emit, timed


def main() -> None:
    prob = paper_problem()
    stream = generate_stream(prob.tasks, prob.server.lam, 5000, seed=3)

    def run(**kw):
        srv = LLMServer(prob, ServerConfig(online_adaptation=False, **kw))
        return srv.run(stream), srv

    (fifo, srv), us = timed(lambda: run(), repeat=1)
    budgets = np.asarray(srv.allocator.solution.lengths_int, dtype=float)
    pred = pk_prediction(prob, list(budgets))
    des = sweep(prob, {"opt": budgets}, lams=[prob.server.lam], n_seeds=8,
                n_queries=5000, seed=3, clip_unstable=False)
    emit("serve.fifo.mean_system_time", f"{fifo.mean_system_time:.4f}",
         f"pk={pred['mean_system_time']:.4f}, "
         f"des={des.mean_system_time[0, 0]:.4f}"
         f"+-{des.ci_system_time[0, 0]:.4f}")
    emit("serve.fifo.p99_system_time", f"{fifo.p99_system_time:.4f}", "")
    emit("serve.fifo.objective", f"{fifo.objective:.4f}", "")
    emit("serve.fifo.utilization", f"{fifo.utilization:.4f}", "")
    emit("serve.fifo.throughput_qps", f"{5000 / (us / 1e6):.0f}",
         "simulated queries per wall-second")

    sjf, _ = run(discipline="sjf")
    emit("serve.sjf.mean_wait", f"{sjf.mean_wait:.4f}",
         f"fifo={fifo.mean_wait:.4f}")
    pri, _ = run(discipline="priority")
    emit("serve.priority.objective", f"{pri.objective:.4f}", "")
    for bs in (2, 4, 8):
        rep, _ = run(batch_size=bs)
        emit(f"serve.batched_{bs}.mean_system_time",
             f"{rep.mean_system_time:.4f}", f"objective={rep.objective:.4f}")
    online_srv = LLMServer(prob, ServerConfig(online_adaptation=True))
    online = online_srv.run(stream)
    emit("serve.online.objective", f"{online.objective:.4f}",
         f"resolves={online.n_resolves}")

    # M/G/c replica planning (beyond paper)
    for c in (1, 2, 4):
        r = solve_mgc(prob, c)
        emit(f"serve.mgc.replicas_{c}.J", f"{float(r.value):.4f}",
             f"iters={r.iterations}")

    # wall mode on the REAL engine: service clock = wall time of the
    # continuous-batching fast path (batched admission + fused chunked
    # decode), reduced model so CPU decode stays tractable
    import jax

    from repro.configs import get_config
    from repro.core import Problem, ServerParams
    from repro.models import init_params, reduced
    from repro.serving.continuous import ContinuousBatchingEngine

    cfg = reduced(get_config("qwen3-0.6b"), d_model=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, capacity=128,
                                   chunk=16)
    small = Problem(tasks=prob.tasks, server=ServerParams(0.1, 2.0, 64.0))
    wall_stream = generate_stream(small.tasks, 0.1, 16, seed=5,
                                  prompt_len_range=(4, 8))
    def run_wall():
        srv = LLMServer(small, ServerConfig(mode="wall", batch_size=4,
                                            generate_tokens=True,
                                            max_extra_tokens=2,
                                            online_adaptation=False),
                        engine=eng)
        return srv.run(wall_stream)

    wall_rep, wall_us = timed(run_wall, repeat=1, warmup=1)
    emit("serve.wall.tokens_generated", f"{wall_rep.tokens_generated}",
         f"n={wall_rep.n}, continuous fast path (batched admission + "
         f"chunked decode)")
    emit("serve.wall.tokens_per_s",
         f"{wall_rep.tokens_generated / (wall_us / 1e6):.0f}",
         "real-engine wall-clock decode throughput, compile excluded")


if __name__ == "__main__":
    compile_cache.enable()
    main()
