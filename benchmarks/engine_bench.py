"""Decode-throughput benchmark: chunked device-resident decode vs the
per-token reference loop, per architecture family.

The serving tentpole claim measured here: fusing generation into a chunked
``lax.scan`` (budget/EOS/alive masks carried as device state, KV cache
donated and updated in place via the static-layer decode path) beats the
per-token loop — one jitted dispatch + host sync + eager sample per token,
re-materializing capacity-sized cache leaves each step — by at least
``--min-speedup`` in tokens/s on the reduced-config CPU grid. Greedy
token-for-token equality between the two paths is asserted for EVERY
architecture measured (the continuous-batching exactness contract), so the
speedup is never bought with drift.

Timing uses ``common.timed`` with an untimed warmup call, so compile time
is excluded from every figure. Wall numbers are CPU debug figures; the TPU
roofline lives in benchmarks/roofline.py.

    PYTHONPATH=src python -m benchmarks.engine_bench [--smoke]

Either mode writes ``BENCH_engine.json`` (``--json-out`` to relocate) with
per-arch tokens/s, speedups, and the grid config. ``--smoke`` shrinks the
grid and relaxes the floor for noisy CI runners (the committed JSON comes
from a full run on a quiet machine, floor 5x). ``--kernel-check`` also
cross-checks the Pallas decode-attention slot path (interpret mode on CPU)
against the reference for token equality.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import compile_cache
from repro.configs import get_config
from repro.models import init_params, reduced
from repro.serving import DecodeEngine

from .common import emit, timed

ARCHS_FULL = ("qwen3-0.6b", "deepseek-moe-16b", "rwkv6-1.6b", "zamba2-7b")
ARCHS_SMOKE = ("qwen3-0.6b", "rwkv6-1.6b")

# grid where per-token dispatch+sync overhead and per-token cache
# re-materialization are both visible: tiny model, modest cache (with
# headroom for prompt + budget), 2 rows, chunk == budget so the fast path
# is a single dispatch per generate
GRID = dict(d_model=128, batch=2, budget=64, capacity=128, chunk=64,
            prompt_len=8)


def bench_arch(arch: str, repeat: int, grid: dict) -> dict:
    cfg = reduced(get_config(arch), d_model=grid["d_model"])
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, cache_capacity=grid["capacity"],
                       chunk=grid["chunk"])
    B, bud = grid["batch"], grid["budget"]
    prompts = (np.arange(B * grid["prompt_len"])
               .reshape(B, grid["prompt_len"]) % 97 + 1).astype(np.int32)
    budgets = [bud] * B

    def run(use_scan):
        return eng.generate(prompts, budgets, max_extra_tokens=0,
                            use_scan=use_scan)

    out_loop, us_loop = timed(run, False, repeat=repeat, best=True)
    out_scan, us_scan = timed(run, True, repeat=repeat, best=True)
    # exactness contract: the fast path must match the reference stream
    np.testing.assert_array_equal(out_loop["tokens"], out_scan["tokens"])
    np.testing.assert_array_equal(out_loop["n_generated"],
                                  out_scan["n_generated"])
    toks = B * bud
    return {
        "per_token_tok_s": toks / us_loop * 1e6,
        "chunked_tok_s": toks / us_scan * 1e6,
        "speedup": us_loop / us_scan,
        "greedy_equal": True,
        "decode_us_per_token_loop": us_loop / toks,
        "decode_us_per_token_scan": us_scan / toks,
        # full repeat-sample distributions (min/median/p95): variance
        # regressions are gateable, not just mean shifts
        "timing_loop": us_loop.stats,
        "timing_scan": us_scan.stats,
    }


def kernel_check(arch: str = "qwen3-0.6b") -> dict:
    """Greedy equality of the Pallas decode-attention slot path (interpret
    mode on CPU) vs the jnp reference, through the full engine."""
    cfg = reduced(get_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    ref = DecodeEngine(cfg, params, cache_capacity=64, chunk=4)
    ker = DecodeEngine(cfg, params, cache_capacity=64, chunk=4,
                       use_decode_kernel=True)
    prompts = np.ones((2, 8), dtype=np.int32)
    o1 = ref.generate(prompts, [4, 6], max_extra_tokens=1)
    o2 = ker.generate(prompts, [4, 6], max_extra_tokens=1)
    np.testing.assert_array_equal(o1["tokens"], o2["tokens"])
    return {"arch": arch, "tokens_equal": True}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + relaxed floor + wall budget (CI)")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="required chunked-vs-per-token tokens/s speedup "
                         "(default: 5 full / 2 smoke)")
    ap.add_argument("--budget-s", type=float, default=None,
                    help="smoke-mode wall-clock budget")
    ap.add_argument("--repeat", type=int, default=5,
                    help="timed calls per path (fastest is reported)")
    ap.add_argument("--json-out", default="BENCH_engine.json")
    ap.add_argument("--kernel-check", action="store_true",
                    help="also cross-check the Pallas decode kernel path")
    args = ap.parse_args(argv)
    min_speedup = args.min_speedup
    if min_speedup is None:
        min_speedup = 2.0 if args.smoke else 5.0
    archs = ARCHS_SMOKE if args.smoke else ARCHS_FULL

    t_start = time.perf_counter()
    results = {}
    for arch in archs:
        r = bench_arch(arch, repeat=args.repeat, grid=GRID)
        results[arch] = r
        emit(f"engine.{arch}.chunked_tok_s", f"{r['chunked_tok_s']:.0f}",
             f"per_token={r['per_token_tok_s']:.0f}, "
             f"speedup={r['speedup']:.2f}x, greedy_equal")
        emit(f"engine.{arch}.decode_us_per_token",
             f"{r['decode_us_per_token_scan']:.0f}",
             f"loop={r['decode_us_per_token_loop']:.0f} "
             f"(reduced d={GRID['d_model']}, CPU, batch={GRID['batch']})")
    wall_s = time.perf_counter() - t_start

    kernel = None
    if args.kernel_check or not args.smoke:
        kernel = kernel_check()
        emit("engine.decode_kernel.tokens_equal", "1",
             "pallas slot path vs jnp reference, interpret mode")

    worst = min(r["speedup"] for r in results.values())
    payload = {
        "grid": GRID,
        "mode": "smoke" if args.smoke else "full",
        "min_speedup": min_speedup,
        "worst_speedup": worst,
        "wall_s": wall_s,
        "archs": results,
        "kernel_check": kernel,
    }
    with open(args.json_out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    emit("engine.worst_speedup", f"{worst:.2f}", f"floor={min_speedup}")

    assert worst >= min_speedup, (
        f"chunked decode speedup {worst:.2f}x below floor {min_speedup}x")
    if args.smoke and args.budget_s is not None:
        assert wall_s <= args.budget_s, (
            f"smoke bench took {wall_s:.1f}s > budget {args.budget_s}s")


if __name__ == "__main__":
    compile_cache.enable()
    main()
