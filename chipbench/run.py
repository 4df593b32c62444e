"""Run one cell of ``BENCHMARK.json`` once, on the chip this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for. JAX's compilation
cache is ``.jax_cache/`` in the checkout, so only a cell's first run there
compiles (in its set-up). The last line of
standard output is the result: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checked``: each number compared with its limit. The same numbers
are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the cache the program is given, before JAX reads its settings
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench import harness

    spec = harness.load_spec(args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < spec.chips:
        sys.exit(f"chipbench: {args.workload} needs {spec.chips} TPU "
                 f"chip(s); found {len(devs)} x {devs[0].platform} "
                 f"({devs[0].device_kind})")
    out = harness.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                           T_START)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": device}
    if args.trace:
        tr = out["trace"]
        if tr:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            line["breakdown"] = {"device_ops": tr["device_ops"],
                                 "idle_gaps": tr["idle_gaps"]}
    line["checked"] = out["checked"]
    for name, v in out["checked"].items():
        print(f"{name} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
