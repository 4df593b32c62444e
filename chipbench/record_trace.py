"""Record one traced run of a cell and keep a trimmed plain trace.

    python3 chipbench/record_trace.py --workload <cell> --seed <n> \
        --seconds <s> --out <file.json.gz> [--steps 3] [--dump]

Runs the cell as ``run.py --trace 1`` does and writes the first
``--steps`` decode chunks of the traced window, with their host spans and
device operations, in ``trace_reduce``'s plain form: the test data of
``tests/test_chipbench_trace.py``. ``--dump`` prints, on standard error,
the device operations by name with their summed time.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def trim(plain: dict, steps: int) -> dict:
    host = plain["host"]
    starts = [s for name, s, _ in host if name == "chipbench.step"]
    lo = starts[0]
    hi = starts[steps] if len(starts) > steps else max(s + d for _, s, d in host)
    return {"host": [h for h in host if lo <= h[1] < hi],
            "devices": {p: [op for op in ops if lo <= op[1] < hi]
                        for p, ops in plain["devices"].items()}}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--dump", action="store_true")
    args = ap.parse_args()

    from chipbench import harness

    kept = {}

    def keep(plain):
        kept["trim"] = trim(plain, args.steps)
        if args.dump:
            ops = {}
            for p, evs in plain["devices"].items():
                for name, _, d in evs:
                    ops[(p, name)] = ops.get((p, name), 0.0) + d
            for (p, name), d in sorted(ops.items(),
                                       key=lambda kv: -kv[1])[:60]:
                print(json.dumps({"plane": p, "op": name, "ms": d / 1e6}),
                      file=sys.stderr)
            print(json.dumps({"host_spans": len(plain["host"]),
                              "first_host": plain["host"][:3]}),
                  file=sys.stderr)

    spec = harness.load_spec(args.workload)
    out = harness.run_cell(spec, args.seed, args.seconds, True, T_START,
                           keep_trace=keep)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "trace": kept["trim"]}, f)
    print(json.dumps({"metrics": out["metrics"], "trace": out["trace"],
                      "checked": out["checked"]}))


if __name__ == "__main__":
    main()
