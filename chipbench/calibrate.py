"""Readings for the correctness limits: the program's numbers on many
seeds, and the controls' on some, at the cell's own size and load.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s> --out <file.json>

Each seed is one run of the cell (``harness.run_cell``, as ``run.py``
makes it: weights and traffic from the seed, a new engine, the window,
the check), with the reference on the fp8 and int8 grids added on the
control seeds. For each number compared, the lower reading is the
program's largest and the upper the smallest of a control's.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

GRIDS = ("fp8", "int8")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from chipbench import harness

    spec = harness.load_spec(args.workload)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        res = harness.run_cell(spec, seed, args.seconds, False, t0,
                               controls=GRIDS if seed in controls else ())
        row = dict(seed=seed, correct=res["correct"],
                   attempted=res["attempted"], failed=res["failed"],
                   seconds=time.perf_counter() - t0, **res["gaps"])
        out.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "runs": out}
    for name in out[0]["program"]:
        summary[name] = {"lower": max(r["program"][name] for r in out)}
        for grid in GRIDS:
            ctl = [r[f"control_{grid}"][name] for r in out
                   if f"control_{grid}" in r]
            summary[name][f"upper_{grid}"] = min(ctl) if ctl else None
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(summary))
    print(json.dumps({k: v for k, v in summary.items() if k != "runs"}))


if __name__ == "__main__":
    main()
