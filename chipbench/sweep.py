"""Find a cell's knee: serve its traffic at several fixed rates, each
rate a run of the cell's own window (``harness.run_cell``), and apply the
knee rule.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1,4,8,12 --out <file.json>

The rule (:func:`knee`): limits are fixed from the unloaded (lowest)
rate's run, TTFT at twice its 95th percentile rounded up to 100 ms and
TPOT at 1.5 times its 95th percentile rounded up to 10 ms. The knee is
the highest rate at which 90% of the requests due in the window meet
both, with no growing backlog: no more requests still waiting for their
first token when the window closes than one second of arrivals. The
cell's ``rate_per_s`` is 0.8 x the knee, written into the cell file with
the knee and the limits.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

MET_SHARE = 0.9


def summary(rate: float, lp) -> dict:
    """One rate's row: each request due in the window, and how many of
    them still waited for their first token when it closed."""
    due = [r for r in lp.records.values()
           if lp.t_open <= r.due < lp.t_close]
    per = [{"ttft_ms": (r.t_first - r.due) * 1e3 if r.t_first else None,
            "tpot_ms": ((r.t_last - r.t_first) * 1e3 / (r.n_tokens - 1)
                        if r.finished and r.n_tokens > 1 else None),
            "system_s": r.t_last - r.due if r.finished else None,
            "n": r.n_tokens, "finished": r.finished} for r in due]
    ch = [x for x in lp.chunks if lp.t_open <= x.t0 < lp.t_close]
    return {"rate": rate, "requests": len(due),
            "finished": sum(r.finished for r in due),
            "admitted_after_close": sum(
                1 for r in due if r.t_first is None or r.t_first > lp.t_close),
            "chunk_ms_mean": (sum(x.t1 - x.t0 for x in ch) * 1e3 / len(ch)
                              if ch else 0.0),
            "per_request": per}


def limits(unloaded: dict) -> tuple:
    """(TTFT, TPOT) limits in ms from the unloaded rate's row."""
    def p95(key):
        return float(np.percentile([p[key] for p in unloaded["per_request"]
                                    if p[key] is not None], 95))
    return (math.ceil(2 * p95("ttft_ms") / 100) * 100,
            math.ceil(1.5 * p95("tpot_ms") / 10) * 10)


def met(row: dict, ttft_ms: float, tpot_ms: float) -> float:
    """Share of the row's requests that finished within both limits."""
    ok = sum(1 for p in row["per_request"]
             if p["finished"] and p["ttft_ms"] <= ttft_ms
             and (p["tpot_ms"] is None or p["tpot_ms"] <= tpot_ms))
    return ok / max(row["requests"], 1)


def knee(rows: list) -> dict:
    """The knee of a sweep's rows by the rule above."""
    rows = sorted(rows, key=lambda r: r["rate"])
    ttft_ms, tpot_ms = limits(rows[0])
    best = None
    for r in rows:
        if (met(r, ttft_ms, tpot_ms) >= MET_SHARE
                and r["admitted_after_close"] <= r["rate"]):
            best = r["rate"]
    return {"knee_per_s": best, "ttft_limit_ms": ttft_ms,
            "tpot_limit_ms": tpot_ms,
            "met": {r["rate"]: met(r, ttft_ms, tpot_ms) for r in rows}}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from chipbench import harness

    spec = harness.load_spec(args.workload)
    rows = []
    for rate in sorted(float(r) for r in args.rates.split(",")):
        out = harness.run_cell(spec, args.seed, args.seconds, False,
                               time.perf_counter(), rate=rate)
        rows.append(summary(rate, out["loop"]))
        print(json.dumps({k: v for k, v in rows[-1].items()
                          if k != "per_request"}), flush=True)
    found = knee(rows)
    print(json.dumps(found), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps({"workload": args.workload,
                                          "seed": args.seed,
                                          "seconds": args.seconds,
                                          "knee": found, "rates": rows}))


if __name__ == "__main__":
    main()
