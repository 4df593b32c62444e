"""The reduction from a profiler trace to busy time, kernel time and
idle gaps: on a hand-made trace, and on a trace recorded on the chip and
trimmed to a few decode chunks (``chipbench/data``)."""
import gzip
import json

import pytest

from chipbench_tiny import ROOT

from chipbench import trace_reduce

RECORDED = ROOT / "chipbench" / "data" / "trace_qwen3-0.6b.table1.json.gz"


def test_hand_made_trace():
    trace = {
        "host": [["chipbench.admit", 0.0, 100.0],
                 ["chipbench.step", 100.0, 1000.0],
                 ["chipbench.host", 1100.0, 50.0],
                 ["chipbench.idle", 1150.0, 850.0]],
        "devices": {"/device:TPU:0": [
            ["fusion", 10.0, 80.0],
            ["while", 150.0, 900.0],       # spans the two ops of its body
            ["attn", 200.0, 300.0],
            ["fusion", 400.0, 600.0],
            ["copy", 1500.0, 100.0],
            ["fusion", 2500.0, 100.0]]},   # outside the window
    }
    r = trace_reduce.reduce(trace, kernel="attn")
    assert r["window_s"] == pytest.approx(2000e-9)
    # union: [10, 90] + [150, 1050] + [1500, 1600]
    assert r["busy_s"] == pytest.approx(1080e-9)
    assert r["kernel_s"] == pytest.approx(300e-9)
    assert r["device_ops"] == [["fusion", pytest.approx(680e-9)],
                               ["attn", pytest.approx(300e-9)],
                               ["copy", pytest.approx(100e-9)]]
    assert r["idle_gaps"] == [["chipbench.idle", pytest.approx(450e-9)],
                              ["chipbench.idle", pytest.approx(400e-9)],
                              ["chipbench.step", pytest.approx(60e-9)],
                              ["chipbench.admit", pytest.approx(10e-9)]]


def test_op_names_drop_the_instruction():
    assert trace_reduce.op_name(
        "%paged_decode_attention.203 = bf16[128,8,2,128] custom-call(...)"
    ) == "paged_decode_attention"
    assert trace_reduce.op_name("%fusion.3249.remat2 = bf16[8]") == "fusion"
    assert trace_reduce.op_name("%copy-done.125 = s32[128]") == "copy-done"


def test_no_spans_reads_nothing():
    assert trace_reduce.reduce({"host": [], "devices": {}}, "paged") == {}


def _brute_busy(ops, lo, hi, step=20000.0):
    """Busy time by sampling the window every ``step`` ns."""
    starts = sorted((s, s + d) for _, s, d in ops)
    n, t, i, live = 0, lo, 0, []
    while t < hi:
        while i < len(starts) and starts[i][0] <= t:
            live.append(starts[i][1])
            i += 1
        live = [e for e in live if e > t]
        n += bool(live)
        t += step
    return n * step


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    rec = json.loads(gzip.open(RECORDED, "rt").read())
    trace = rec["trace"]
    r = trace_reduce.reduce(trace, kernel="paged_decode_attention")
    lo, hi = trace_reduce.window(trace)
    ops = next(iter(trace["devices"].values()))
    assert 0 < r["busy_s"] <= r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert r["busy_s"] == pytest.approx(
        _brute_busy(ops, lo, hi) / 1e9, rel=0.02)
    kernel = [op for op in ops if op[0] == "paged_decode_attention"]
    n_layers = 28
    steps = sum(1 for h in trace["host"] if h[0] == "chipbench.step")
    # one paged kernel call per layer per decode step of each chunk
    assert len(kernel) == n_layers * 8 * steps
    assert 0 < r["kernel_s"] < r["busy_s"]
    assert all(name.startswith("chipbench.") for name, _ in r["idle_gaps"])
