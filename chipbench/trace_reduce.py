"""From a profiler trace to device busy time, kernel time and idle gaps.

:func:`load` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small plain form: the device's operations (``XLA Ops`` lines of each TPU
plane) and the benchmark's host spans (its ``TraceAnnotation`` names,
which start with ``chipbench.``). :func:`reduce` works on that form only,
so a test can run it on a trimmed trace recorded on the chip.

On a TPU v5e an op event's name is its whole HLO instruction
(``%paged_decode_attention.203 = bf16[...] custom-call(...)``); the
plain form keeps the instruction's name without its number
(``paged_decode_attention``), which is the jitted function's name for a
Pallas kernel. A ``while`` op spans the ops of its body, which the trace
lists too: it counts towards busy time, not among the ops.

The traced window runs from the first host span's start to the last
one's end: the loop annotates all it does (admit, step, host, idle).
"""
from __future__ import annotations

import glob
import os
import re

HOST_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%fusion.3414 = bf16[...] ...`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+(\.remat\d*)?$", "", head)


def load(trace_dir: str) -> dict:
    """The plain form of the newest ``.xplane.pb`` under ``trace_dir``:
    ``{"devices": {plane: [[op, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append([op_name(ev.name), float(ev.start_ns),
                                float(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def window(trace: dict):
    """(start_ns, end_ns) of the traced window, or None without spans."""
    host = trace["host"]
    if not host:
        return None
    return host[0][1], max(s + d for _, s, d in host)


def reduce(trace: dict, kernel: str, top: int = 10) -> dict:
    """Busy and window seconds (busy averaged over the chips), the summed
    device time of the ops named ``kernel``, the ops that took most time
    (summed by name), and the longest idle gaps, each named by the host
    span it fell in."""
    win = window(trace)
    if win is None or not trace["devices"]:
        return {}
    lo, hi = win
    busy, kernel_ns, per_op, gaps = [], 0.0, {}, []
    for ops in trace["devices"].values():
        ops = [op for op in ops if op[1] < hi and op[1] + op[2] > lo]
        merged = _clip(_union([[s, s + d] for _, s, d in ops]), lo, hi)
        busy.append(sum(b - a for a, b in merged))
        for name, _, d in ops:
            if name not in CONTAINERS:
                per_op[name] = per_op.get(name, 0.0) + d
            if name == kernel:
                kernel_ns += d
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(trace["devices"])
    spans = trace["host"]

    def label(a, b):
        best, most = "none", 0.0
        for name, s, d in spans:
            o = min(b, s + d) - max(a, s)
            if o > most:
                best, most = name, o
        return best

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "kernel_s": kernel_ns / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
    }
