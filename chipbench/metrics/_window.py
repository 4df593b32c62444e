"""Helpers the readers share: what falls inside the measured window."""
from __future__ import annotations


def due_in_window(run) -> list:
    lp = run.loop
    return [r for r in lp.records.values()
            if lp.t_open <= r.due < lp.t_close]


def chunks_in_window(run) -> list:
    lp = run.loop
    return [c for c in lp.chunks if lp.t_open <= c.t0 < lp.t_close]


def percentile(values, q: float):
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None
