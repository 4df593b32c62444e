"""Rows active at the start of each ``step_chunk`` in the window, mean
weighted by the steps each ran."""
from ._window import chunks_in_window


def read(run):
    ch = chunks_in_window(run)
    steps = sum(c.steps for c in ch)
    return sum(c.n_active * c.steps for c in ch) / steps if steps else None
