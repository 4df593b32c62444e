"""Host time of the window's ``step_chunk`` calls (each ends in a host
sync), over the decode steps they ran."""
from ._window import chunks_in_window


def read(run):
    ch = chunks_in_window(run)
    steps = sum(c.steps for c in ch)
    return sum(c.t1 - c.t0 for c in ch) * 1e3 / steps if steps else None
