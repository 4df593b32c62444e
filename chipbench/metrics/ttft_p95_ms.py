"""95th percentile, over all requests due in the window that got a first
token, of due time to first token on the host."""
from ._window import due_in_window, percentile


def read(run):
    vals = [(r.t_first - r.due) * 1e3 for r in due_in_window(run)
            if r.t_first is not None]
    return percentile(vals, 95)
