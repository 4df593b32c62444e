"""95th percentile, over the finished requests due in the window, of
(last token time - first token time) / (tokens - 1)."""
from ._window import due_in_window, percentile


def read(run):
    vals = [(r.t_last - r.t_first) * 1e3 / (r.n_tokens - 1)
            for r in due_in_window(run) if r.finished and r.n_tokens > 1]
    return percentile(vals, 95)
