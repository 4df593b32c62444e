"""Mean, over the finished requests due in the window, of last token time
minus due time: the paper's E[T]."""


from ._window import due_in_window


def read(run):
    vals = [r.t_last - r.due for r in due_in_window(run) if r.finished]
    return sum(vals) / len(vals) if vals else None
