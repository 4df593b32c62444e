"""Output tokens that reached the host inside the window, over the
window's length."""


def read(run):
    lp = run.loop
    n = sum(k for r in lp.records.values() for t, k in r.arrivals
            if lp.t_open <= t <= lp.t_close)
    return n / (lp.t_close - lp.t_open)
