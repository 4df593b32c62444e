"""Host time of the window's ``admit_many`` calls (prefill, paged insert,
first-token sync), summed, over the requests they admitted."""


def read(run):
    lp = run.loop
    adm = [a for a in lp.admissions if lp.t_open <= a.t0 < lp.t_close]
    n = sum(a.n for a in adm)
    return sum(a.t1 - a.t0 for a in adm) * 1e3 / n if n else None
