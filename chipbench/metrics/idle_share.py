"""1 - (union of device op intervals) / traced window, in %."""


def read(run):
    t = run.trace
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
