"""Process start to the window's start: loading, weights, warm-up and,
in a run that compiles, compilation."""


def read(run):
    return run.setup_s
