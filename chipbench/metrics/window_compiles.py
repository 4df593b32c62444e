"""Traces of the engine's jitted entry points
(``repro.obs.jax_hooks.trace_counts``) from the window's start to the end
of the drain."""


def read(run):
    return run.window_compiles
