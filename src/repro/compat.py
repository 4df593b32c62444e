"""Version- and backend-sensitive JAX calls, in one place.

Written for the installed JAX (0.9). Everything in the repo that needs
double precision (the control-plane solvers, the queueing DES) goes
through :func:`enable_x64`, which also keeps that work on the host CPU:
the TPU only emulates float64, and the allocator's solve came out NaN
there.

Buffer donation is version- and backend-sensitive too: some backends (and
older CPU clients) silently ignore ``donate_argnums`` and warn on every
call. :func:`donation_supported` probes the default backend once, and
:func:`jit` only requests donation where it is actually honored, so the
serving fast path gets in-place cache updates without per-call warning
spam elsewhere.
"""
from __future__ import annotations

import contextlib
import functools
import warnings

import jax


@contextlib.contextmanager
def enable_x64(enabled: bool = True):
    """64-bit JAX computation within the scope, on the host CPU device.

    Arrays made and programs run inside land on the CPU whatever the
    default backend is, so the float64 solvers give the same answer in a
    process that holds a TPU as in a CPU-only one.
    """
    with jax.enable_x64(enabled), jax.default_device(jax.devices("cpu")[0]):
        yield


@functools.lru_cache(maxsize=1)
def donation_supported() -> bool:
    """True iff ``jit(..., donate_argnums=...)`` actually reuses buffers.

    Probes the default backend with a tiny donated identity-plus-one: a
    backend that honors donation deletes the input buffer and emits no
    "donation is not implemented" warning. Cached so the probe (one tiny
    compile) runs at most once per process.
    """
    import jax.numpy as jnp

    probe = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    x = jnp.zeros((8,), jnp.float32)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        probe(x).block_until_ready()
    warned = any("donat" in str(w.message).lower() for w in caught)
    deleted = getattr(x, "is_deleted", lambda: False)()
    return deleted and not warned


def jit(fun, *, donate_argnums=(), label=None, **kwargs):
    """``jax.jit`` that requests buffer donation only where it is honored.

    The serving engines route every cache-threading entry point (prefill
    insert / decode step / fused decode scan) through this so the KV cache
    is updated in place on backends that support donation, and silently
    falls back to copying semantics (no per-call warnings) on backends
    that do not.

    ``label`` registers the entry point with ``obs.jax_hooks``: the python
    function is wrapped so each JAX *trace* (compilation) increments the
    label's counter, making retraces observable and assertable
    (``obs.jax_hooks.assert_max_compiles``). Per-call cost after tracing
    is zero — jit caches the traced computation, the wrapper only runs
    while tracing.
    """
    if label is not None:
        from .obs import jax_hooks
        fun = jax_hooks.count_traces(fun, label)
    if donate_argnums and donation_supported():
        return jax.jit(fun, donate_argnums=donate_argnums, **kwargs)
    return jax.jit(fun, **kwargs)

