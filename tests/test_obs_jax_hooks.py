"""Recompile / transfer guards wired through ``compat.jit``.

Satellite regression pinned here: **one compile serves all budgets** —
``DecodeEngine.generate`` across ragged per-request budgets and chunk
boundaries must trace each jitted decode entry point exactly once,
because budgets ride as device state (masks), never as static shapes.
``obs.jax_hooks`` makes that assertable: ``compat.jit(label=...)``
counts a trace every time the wrapped python function actually runs
(jit calls it only while tracing), and ``assert_max_compiles`` turns a
silent recompile storm into a hard failure.

Counters are process-global (JAX's compile caches are too), so every
test starts with ``jax_hooks.reset()`` and builds FRESH engines — a new
``DecodeEngine`` makes new jit-wrapped function objects with their own
caches, so counts reflect this test alone.
"""
import numpy as np
import pytest

from repro import compat
from repro.obs import jax_hooks


@pytest.fixture(autouse=True)
def _clean_counters():
    jax_hooks.reset()
    yield
    jax_hooks.reset()


def test_label_names_the_xla_module():
    """The label, not the python function's name, names the compiled
    module, so a device trace's module line survives a rename."""
    import jax.numpy as jnp

    def _private_impl(x, *, k):
        return x * k

    f = compat.jit(_private_impl, static_argnames=("k",),
                   label="hooks.scale")
    text = f.lower(jnp.ones(4), k=3).as_text()
    assert "module @jit_hooks.scale" in text
    assert "_private_impl" not in text
    assert f(jnp.ones(4), k=3).tolist() == [3.0] * 4


def test_count_traces_one_per_compile():
    import jax.numpy as jnp

    f = compat.jit(lambda x: x * 2, label="hooks.double")
    f(jnp.ones(4))
    f(jnp.ones(4))
    f(jnp.zeros(4))                      # same shape/dtype: cached
    assert jax_hooks.trace_counts()["hooks.double"] == 1
    f(jnp.ones(8))                       # new shape: retrace
    assert jax_hooks.trace_counts()["hooks.double"] == 2


def test_assert_max_compiles_raises_on_retrace_storm():
    import jax.numpy as jnp

    f = compat.jit(lambda x: x + 1, label="hooks.storm")
    for n in (2, 3, 4):
        f(jnp.ones(n))
    assert jax_hooks.assert_max_compiles("hooks.storm", 3) == 3
    with pytest.raises(AssertionError, match="hooks.storm"):
        jax_hooks.assert_max_compiles("hooks.storm", 2)


def test_to_host_counts_transfers():
    import jax.numpy as jnp
    x = jnp.ones(3)
    out = jax_hooks.to_host(x, "hooks.sync")
    np.testing.assert_array_equal(out, np.ones(3))
    jax_hooks.to_host(x, "hooks.sync")
    assert jax_hooks.transfer_counts()["hooks.sync"] == 2
    snap = jax_hooks.snapshot()
    assert snap["transfers"]["hooks.sync"] == 2


def test_reset_scoped_and_global():
    import jax.numpy as jnp
    f = compat.jit(lambda x: x, label="hooks.a")
    g = compat.jit(lambda x: x, label="hooks.b")
    f(jnp.ones(2))
    g(jnp.ones(2))
    jax_hooks.reset("hooks.a")
    counts = jax_hooks.trace_counts()
    assert "hooks.a" not in counts and counts["hooks.b"] == 1
    jax_hooks.reset()
    assert jax_hooks.trace_counts() == {}


def test_one_compile_serves_all_budgets():
    """The tentpole regression: ragged budgets and chunk-boundary
    crossings reuse ONE compilation of each decode entry point."""
    import jax

    from repro.configs import get_config
    from repro.models import init_params, reduced
    from repro.serving import DecodeEngine

    cfg = reduced(get_config("qwen3-0.6b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, cache_capacity=64, chunk=4)
    prompts = np.ones((2, 8), dtype=np.int32)

    # ragged budgets, equal budgets, budgets off the chunk boundary, and a
    # budget that exactly fills a chunk — same (B, S) shapes throughout
    for budgets in ([3, 7], [5, 2], [8, 8], [4, 4], [1, 6]):
        eng.generate(prompts, budgets, max_extra_tokens=0)

    assert jax_hooks.assert_max_compiles("engine.prefill", 1) == 1
    assert jax_hooks.assert_max_compiles("engine.scan", 1) == 1
    # the per-token reference loop is never dispatched by the fast path
    assert jax_hooks.trace_counts().get("engine.step", 0) == 0

    # a genuinely new prompt shape MAY retrace prefill (shape-polymorphic
    # entry), but decode must still reuse the single scan compilation
    eng.generate(np.ones((2, 16), dtype=np.int32), [3, 5],
                 max_extra_tokens=0)
    assert jax_hooks.assert_max_compiles("engine.scan", 1) == 1
