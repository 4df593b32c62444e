"""The float32 reference against the program's own prefill-then-decode
logits, and the seeded weights against the program's parameter layout."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import SMALL, TINY

from chipbench import model, weights
from chipbench.reference import qwen3

SEED = 2**31 + 41


@pytest.mark.parametrize("cfg", [TINY, SMALL], ids=["tied", "untied"])
def test_weights_match_program_layout_and_layers(cfg):
    from repro.models import init_params

    params = weights.model_params(cfg, SEED)
    want = jax.eval_shape(lambda k: init_params(model.model_config(cfg), k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for got, exp in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert got.shape == exp.shape and got.dtype == exp.dtype
    # each layer of the stacked tree is what the reference draws alone
    for layer in range(cfg["num_hidden_layers"]):
        alone = weights.layer_f32(cfg, SEED, layer)
        stacked = jax.tree.map(lambda t: t[layer].astype(jnp.float32),
                               params["blocks"])
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(weights.top_f32(cfg, SEED, "tok")),
        np.asarray(params["embed"]["tok"].astype(jnp.float32)))


def _program_logits(cfg, tokens, n_prompt, dtype):
    """Prefill the prompt, then decode the rest token by token through
    the cache; the logits that pick each following token."""
    from repro.models import decode_step, forward

    mc = dataclasses.replace(model.model_config(cfg), dtype=dtype)
    params = jax.tree.map(lambda t: t.astype(dtype),
                          weights.model_params(cfg, SEED))
    toks = jnp.asarray(tokens[None, :n_prompt])
    out = jax.jit(lambda p, t: forward(mc, p, t, return_cache=True,
                                       cache_capacity=tokens.shape[0]))(
        params, toks)
    step = jax.jit(lambda p, t, c: decode_step(mc, p, t, c))
    logits, cache = [out.logits[0, -1]], out.cache
    for t in tokens[n_prompt:-1]:
        o = step(params, jnp.asarray([[t]]), cache)
        logits.append(o.logits[0, 0])
        cache = o.cache
    return np.asarray(jnp.stack(logits).astype(jnp.float32))


def _reference_logits(cfg, tokens, n_prompt):
    h = qwen3.hidden(cfg, SEED, tokens[None, :])[0, n_prompt - 1:-1]
    with jax.default_matmul_precision("highest"):
        return np.asarray(h @ qwen3.head_weight(cfg, SEED))


@pytest.mark.parametrize("cfg", [TINY, SMALL], ids=["tied", "untied"])
def test_reference_matches_prefill_then_decode(cfg):
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg["vocab_size"], size=40).astype(np.int32)
    ref = _reference_logits(cfg, tokens, 24)
    scale = np.abs(ref).max()
    # float32 on both sides: only the order of summation differs, so the
    # logits agree to float32 rounding accumulated over two layers
    got32 = _program_logits(cfg, tokens, 24, "float32")
    np.testing.assert_allclose(got32, ref, atol=2e-5 * scale, rtol=0)
    # bfloat16, as served: every stored activation rounds to 8 bits of
    # mantissa (relative 2**-9), a few percent of the logit scale after
    # two layers, and far below the logits' own spread
    got16 = _program_logits(cfg, tokens, 24, "bfloat16")
    err = np.abs(got16 - ref).max()
    assert err < 0.05 * scale
    assert err > 10 * np.abs(got32 - ref).max()


def test_control_grids_round_as_stated():
    x = jnp.asarray([[0.5, -1.0, 0.013, 448.0]])
    np.testing.assert_allclose(np.asarray(qwen3._round(x, -1, "int8")),
                               np.round(np.asarray(x) / (448 / 127))
                               * (448 / 127), rtol=1e-6)
    fp8 = np.asarray(qwen3._round(x, -1, "fp8"))
    assert fp8[0, 3] == 448.0 and fp8[0, 1] == -1.0
    assert fp8[0, 2] != 0.013          # 3 bits of mantissa
    with pytest.raises(ValueError):
        qwen3._round(x, -1, "int4")
