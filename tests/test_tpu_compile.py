"""The served path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, too much VMEM. These tests
compile each kernel of the main path at Qwen3-0.6B's widths (8 decode
rows, 8 KV heads, 2 query heads per KV head, head dim 128, bf16) for a
described, unattached ``v5e:2x2`` chip, and check that the compiled
program holds the kernel (``tpu_custom_call``) and not an interpreter.

The topology is described inside a module fixture, never at import, so
only the worker that runs this file loads the TPU compiler. The kernels
are called with ``interpret=False`` directly: ``kernels.ops`` picks the
mode from the process's backend, which is the CPU here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.flash_attention import flash_attention

B, NKV, G, HD = 8, 8, 2, 128
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_paged_decode_kernel_compiles(one_chip):
    """Paged decode over the serving pool: 28 layers, 8 rows x 64 pages
    of 16 tokens."""
    L, bs, n_bt = 28, 16, 64
    P = B * n_bt
    text = _compiled_text(
        lambda q, k, v, bt, pos: paged_decode_attention(
            q, k, v, bt, pos, layer=L - 1, interpret=False),
        one_chip,
        ((B, NKV, G, HD), BF16),
        ((L, P, NKV, bs, HD), BF16),
        ((L, P, NKV, bs, HD), BF16),
        ((B, n_bt), jnp.int32),
        ((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_slot_decode_kernel_compiles(one_chip):
    """Dense-slot decode over a 4096-token cache."""
    C = 4096
    text = _compiled_text(
        lambda q, k, v, valid: decode_attention(q, k, v, valid,
                                                interpret=False),
        one_chip,
        ((B * NKV, G, HD), BF16),
        ((B * NKV, C, HD), BF16),
        ((B * NKV, C, HD), BF16),
        ((B * NKV, C), jnp.bool_))
    assert "tpu_custom_call" in text


def test_flash_attention_kernel_compiles(one_chip):
    """Causal prefill over a 512-token prompt."""
    S = 512
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        one_chip,
        ((B * NKV, G, S, HD), BF16),
        ((B * NKV, S, HD), BF16),
        ((B * NKV, S, HD), BF16))
    assert "tpu_custom_call" in text
