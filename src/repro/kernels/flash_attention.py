"""Pallas TPU flash attention (prefill): online-softmax, causal, GQA-folded.

TPU adaptation notes: blocks are MXU-aligned (block_q = block_k = 128 by
default), the KV loop is the innermost *sequential* grid dimension so the
(m, l, acc) online-softmax state lives in VMEM scratch across KV steps, and
the GQA query-head group G is folded into the q-block rows so one kernel
invocation serves all query heads of a KV head (no KV duplication in HBM —
the contrast with a CUDA warp-per-head layout).

Layouts:
    q:  [Bkv, G, S, hd]   (Bkv = batch * n_kv_heads, G = q heads per kv head)
    k:  [Bkv, S, hd]
    v:  [Bkv, S, hd]
    out:[Bkv, G, S, hd]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            block_q: int, block_k: int, n_kv_blocks: int, causal: bool,
            window: int | None, sm_scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = kj * block_k

    # skip blocks that are entirely masked out
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1
                              > q_start - window)

    @pl.when(run)
    def _compute():
        G = q_ref.shape[1]
        q = q_ref[0].reshape(G * block_q, q_ref.shape[-1])
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [G*bq, bk]
        rows = jax.lax.broadcasted_iota(jnp.int32, (G * block_q, block_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (G * block_q, block_k), 1)
        q_pos = q_start + rows % block_q
        k_pos = k_start + cols
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        G = q_ref.shape[1]
        l = jnp.maximum(l_ref[...], 1e-30)
        out = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        o_ref[0] = out.reshape(G, block_q, o_ref.shape[-1])


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """q [Bkv, G, S, hd]; k, v [Bkv, S, hd] -> [Bkv, G, S, hd]."""
    Bkv, G, S, hd = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq, nk = S // block_q, S // block_k
    sm_scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, n_kv_blocks=nk,
        causal=causal, window=window, sm_scale=sm_scale)

    return pl.pallas_call(
        kernel,
        grid=(Bkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, G, block_q, hd), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, block_q, hd),
                               lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * block_q, hd), jnp.float32),
            pltpu.VMEM((G * block_q,), jnp.float32),
            pltpu.VMEM((G * block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
