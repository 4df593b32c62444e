"""Pallas TPU decode attention: one query token vs a long KV cache.

The serving hot spot for decode_32k / long_500k: a single new token attends
to a cache of C past positions. This is HBM-bandwidth-bound (the whole cache
streams through once per step), so the kernel's job is to keep the VMEM
working set small and the stream contiguous: the cache is blocked along C
(innermost sequential grid dim) with online-softmax state in VMEM scratch,
and all G query heads of a KV head share each cache block load (GQA fold —
one cache read amortized over G heads, the key roofline lever when kv heads
are few, e.g. starcoder2's kv=2).

Layouts:
    q:     [Bkv, G, hd]
    k, v:  [Bkv, C, hd]
    valid: [Bkv, C]  bool (masks ring-buffer slots / unfilled capacity)
    out:   [Bkv, G, hd]

The paged variant (:func:`paged_decode_attention`) reads the serving
engine's block pool in place, one head-major ``[nkv, bs, hd]`` block per
grid step, through the slot's block table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_C = 512
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, acc_ref, m_ref, l_ref, *,
            n_blocks: int, sm_scale: float):
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                   # [G, hd]
    k = k_ref[0]                                   # [bc, hd]
    v = v_ref[0]
    ok = valid_ref[0] != 0                         # [1, bc]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(ok, s, NEG_INF)                  # [G, bc]

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(cj == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _paged_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, block_size: int,
                  n_blocks_pool: int, sm_scale: float):
    """Online-softmax decode over a slot's block list, all KV heads at once.

    ``bt_ref`` / ``pos_ref`` are scalar-prefetched (SMEM): the block table
    feeds the k/v BlockSpec index maps — each grid step DMAs exactly the
    one physical block the slot's logical block ``j`` maps to, every head
    of it in one copy — and the kernel only masks. Same accumulator scheme
    as :func:`_kernel`, batched over the KV heads.
    """
    b = pl.program_id(0)
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...]                                 # [nkv, G, hd]
    k = k_ref[...]                                 # [nkv, bs, hd]
    v = v_ref[...]
    # logical position of each row of this block; sentinel blocks (table
    # entry == pool size) are fully masked
    off = jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_size), 2)
    ok = ((cj * block_size + off <= pos_ref[b])
          & (bt_ref[b, cj] < n_blocks_pool))
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(ok, s, NEG_INF)                  # [nkv, G, bs]

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(cj == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, block_tables, pos, *,
                           layer: int, interpret: bool = False):
    """One query token per slot over one layer of a paged KV pool.

    Layouts:
        q            [B, nkv, G, hd]
        k/v_pool     [L, P, nkv, bs, hd]  layer-stacked, head-major blocks
        block_tables [B, n_bt] int32 (entry P = unassigned sentinel)
        pos          [B] int32 position of the NEW token (slots <= pos
                     attend; the new token's KV must already be written)
    Returns [B, nkv, G, hd].

    The block table and positions ride scalar prefetch
    (``PrefetchScalarGridSpec``): the k/v index maps read
    ``block_tables[b, j]`` so the kernel streams exactly the slot's own
    physical blocks of layer ``layer`` (static) — the pool is never
    sliced, gathered or densified. A block is the TPU tile ``(bs, hd)``
    for every KV head, so ``bs`` must be a multiple of 8 (16 for bf16).
    """
    B, nkv, G, hd = q.shape
    P, bs = k_pool.shape[1], k_pool.shape[3]
    n_bt = block_tables.shape[1]
    kernel = functools.partial(_paged_kernel, block_size=bs,
                               n_blocks_pool=P,
                               sm_scale=1.0 / (hd ** 0.5))

    def q_map(b, j, bt, pos):
        return (b, 0, 0, 0)

    def kv_map(b, j, bt, pos):
        return (layer, jnp.minimum(bt[b, j], P - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_bt),
        in_specs=[
            pl.BlockSpec((None, nkv, G, hd), q_map),
            pl.BlockSpec((None, None, nkv, bs, hd), kv_map),
            pl.BlockSpec((None, None, nkv, bs, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((None, nkv, G, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((nkv, G, hd), jnp.float32),
            pltpu.VMEM((nkv, G), jnp.float32),
            pltpu.VMEM((nkv, G), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(pos, jnp.int32),
      q, k_pool, v_pool)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def decode_attention(q, k, v, valid, *, block_c: int = DEFAULT_BLOCK_C,
                     interpret: bool = False):
    """q [Bkv,G,hd]; k,v [Bkv,C,hd]; valid [Bkv,C] bool -> [Bkv,G,hd].

    The mask travels as int32 ``[Bkv, 1, C]`` so that each of its blocks,
    ``(1, block_c)`` in the last two dims, is a legal TPU tile; ``block_c``
    must then be a multiple of 128 or all of ``C``.
    """
    Bkv, G, hd = q.shape
    C = k.shape[1]
    block_c = min(block_c, C)
    assert C % block_c == 0
    nb = C // block_c
    kernel = functools.partial(_kernel, n_blocks=nb,
                               sm_scale=1.0 / (hd ** 0.5))
    return pl.pallas_call(
        kernel,
        grid=(Bkv, nb),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_c, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_c, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, block_c), lambda b, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, valid.astype(jnp.int32)[:, None, :])
