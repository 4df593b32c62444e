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
engine's block pool in place through each slot's block table: one grid
step per slot, which copies only the slot's live head-major
``[nkv, bs, hd]`` blocks, several a group, double-buffered.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_C = 512
NEG_INF = -1e30
# K and V bytes of one group of pages in the paged kernel (two groups,
# double-buffered, sit in VMEM)
GROUP_BYTES = 512 * 1024


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


def pages_per_group(nkv: int, block_size: int, hd: int, itemsize: int,
                    n_bt: int) -> int:
    """Pages one DMA group of :func:`paged_decode_attention` holds: the
    most whose K and V stay within ``GROUP_BYTES``, at most the table.
    (bs 16, nkv 8, hd 128, bf16: 8 pages, 128 tokens.)"""
    page = 2 * nkv * block_size * hd * itemsize
    return max(1, min(n_bt, GROUP_BYTES // page))


def _paged_kernel(layer_ref, n_live_ref, bt_ref, pos_ref, q_ref, k_hbm,
                  v_hbm, o_ref, kbuf, vbuf, sem, slot_ref, *,
                  block_size: int, ppg: int, n_bt: int, sm_scale: float):
    """Online-softmax decode of one row over its live pages, all KV heads
    at once.

    Scalar prefetch (SMEM): the layer, ``n_live`` the rows' live pages,
    the flattened block tables and the positions. The pools stay in HBM.
    Pages ``j < n_live`` are copied ``ppg`` at a time into one of two
    VMEM slots ``[nkv, ppg*bs, hd]``, each page's ``[nkv, bs, hd]`` in
    one DMA a pool; group ``g+1`` (or, after a row's last group, the next
    row's first) is started before group ``g`` is waited on. Pages past
    ``n_live`` are neither copied nor attended. ``slot_ref`` (SMEM)
    carries the slot of a row's first group from one grid step to the
    next, so the grid runs in order (``arbitrary``).
    """
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    layer = layer_ref[0]
    bs = block_size
    tokens = ppg * bs

    def group_dma(row, g, slot, *, wait: bool):
        n = n_live_ref[row]
        for i in range(ppg):
            j = g * ppg + i

            @pl.when(j < n)
            def _page():
                page = bt_ref[row * n_bt + j]
                for pool, buf, kv in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                    cp = pltpu.make_async_copy(
                        pool.at[layer, page],
                        buf.at[slot, :, pl.ds(i * bs, bs), :],
                        sem.at[kv, slot])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    n = n_live_ref[b]
    n_groups = (n + ppg - 1) // ppg

    @pl.when(b == 0)
    def _first_row():
        # masked tail pages are never copied: keep whatever the slots
        # hold finite (0 x NaN would poison the PV product)
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0

    # groups alternate between the slots, across rows too
    first = slot_ref[0]
    nxt = jnp.minimum(b + 1, n_rows - 1)
    next_live = (b + 1 < n_rows) & (n_live_ref[nxt] > 0)
    # row 0 starts its own first group; an empty row starts the next's
    own = (b == 0) & (n_groups > 0)

    @pl.when(own | ((n_groups == 0) & next_live))
    def _start_first():
        group_dma(jnp.where(own, b, nxt), 0, first, wait=False)

    # last position attended: the new token's, within the live pages
    last = jnp.minimum(pos_ref[b], n * bs - 1)
    q = q_ref[...]                                 # [nkv, G, hd]
    nkv, G, hd = q.shape

    def body(g, carry):
        m_prev, l_prev, acc = carry
        # the group after g: this row's g+1, else the next row's first
        mine = g + 1 < n_groups

        @pl.when(mine | next_live)
        def _start_next():
            group_dma(jnp.where(mine, b, nxt), jnp.where(mine, g + 1, 0),
                      (first + g + 1) % 2, wait=False)

        slot = (first + g) % 2
        group_dma(b, g, slot, wait=True)
        k = kbuf[slot]                             # [nkv, ppg*bs, hd]
        v = vbuf[slot]
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * sm_scale
        idx = g * tokens + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, tokens), 2)
        s = jnp.where(idx <= last, s, NEG_INF)     # [nkv, G, ppg*bs]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((nkv, G), NEG_INF, jnp.float32),
            jnp.zeros((nkv, G), jnp.float32),
            jnp.zeros((nkv, G, hd), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_groups, body, init)
    slot_ref[0] = (first + n_groups) % 2
    # an empty row keeps acc 0 and l 0: it writes zeros
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_tables, pos, *,
                           layer, interpret: bool = False):
    """One query token per slot over one layer of a paged KV pool.

    Layouts:
        q            [B, nkv, G, hd]
        k/v_pool     [L, P, nkv, bs, hd]  layer-stacked, head-major blocks
        block_tables [B, n_bt] int32 (entry P = unassigned sentinel)
        pos          [B] int32 position of the NEW token (slots <= pos
                     attend; the new token's KV must already be written)
        layer        int32 scalar, the pool layer attended
    Returns [B, nkv, G, hd].

    Work follows each row's live length, not the table's capacity: a row
    reads its ``n_live = min(pos // bs + 1, leading real entries)``
    blocks of layer ``layer`` straight from the pool, several a group
    (:func:`pages_per_group`). Tables are filled as a prefix, so the
    leading real entries are all the row holds; a retired row (all
    sentinel) reads nothing and returns zeros, and a row stepping past
    its blocks attends only those. No sentinel index is ever copied.
    Lengths and the layer are traced, so neither ever recompiles: one
    kernel serves every layer of a model. A block is the TPU tile
    ``(bs, hd)`` for every KV head, so ``bs`` must be a multiple of 8
    (16 for bf16).
    """
    B, nkv, G, hd = q.shape
    P, bs = k_pool.shape[1], k_pool.shape[3]
    n_bt = block_tables.shape[1]
    ppg = pages_per_group(nkv, bs, hd, k_pool.dtype.itemsize, n_bt)
    bt = jnp.asarray(block_tables, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    # live pages: up to the new token's, within the leading real entries
    real = jnp.min(jnp.where(bt < P, n_bt, jnp.arange(n_bt)[None, :]),
                   axis=1)
    n_live = jnp.clip(pos // bs + 1, 0, real)
    kernel = functools.partial(_paged_kernel, block_size=bs, ppg=ppg,
                               n_bt=n_bt, sm_scale=1.0 / (hd ** 0.5))

    def row_map(b, *_):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, nkv, G, hd), row_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, nkv, G, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, nkv, ppg * bs, hd), k_pool.dtype),
            pltpu.VMEM((2, nkv, ppg * bs, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), n_live, bt.reshape(-1), pos,
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
