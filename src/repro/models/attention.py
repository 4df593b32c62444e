"""GQA attention: RoPE, optional qk-norm, sliding window, KV cache decode.

Three entry points:
  * ``attn_forward``  — full-sequence causal attention (train / prefill);
    returns the KV tensors so prefill can seed a decode cache.
  * ``attn_decode``   — single-token step against a fixed-size KV cache
    (dense cache for full attention; ring buffer when sliding_window is
    set, which keeps long_500k memory O(window) instead of O(seq)).

The pure-jnp path here is the reference and the dry-run/roofline path (XLA
cost analysis reads it); the Pallas kernels in ``repro.kernels`` implement
the same math for TPU execution and are validated against these.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import _he, apply_rope, rope_freqs

Array = jnp.ndarray


class KVCache(NamedTuple):
    k: Array          # [B, C, n_kv, hd]  (C = cache capacity)
    v: Array          # [B, C, n_kv, hd]
    length: Array     # scalar int32: number of valid positions (global pos)

    @property
    def capacity(self) -> int:
        return self.k.shape[1]


class QuantKVCache(NamedTuple):
    """int8 KV cache: symmetric absmax quantization per (position, head)."""

    k: Array          # int8 [B, C, n_kv, hd]
    v: Array          # int8 [B, C, n_kv, hd]
    k_scale: Array    # f32  [B, C, n_kv]
    v_scale: Array    # f32  [B, C, n_kv]
    length: Array

    @property
    def capacity(self) -> int:
        return self.k.shape[1]


class PagedKVCache(NamedTuple):
    """Block-pooled KV cache (vLLM-style paged attention).

    Instead of every slot owning a dense ``[C, nkv, hd]`` row, KV lives in
    a shared pool of ``P`` fixed-size blocks and each slot maps its logical
    positions onto pool blocks through a block table:

        k, v          [L, P, nkv, bs, hd]   pool (bs = block_size); the same
                                            block id addresses layer-aligned
                                            physical blocks in every layer;
                                            head-major, so each block is one
                                            (bs, hd) TPU tile per KV head
        block_tables  [B, n_bt] int32       per-slot logical->physical map;
                                            entries == P (one past the pool)
                                            are the UNASSIGNED sentinel
        length        [L, B] int32          per-layer per-slot position
        k_scale/v_scale [L, P, nkv, bs] f32 absmax scales when the pool is
                                            int8 (``kv_cache_dtype="int8"``);
                                            None for full-precision pools

    Logical position ``p`` of slot ``b`` lives at
    ``pool[layer, block_tables[b, p // bs], :, p % bs]``. Writes through a
    sentinel entry are dropped (``.at[...].set(mode="drop")``), so rows
    whose requests have retired can keep stepping inside a fused scan
    without corrupting blocks that were freed and reassigned; reads clip
    the sentinel and rely on the validity mask (``slots <= pos``) plus the
    allocator's invariant that every block at index <= pos // bs of a live
    slot is assigned.

    The pool is sized independently of the slot count: admission is gated
    by *tokens in use* (``serving.continuous.BlockAllocator``), not by
    worst-case per-slot capacity.
    """

    k: Array
    v: Array
    block_tables: Array
    length: Array
    k_scale: Optional[Array] = None
    v_scale: Optional[Array] = None

    @property
    def n_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def capacity(self) -> int:
        """Per-slot logical capacity (block-table width x block size)."""
        return self.block_tables.shape[1] * self.block_size


def _quantize(t: Array):
    """t [..., hd] -> (int8, scale[...])."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: Array, scale: Array, dtype):
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)) \
        .astype(dtype)


def init_attn(cfg: ModelConfig, key) -> dict:
    hd, nh, nkv, d = cfg.hd, cfg.n_heads, cfg.n_kv_eff, cfg.d_model
    ks = jax.random.split(key, 6)
    p = {
        "wq": _he(ks[0], (d, nh * hd), cfg.jdtype),
        "wk": _he(ks[1], (d, nkv * hd), cfg.jdtype),
        "wv": _he(ks[2], (d, nkv * hd), cfg.jdtype),
        "wo": _he(ks[3], (nh * hd, d), cfg.jdtype),
    }
    if cfg.qk_norm:   # Qwen3-style per-head RMS norm on q and k
        p["q_norm"] = jnp.ones((hd,), cfg.jdtype)
        p["k_norm"] = jnp.ones((hd,), cfg.jdtype)
    return p


def _qk_rms(x: Array, scale: Array) -> Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _project_qkv(cfg: ModelConfig, p: dict, x: Array, positions: Array):
    B, S, _ = x.shape
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_eff
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, nh, hd)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(B, S, nkv, hd)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(B, S, nkv, hd)
    if cfg.qk_norm:
        q = _qk_rms(q, p["q_norm"])
        k = _qk_rms(k, p["k_norm"])
    cos, sin = rope_freqs(cfg, positions)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def _sdpa(cfg: ModelConfig, q: Array, k: Array, v: Array,
          mask: Array) -> Array:
    """q [B,S,nh,hd], k/v [B,T,nkv,hd], mask [B or 1, S, T] bool."""
    B, S, nh, hd = q.shape
    nkv = k.shape[2]
    group = nh // nkv
    qg = q.reshape(B, S, nkv, group, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    logits = logits / (hd ** 0.5)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", w, v).reshape(B, S, nh, hd)
    return out


def causal_mask(cfg: ModelConfig, q_pos: Array, kv_pos: Array) -> Array:
    """[1, S, T] bool: kv visible to query (causal + optional window)."""
    m = kv_pos[None, :] <= q_pos[:, None]
    if cfg.sliding_window is not None:
        m &= kv_pos[None, :] > q_pos[:, None] - cfg.sliding_window
    return m[None]


def attn_forward(cfg: ModelConfig, p: dict, x: Array,
                 positions: Optional[Array] = None):
    """Full-sequence causal attention. x [B,S,d] -> (y [B,S,d], (k, v))."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    q, k, v = _project_qkv(cfg, p, x, positions)
    if cfg.use_kernels and S % 16 == 0:
        from ..kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True,
                                   window=cfg.sliding_window)
    else:
        mask = causal_mask(cfg, positions, positions)
        out = _sdpa(cfg, q, k, v, mask)
    y = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, -1), p["wo"])
    return y, (k, v)


def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               dtype=None) -> KVCache:
    """Dense cache for full attention; ring buffer (capacity = window) when
    sliding_window is set."""
    if cfg.sliding_window is not None:
        capacity = min(capacity, cfg.sliding_window)
    dtype = dtype or cfg.jdtype
    shape = (batch, capacity, cfg.n_kv_eff, cfg.hd)
    if cfg.kv_cache_dtype == "int8":
        return QuantKVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32),
            length=jnp.zeros((), jnp.int32))
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((), jnp.int32))


def init_paged_cache(cfg: ModelConfig, batch: int, n_blocks: int,
                     block_size: int, n_bt: int,
                     dtype=None) -> PagedKVCache:
    """Zeroed paged pool + all-sentinel block tables (layer-stacked).

    ``n_bt`` is the block-table width = per-slot logical capacity in
    blocks. Paged decode requires full (non-windowed) attention; ring
    buffers keep the dense slot path.
    """
    assert cfg.sliding_window is None, "paged KV requires full attention"
    dtype = dtype or cfg.jdtype
    L = cfg.n_layers
    shape = (L, n_blocks, cfg.n_kv_eff, block_size, cfg.hd)
    tables = jnp.full((batch, n_bt), n_blocks, jnp.int32)
    length = jnp.zeros((L, batch), jnp.int32)
    if cfg.kv_cache_dtype == "int8":
        return PagedKVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            block_tables=tables, length=length,
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32))
    return PagedKVCache(k=jnp.zeros(shape, dtype),
                        v=jnp.zeros(shape, dtype),
                        block_tables=tables, length=length)


def gather_paged(pool: Array, block_tables: Array) -> Array:
    """One layer's pool [P, nkv, bs, ...] -> dense [B, n_bt*bs, nkv, ...]
    through the block tables [B, n_bt]. Sentinel entries clip to a real
    block; callers mask by position."""
    P, bs = pool.shape[0], pool.shape[2]
    B, n_bt = block_tables.shape
    g = pool[jnp.clip(block_tables, 0, P - 1)]       # [B, n_bt, nkv, bs, ...]
    g = jnp.swapaxes(g, 2, 3)                        # [B, n_bt, bs, nkv, ...]
    return g.reshape((B, n_bt * bs) + g.shape[3:])


def attn_decode_paged(cfg: ModelConfig, p: dict, x: Array, pc: PagedKVCache,
                      pos, layer: int):
    """One-token step against the paged block pool.

    x [B,1,d]; ``pc`` the layer-stacked :class:`PagedKVCache`; ``pos`` [B]
    the layer's per-slot positions; ``layer`` a static index. Returns
    (y [B,1,d], pc) with the new token's KV scattered into
    ``pool[layer, block_tables[b, pos//bs], :, pos % bs]`` — writes through
    sentinel / out-of-table positions are dropped, so retired rows riding
    a fused scan are harmless. The attend path gathers the slot's blocks
    back into dense ``[B, C, nkv, hd]`` (reference; pinned token-for-token
    against ``attn_decode_stacked``) or runs the Pallas paged kernel over
    the pool directly when ``cfg.use_decode_kernel`` is set.
    """
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(cfg, p, x, pos[:, None].astype(jnp.int32))
    P, bs = pc.n_blocks, pc.block_size
    n_bt = pc.block_tables.shape[1]
    C = n_bt * bs
    rows = jnp.arange(B)
    bidx = pos // bs
    # block id of the write; out-of-table positions (dead rows that kept
    # stepping) map to the sentinel so mode="drop" discards them
    blk = jnp.where(bidx < n_bt,
                    pc.block_tables[rows, jnp.minimum(bidx, n_bt - 1)], P)
    off = pos % bs

    heads = jnp.arange(pc.k.shape[2])[None, :]

    def put(pool, val):
        # one (row, head) vector per update, so the scatter's window is the
        # minor dim alone and XLA keeps the pool in the kernel's layout
        return pool.at[layer, blk[:, None], heads, off[:, None]].set(
            val[:, 0], mode="drop")

    quant = pc.k_scale is not None
    if quant:
        kq, ks = _quantize(k_new)
        vq, vs = _quantize(v_new)
        pc = pc._replace(k=put(pc.k, kq), v=put(pc.v, vq),
                         k_scale=put(pc.k_scale, ks),
                         v_scale=put(pc.v_scale, vs))
    else:
        pc = pc._replace(k=put(pc.k, k_new), v=put(pc.v, v_new))

    if cfg.use_decode_kernel and not quant:
        from ..kernels import ops as kops
        out = kops.paged_decode_attention(q, pc.k, pc.v, pc.block_tables,
                                          pos, layer=layer)
        y = jnp.einsum("bsh,hd->bsd", out.reshape(B, 1, -1), p["wo"])
        return y, pc
    # reference / int8 path: gather the slot's blocks into the dense
    # [B, C, nkv, hd] layout (sentinels clip to a real block; the validity
    # mask hides whatever they alias) and reuse the slot attend
    k = gather_paged(pc.k[layer], pc.block_tables)
    v = gather_paged(pc.v[layer], pc.block_tables)
    if quant:
        k_sc = gather_paged(pc.k_scale[layer], pc.block_tables)
        v_sc = gather_paged(pc.v_scale[layer], pc.block_tables)
        k = _dequantize(k, k_sc, x.dtype)
        v = _dequantize(v, v_sc, x.dtype)
    valid = _decode_valid(cfg, pos, pos, B, C, per_row=True)
    y = _decode_attend(cfg, p, q, k, v, valid, B, C)
    return y, pc


def cache_from_prefill(cfg: ModelConfig, k: Array, v: Array,
                       capacity: int) -> KVCache:
    """Seed a decode cache with prefill KV (keeps the trailing window when
    sliding)."""
    B, S = k.shape[:2]
    if cfg.sliding_window is not None:
        capacity = min(capacity, cfg.sliding_window)
        w = capacity
        # place the last w positions at ring slots pos % w
        idx = (jnp.arange(S - w, S) % w) if S >= w else None
        kc = jnp.zeros((B, w) + k.shape[2:], k.dtype)
        vc = jnp.zeros((B, w) + v.shape[2:], v.dtype)
        if idx is not None:
            kc = kc.at[:, idx].set(k[:, -w:])
            vc = vc.at[:, idx].set(v[:, -w:])
        else:
            kc = kc.at[:, :S].set(k)
            vc = vc.at[:, :S].set(v)
        return _maybe_quantize_cache(
            cfg, KVCache(k=kc, v=vc, length=jnp.asarray(S, jnp.int32)))
    pad = capacity - S
    kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return _maybe_quantize_cache(
        cfg, KVCache(k=kc, v=vc, length=jnp.asarray(S, jnp.int32)))


def _maybe_quantize_cache(cfg: ModelConfig, cache: KVCache):
    if cfg.kv_cache_dtype != "int8":
        return cache
    kq, ks = _quantize(cache.k)
    vq, vs = _quantize(cache.v)
    return QuantKVCache(k=kq, v=vq, k_scale=ks, v_scale=vs,
                        length=cache.length)


def _decode_pos_slot(cfg: ModelConfig, pos, C: int):
    """Write slot for the new token: ring position when sliding."""
    if cfg.sliding_window is not None:
        return (pos % C).astype(jnp.int32)
    return pos


def _decode_valid(cfg: ModelConfig, pos, slot, B: int, C: int,
                  per_row: bool) -> Array:
    """[B or 1, 1, C] bool mask over cache slots (capacity / ring window)."""
    slots = jnp.arange(C)
    pos_b = pos[:, None] if per_row else pos[None, None]      # broadcastable
    slot_b = slot[:, None] if per_row else slot[None, None]
    if cfg.sliding_window is not None:
        # ring buffer: reconstruct global positions per slot
        kv_pos = jnp.where(slots[None] <= slot_b,
                           pos_b - slot_b + slots[None],
                           pos_b - slot_b + slots[None] - C)
        valid = (kv_pos >= 0) & (kv_pos > pos_b - cfg.sliding_window)
    else:
        valid = slots[None] <= pos_b
    return valid.reshape((B if per_row else 1), 1, C)


def _decode_attend(cfg: ModelConfig, p: dict, q: Array, k: Array, v: Array,
                   valid: Array, B: int, C: int) -> Array:
    """Attend one query token over the cache and project out.

    Dispatches to the Pallas decode-attention slot kernel when
    ``cfg.use_decode_kernel`` is set (per-row valid masks cover both ragged
    continuous-batching lengths and ring-buffer windows); the jnp ``_sdpa``
    is the cross-checked reference.
    """
    if cfg.use_decode_kernel:
        from ..kernels import ops as kops
        out = kops.decode_attention(
            q, k, v, jnp.broadcast_to(valid[:, 0, :], (B, C)))
    else:
        mask = jnp.broadcast_to(valid, (B, 1, C))
        out = _sdpa(cfg, q, k, v, mask)
    return jnp.einsum("bsh,hd->bsd", out.reshape(B, 1, -1), p["wo"])


def attn_decode(cfg: ModelConfig, p: dict, x: Array, cache):
    """One-token step. x [B,1,d] -> (y [B,1,d], new cache).

    ``cache.length`` may be a scalar (aligned batch; the M/G/1 serving
    path and the dry-run) or a vector [B] (continuous batching: every slot
    sits at its own position; writes become per-row scatters and the mask
    goes per-row).
    """
    B = x.shape[0]
    quant = isinstance(cache, QuantKVCache)
    pos = cache.length                       # global position of the new token
    per_row = pos.ndim == 1
    rope_pos = pos[:, None] if per_row else pos[None]
    q, k_new, v_new = _project_qkv(cfg, p, x, rope_pos.astype(jnp.int32))
    C = cache.capacity
    slot = _decode_pos_slot(cfg, pos, C)

    if per_row:
        rows = jnp.arange(B)

        def put(buf, val):                   # val [B, 1, ...] -> row scatter
            return buf.at[rows, slot].set(val[:, 0])
    else:
        def put(buf, val):
            start = (0, slot) + (0,) * (buf.ndim - 2)
            return jax.lax.dynamic_update_slice(buf, val, start)

    if quant:
        kq, ks = _quantize(k_new)
        vq, vs = _quantize(v_new)
        k_int = put(cache.k, kq)
        v_int = put(cache.v, vq)
        k_sc = put(cache.k_scale, ks)
        v_sc = put(cache.v_scale, vs)
        k = _dequantize(k_int, k_sc, x.dtype)
        v = _dequantize(v_int, v_sc, x.dtype)
    else:
        k = put(cache.k, k_new)
        v = put(cache.v, v_new)

    valid = _decode_valid(cfg, pos, slot, B, C, per_row)
    y = _decode_attend(cfg, p, q, k, v, valid, B, C)
    if quant:
        return y, QuantKVCache(k=k_int, v=v_int, k_scale=k_sc,
                               v_scale=v_sc, length=pos + 1)
    return y, KVCache(k=k, v=v, length=pos + 1)


def attn_decode_stacked(cfg: ModelConfig, p: dict, x: Array, kv, pos,
                        layer: int):
    """One-token step scattering straight into STACKED cache leaves.

    x [B,1,d]; ``kv`` a stacked :class:`KVCache` or :class:`QuantKVCache`
    (leaves [L, B, C, ...]) with ``layer`` a static (trace-time) index into
    the leading stack axis; ``pos`` the layer's cache length (scalar or
    [B]). Returns (y, kv) with the new token's KV written in place at
    ``[layer, :, slot]`` — no per-layer slice-out/write-back copies, which
    is what lets XLA keep the whole stacked cache aliased as a loop carry
    in the serving engines' fused decode scan. The caller owns the
    ``length`` update. Float math is identical to :func:`attn_decode`
    (int8: identical to the quantized slot path — scales land at the same
    per-(position, head) granularity).
    """
    B = x.shape[0]
    quant = isinstance(kv, QuantKVCache)
    per_row = pos.ndim == 1
    rope_pos = pos[:, None] if per_row else pos[None]
    q, k_new, v_new = _project_qkv(cfg, p, x, rope_pos.astype(jnp.int32))
    C = kv.k.shape[-3]
    slot = _decode_pos_slot(cfg, pos, C)

    if per_row:
        rows = jnp.arange(B)

        def put(buf, val):                  # val [B, 1, ...] -> row scatter
            return buf.at[layer, rows, slot].set(val[:, 0])
    else:
        def put(buf, val):
            start = (layer, 0, slot) + (0,) * (buf.ndim - 3)
            return jax.lax.dynamic_update_slice(buf, val[None], start)

    if quant:
        kq, ks = _quantize(k_new)
        vq, vs = _quantize(v_new)
        kv = kv._replace(k=put(kv.k, kq), v=put(kv.v, vq),
                         k_scale=put(kv.k_scale, ks),
                         v_scale=put(kv.v_scale, vs))
        k = _dequantize(kv.k[layer], kv.k_scale[layer], x.dtype)
        v = _dequantize(kv.v[layer], kv.v_scale[layer], x.dtype)
    else:
        kv = kv._replace(k=put(kv.k, k_new), v=put(kv.v, v_new))
        k = kv.k[layer]
        v = kv.v[layer]
    valid = _decode_valid(cfg, pos, slot, B, C, per_row)
    y = _decode_attend(cfg, p, q, k, v, valid, B, C)
    return y, kv
