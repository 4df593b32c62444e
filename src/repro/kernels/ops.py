"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy (:func:`_interpret`): on the TPU backend the Pallas kernel
runs compiled; on the CPU backend (unit tests) it runs in interpret mode,
which executes the same kernel body for correctness; any other backend is
an error, never a silent interpreter. The choice follows the process's
default backend, so a compile for a described chip made from a CPU
process must call the kernels with ``interpret=False`` itself.
``force_ref=True`` bypasses Pallas entirely (used by the dry-run so the
XLA cost model sees analyzable HLO instead of an opaque custom call).

Model-facing adapters translate between model layouts ([B, S, nh, hd]) and
kernel layouts ([Bkv, G, S, hd] etc.).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention as _decode_pallas
from .decode_attention import paged_decode_attention as _paged_pallas
from .flash_attention import flash_attention as _flash_pallas
from .fused_ffn import fused_ffn as _ffn_pallas
from .rwkv6_scan import rwkv6_scan as _rwkv_pallas
from .ssd_scan import ssd_scan as _ssd_pallas

Array = jnp.ndarray


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on the TPU or interpreted on the "
            f"CPU; the default backend is {backend!r}")
    return backend == "cpu"


# ------------------------------------------------------------------ attention
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int | None = None,
                    force_ref: bool = False) -> Array:
    """Model layout: q [B,S,nh,hd]; k,v [B,S,nkv,hd] -> [B,S,nh,hd]."""
    B, S, nh, hd = q.shape
    nkv = k.shape[2]
    G = nh // nkv
    qk = q.reshape(B, S, nkv, G, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(B * nkv, G, S, hd)
    kk = k.transpose(0, 2, 1, 3).reshape(B * nkv, S, hd)
    vv = v.transpose(0, 2, 1, 3).reshape(B * nkv, S, hd)
    if force_ref:
        qf = qk.reshape(B * nkv * G, S, hd)
        kf = jnp.repeat(kk[:, None], G, 1).reshape(B * nkv * G, S, hd)
        vf = jnp.repeat(vv[:, None], G, 1).reshape(B * nkv * G, S, hd)
        out = ref.flash_attention_ref(qf, kf, vf, causal=causal,
                                      window=window)
        out = out.reshape(B * nkv, G, S, hd)
    else:
        out = _flash_pallas(qk, kk, vv, causal=causal, window=window,
                            interpret=_interpret())
    return out.reshape(B, nkv, G, S, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(B, S, nh, hd)


def decode_attention(q: Array, k: Array, v: Array, valid: Array, *,
                     force_ref: bool = False) -> Array:
    """q [B,1,nh,hd]; k,v [B,C,nkv,hd]; valid [B,C] -> [B,1,nh,hd]."""
    B, _, nh, hd = q.shape
    C, nkv = k.shape[1], k.shape[2]
    G = nh // nkv
    qk = q.reshape(B, nkv, G, hd).reshape(B * nkv, G, hd)
    kk = k.transpose(0, 2, 1, 3).reshape(B * nkv, C, hd)
    vv = v.transpose(0, 2, 1, 3).reshape(B * nkv, C, hd)
    vd = jnp.repeat(valid[:, None, :], nkv, 1).reshape(B * nkv, C)
    if force_ref:
        out = ref.decode_attention_ref(qk, kk, vv, vd)
    else:
        out = _decode_pallas(qk, kk, vv, vd,
                             block_c=_divisor_block(C, 512),
                             interpret=_interpret())
    return out.reshape(B, 1, nh, hd)


def paged_decode_attention(q: Array, k_pool: Array, v_pool: Array,
                           block_tables: Array, pos: Array, *, layer: int,
                           force_ref: bool = False) -> Array:
    """Model layout: q [B,1,nh,hd]; k/v_pool [L,P,nkv,bs,hd] (attends
    layer ``layer``); block_tables [B,n_bt]; pos [B] -> [B,1,nh,hd].

    ``force_ref`` densifies the layer's pool through the block table
    (gather + masked reference attend) — the cross-check path for the
    scalar-prefetch kernel.
    """
    from ..models.attention import gather_paged

    B, _, nh, hd = q.shape
    P, nkv, bs = k_pool.shape[1], k_pool.shape[2], k_pool.shape[3]
    n_bt = block_tables.shape[1]
    G = nh // nkv
    qk = q.reshape(B, nkv, G, hd)
    if force_ref:
        C = n_bt * bs
        kk = gather_paged(k_pool[layer], block_tables)     # [B, C, nkv, hd]
        vv = gather_paged(v_pool[layer], block_tables)
        valid = (jnp.arange(C)[None, :] <= pos[:, None]) \
            & jnp.repeat(block_tables < P, bs, axis=1)
        out = ref.decode_attention_ref(
            qk.reshape(B * nkv, G, hd),
            kk.transpose(0, 2, 1, 3).reshape(B * nkv, C, hd),
            vv.transpose(0, 2, 1, 3).reshape(B * nkv, C, hd),
            jnp.repeat(valid[:, None, :], nkv, 1).reshape(B * nkv, C))
        return out.reshape(B, 1, nh, hd)
    out = _paged_pallas(qk, k_pool, v_pool, block_tables, pos, layer=layer,
                        interpret=_interpret())
    return out.reshape(B, 1, nh, hd)


# ----------------------------------------------------------------- recurrent
def ssd_scan(x, dt, a, Bm, Cm, *, chunk: int = 128, force_ref: bool = False):
    if force_ref:
        return ref.ssd_scan_ref(x, dt, a, Bm, Cm)
    return _ssd_pallas(x, dt, a, Bm, Cm, chunk=chunk,
                       interpret=_interpret())


def rwkv6_scan(r, k, v, la, u, *, chunk: int = 64, force_ref: bool = False):
    if force_ref:
        return ref.rwkv_scan_ref(r, k, v, la, u)
    return _rwkv_pallas(r, k, v, la, u, chunk=chunk,
                        interpret=_interpret())


# ----------------------------------------------------------------------- ffn
def _divisor_block(n: int, target: int) -> int:
    b = min(target, n)
    while n % b:
        b -= 1
    return max(b, 1)


def fused_ffn(x, wg, wu, wd, *, force_ref: bool = False):
    if force_ref:
        return ref.fused_ffn_ref(x, wg, wu, wd)
    bt = _divisor_block(x.shape[1], 128)
    bf = _divisor_block(wg.shape[-1], 512)
    return _ffn_pallas(x, wg, wu, wd, block_t=bt, block_f=bf,
                       interpret=_interpret())
