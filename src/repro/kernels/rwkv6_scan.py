"""Pallas TPU RWKV6 wkv chunked scan (data-dependent per-channel decay).

Same chunking strategy as the SSD kernel, but the decay is a per-channel
vector (the RWKV6 "Finch" feature) and the bonus term u applies to the
current token only. State [hd, hd] carried in VMEM scratch across the
sequential chunk grid dimension.

Layouts:
    r, k, v, la [BH, S, hd]   (la = log decay < 0)
    u           [BH, hd]
    y           [BH, S, hd]
    s_final     [BH, hd, hd]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _kernel(r_ref, k_ref, v_ref, la_ref, u_ref, y_ref, sf_ref, state_ref, *,
            n_chunks: int, chunk: int):
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)          # [Q, hd]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    la = la_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # [hd]

    cs = jnp.cumsum(la, axis=0)               # inclusive [Q, hd]
    ri = r * jnp.exp(cs - la)                 # decay to state BEFORE token i
    kj = k * jnp.exp(-cs)
    att = jax.lax.dot_general(ri, kj, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [Qi, Qj]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(cols < rows, att, 0.0)    # strictly causal
    y = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # diagonal bonus: y_i += (r_i . (u * k_i)) v_i
    diag = jnp.sum(r * u[None, :] * k, axis=-1)
    y = y + diag[:, None] * v
    # inter-chunk: y_i += (r_i * exp(cs_i - la_i)) . S_prev
    s_prev = state_ref[...]
    y = y + jax.lax.dot_general(ri, s_prev, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # state: S = diag(exp(cs_last)) S_prev + sum_j exp(cs_last - cs_j) k_j v_j^T
    kst = k * jnp.exp(cs[-1][None, :] - cs)   # [Q, hd]
    s_new = jnp.exp(cs[-1])[:, None] * s_prev + jax.lax.dot_general(
        kst, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_ref[...] = s_new

    @pl.when(cj == n_chunks - 1)
    def _final():
        sf_ref[0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, la, u, *, chunk: int = DEFAULT_CHUNK,
               interpret: bool = False):
    BH, S, hd = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    kernel = functools.partial(_kernel, n_chunks=nc, chunk=chunk)
    seq_spec = pl.BlockSpec((1, chunk, hd), lambda b, j: (b, j, 0))
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, hd), lambda b, j: (b, 0))],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, hd, hd), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), r.dtype),
            jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, la, u)
