"""Pallas TPU Mamba2 SSD chunked scan.

TPU adaptation of the GPU selective-scan: each grid step processes one
sequence chunk with dense MXU matmuls (decay-masked score matrix), and the
recurrent state [hd, ds] is carried across chunks in VMEM scratch — the
chunk axis is the innermost *sequential* grid dimension. The sequential
dependency is thus S/Q steps instead of S.

Layouts (heads flattened into the batch dim):
    x   [BH, S, hd]   head inputs
    dt  [BH, S]       softplus step sizes (>0)
    a   [BH, S]       log decay = A * dt  (< 0)
    Bm  [BH, S, ds]   input projections
    Cm  [BH, S, ds]   output projections
    y   [BH, S, hd]
    s_final [BH, hd, ds]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, sf_ref, state_ref, *,
            n_chunks: int, chunk: int):
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # [Q, hd]
    dt = dt_ref[0].astype(jnp.float32)        # [Q]
    la = a_ref[0].astype(jnp.float32)         # [Q]
    Bm = b_ref[0].astype(jnp.float32)         # [Q, ds]
    Cm = c_ref[0].astype(jnp.float32)         # [Q, ds]

    cums = jnp.cumsum(la)                     # inclusive [Q]
    # intra-chunk: y_i += sum_{j<=i} exp(cums_i - cums_j) dt_j (C_i.B_j) x_j
    diff = cums[:, None] - cums[None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(cols <= rows, jnp.exp(diff), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    scores = cb * decay * dt[None, :]         # [Qi, Qj]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk: y_i += C_i . (exp(cums_i) * S_prev)
    s_prev = state_ref[...]                   # [hd, ds]
    cin = jnp.exp(cums)[:, None] * Cm         # [Q, ds]
    y = y + jax.lax.dot_general(cin, s_prev, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: S = exp(sum la) S_prev + sum_j exp(cums_Q - cums_j) dt_j x_j B_j^T
    w = dt * jnp.exp(cums[-1] - cums)         # [Q]
    xw = x * w[:, None]                       # [Q, hd]
    s_new = jnp.exp(cums[-1]) * s_prev + jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # [hd, ds]
    state_ref[...] = s_new

    @pl.when(cj == n_chunks - 1)
    def _final():
        sf_ref[0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, Bm, Cm, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool = False):
    BH, S, hd = x.shape
    ds = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    kernel = functools.partial(_kernel, n_chunks=nc, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk), lambda b, j: (b, j)),
            pl.BlockSpec((1, chunk), lambda b, j: (b, j)),
            pl.BlockSpec((1, chunk, ds), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, hd, ds), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), x.dtype),
            jax.ShapeDtypeStruct((BH, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a, Bm, Cm)
