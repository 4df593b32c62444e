"""A plain float32 forward pass of the dense Qwen3 decoder.

RMSNorm (eps from the configuration), per-head RMSNorm on q and k, RoPE
with theta from the configuration on the two halves of each head, grouped
query attention with a causal mask, SwiGLU, and a tied or untied head.
Every matmul runs at ``jax.default_matmul_precision("highest")``. It
shares no code with the program: the weights are drawn again from the
seed, layer by layer, by ``chipbench.weights``, and the sequences run one
at a time through each layer so that 8B widths fit beside nothing else.

``quant`` gives the benchmark's control: the same pass with both operands
of every linear layer (projections, MLP and head) rounded to ``"fp8"``
(float8_e4m3fn) or ``"int8"`` (a symmetric grid), scaled per row of the
activations and per output column of the weights, accumulating in
float32. Attention scores and norms stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights

HEAD_ROWS = 512      # rows of hidden states put through the head at once


def _round(x, axis, quant: str):
    """x on the ``quant`` grid, scaled by its largest value along
    ``axis``; float32 again."""
    top = 448.0 if quant == "fp8" else 127.0
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top,
                        1e-30)
    if quant == "fp8":
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    elif quant == "int8":
        q = jnp.round(x / scale)
    else:
        raise ValueError(quant)
    return q * scale


def linear(x, w, quant):
    """x [..., din] @ w [din, dout] in float32, on the ``quant`` grid
    unless ``quant`` is None."""
    if quant:
        x, w = _round(x, -1, quant), _round(w, 0, quant)
    return x @ w


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [S, H, hd]: rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_one(cfg: dict, w: dict, x, quant):
    """One decoder layer over one sequence x [S, d]."""
    S = x.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    pos = jnp.arange(S)
    a = w["attn"]
    h = rms_norm(x, w["ln1"]["scale"], eps)
    q = linear(h, a["wq"], quant).reshape(S, nh, hd)
    k = linear(h, a["wk"], quant).reshape(S, nkv, hd)
    v = linear(h, a["wv"], quant).reshape(S, nkv, hd)
    q = rope(rms_norm(q, a["q_norm"], eps), pos, theta)
    k = rope(rms_norm(k, a["k_norm"], eps), pos, theta)
    qg = q.reshape(S, nkv, nh // nkv, hd)
    s = jnp.einsum("skgh,tkh->kgst", qg, k) / np.sqrt(hd)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    o = jnp.einsum("kgst,tkh->skgh", jax.nn.softmax(s, axis=-1), v)
    x = x + linear(o.reshape(S, nh * hd), a["wo"], quant)
    m = w["mlp"]
    h = rms_norm(x, w["ln2"]["scale"], eps)
    g = linear(h, m["gate"], quant)
    return x + linear(jax.nn.silu(g) * linear(h, m["up"], quant), m["down"],
                      quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(cfg_items, w, x, quant):
    cfg = dict(cfg_items)
    return jax.lax.map(lambda xs: _layer_one(cfg, w, xs, quant), x)


@functools.partial(jax.jit, static_argnums=(0,))
def _final(cfg_items, scale, x):
    return rms_norm(x, scale, dict(cfg_items)["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(3,))
def _head_gap(h, w, tokens, quant):
    """Rows h [R, d] through the head w [d, V]: the gap of ``tokens``
    below the row's best logit, and the row's first token."""
    logits = linear(h, w, quant)
    best = jnp.max(logits, -1)
    at = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    return best - at, jnp.argmax(logits, -1).astype(jnp.int32)


def _items(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def hidden(cfg: dict, seed: int, tokens: np.ndarray, quant=None):
    """Final normed hidden states [K, S, d] of token rows [K, S]."""
    items = _items(cfg)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights.top_f32(cfg, seed, "tok"),
                     jnp.asarray(tokens), axis=0)
        for layer in range(cfg["num_hidden_layers"]):
            x = _layer(items, weights.layer_f32(cfg, seed, layer), x, quant)
        return _final(items, weights.top_f32(cfg, seed, "final_norm"), x)


def head_weight(cfg: dict, seed: int):
    if cfg["tie_word_embeddings"]:
        return weights.top_f32(cfg, seed, "tok").T
    return weights.top_f32(cfg, seed, "head")


def head_gap(h, w, tokens, quant=None):
    """:func:`_head_gap` over any number of rows, ``HEAD_ROWS`` at once
    (the last block padded), as numpy arrays."""
    n = h.shape[0]
    pad = -n % HEAD_ROWS
    h = jnp.pad(h, ((0, pad), (0, 0)))
    tokens = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, pad))
    gaps, firsts = [], []
    with jax.default_matmul_precision("highest"):
        for i in range(0, n + pad, HEAD_ROWS):
            g, f = _head_gap(h[i:i + HEAD_ROWS], w,
                             tokens[i:i + HEAD_ROWS], quant)
            gaps.append(np.asarray(g))
            firsts.append(np.asarray(f))
    return np.concatenate(gaps)[:n], np.concatenate(firsts)[:n]
