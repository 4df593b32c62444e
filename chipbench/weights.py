"""Random weights from a seed, in the layout the serving engine takes.

Every leaf of every layer is drawn from its own key,
``fold_in(fold_in(seed_key, leaf), layer)``, so one layer can be drawn
alone (the reference does, layer by layer) and equals the same layer of
the stacked tree the engine is given (drawn in one jitted call, in
bfloat16, on the device).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# (group, leaf) of one decoder layer, in a fixed order: the index is the
# leaf's key
LAYER_LEAVES = (("ln1", "scale"), ("attn", "wq"), ("attn", "wk"),
                ("attn", "wv"), ("attn", "wo"), ("attn", "q_norm"),
                ("attn", "k_norm"), ("ln2", "scale"), ("mlp", "gate"),
                ("mlp", "up"), ("mlp", "down"))
_TOP = {"tok": 100, "head": 101, "final_norm": 102}
NORM_SPREAD = 0.1      # norm scales are 1 + NORM_SPREAD * N(0, 1)


def seed_key(seed: int):
    """A key for any whole number seed, 64 bits of it."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _shape(cfg: dict, leaf: str):
    d, dff = cfg["hidden_size"], cfg["intermediate_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return {"scale": (d,), "q_norm": (hd,), "k_norm": (hd,),
            "wq": (d, nh * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
            "wo": (nh * hd, d), "gate": (d, dff), "up": (d, dff),
            "down": (dff, d)}[leaf]


def _draw(key, shape, is_norm: bool, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    w = 1.0 + NORM_SPREAD * z if is_norm else z * shape[0] ** -0.5
    return w.astype(dtype)


def _layer(cfg: dict, key, layer, dtype) -> dict:
    out: dict = {}
    for i, (group, leaf) in enumerate(LAYER_LEAVES):
        k = jax.random.fold_in(jax.random.fold_in(key, i), layer)
        out.setdefault(group, {})[leaf] = _draw(
            k, _shape(cfg, leaf), leaf in ("scale", "q_norm", "k_norm"),
            dtype)
    return out


def _top(cfg: dict, key, name: str, dtype):
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    k = jax.random.fold_in(key, _TOP[name])
    if name == "final_norm":
        return _draw(k, (d,), True, dtype)
    # embedding rows and head columns have fan-in d: logits of unit scale
    shape = (vocab, d) if name == "tok" else (d, vocab)
    return (jax.random.normal(k, shape, jnp.float32) * d ** -0.5).astype(dtype)


@functools.partial(jax.jit, static_argnums=(0,))
def _model_params(cfg_items: tuple, key) -> dict:
    cfg = dict(cfg_items)
    dtype = jnp.bfloat16
    embed = {"tok": _top(cfg, key, "tok", dtype)}
    if not cfg["tie_word_embeddings"]:
        embed["head"] = _top(cfg, key, "head", dtype)
    layers = jnp.arange(cfg["num_hidden_layers"])
    blocks = jax.vmap(lambda l: _layer(cfg, key, l, dtype))(layers)
    return {"embed": embed, "blocks": blocks,
            "final_norm": {"scale": _top(cfg, key, "final_norm", dtype)}}


def _items(cfg: dict) -> tuple:
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "tie_word_embeddings")
    return tuple((k, cfg[k]) for k in keys)


def model_params(cfg: dict, seed: int) -> dict:
    """The engine's whole parameter tree, bf16, drawn on the default
    device in one jitted call."""
    return _model_params(_items(cfg), seed_key(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_f32(cfg_items: tuple, key, layer) -> dict:
    # drawn in bf16, as served, then widened
    tree = _layer(dict(cfg_items), key, layer, jnp.bfloat16)
    return jax.tree.map(lambda t: t.astype(jnp.float32), tree)


def layer_f32(cfg: dict, seed: int, layer: int) -> dict:
    """Layer ``layer`` of :func:`model_params`, widened to float32."""
    return _layer_f32(_items(cfg), seed_key(seed), jnp.int32(layer))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _top_f32(cfg_items: tuple, key, name: str):
    return _top(dict(cfg_items), key, name, jnp.bfloat16).astype(jnp.float32)


def top_f32(cfg: dict, seed: int, name: str):
    """``tok``, ``head`` or ``final_norm`` of :func:`model_params`,
    widened to float32."""
    return _top_f32(_items(cfg), seed_key(seed), name)
