"""The system under test, built from a configuration file and a cell's
engine shape: the paged ``ContinuousBatchingEngine`` of ``repro.serving``
holding a Qwen3-style dense decoder with weights from the seed."""
from __future__ import annotations

import jax

from . import weights


def model_config(cfg: dict):
    """``repro``'s ModelConfig for a configuration file (HF key names)."""
    from repro.models import ModelConfig

    if cfg["torch_dtype"] != "bfloat16" or cfg["hidden_act"] != "silu":
        raise ValueError("only bfloat16 SwiGLU decoders are wired up")
    return ModelConfig(
        arch_id=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qk_norm=True, rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], dtype="bfloat16",
        source=cfg["source"])


def build_engine(cfg: dict, engine: dict, seed: int):
    """The engine the window drives, with the Pallas paged decode kernel
    on the TPU (the gather reference elsewhere: the interpreted kernel is
    far too slow for the CPU tests)."""
    from repro.serving import ContinuousBatchingEngine

    params = weights.model_params(cfg, seed)
    return ContinuousBatchingEngine(
        model_config(cfg), params, max_slots=engine["rows"],
        capacity=engine["capacity"], chunk=engine["chunk"], paged=True,
        block_size=engine["block_size"], n_blocks=engine["pool_blocks"],
        use_decode_kernel=jax.default_backend() == "tpu")
