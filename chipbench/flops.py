"""Operations and bytes the algorithm needs, counted from shapes.

``cfg`` is a configuration file of ``chipbench/configs`` as a dict (the
Hugging Face key names). A decode row-step is one active row advancing
one token; ``kv_tokens`` is the sum, over the row-steps counted, of the
KV positions that step attends (the new token's included). Retired rows
that the engine keeps stepping, and blocks the kernel streams past a
row's live length, are not work the algorithm needs and are not counted.
"""
from __future__ import annotations

BF16 = 2


def _dims(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def layer_params(cfg: dict) -> int:
    """Matmul weights of one decoder layer (norm scales left out)."""
    _, d, nh, nkv, hd, dff, _ = _dims(cfg)
    return d * (nh + 2 * nkv) * hd + nh * hd * d + 3 * d * dff


def matmul_flops_per_token(cfg: dict) -> int:
    """Matmul FLOPs of one token through every layer and the LM head."""
    n_layers, d, *_, vocab = _dims(cfg)
    return 2 * (n_layers * layer_params(cfg) + d * vocab)


def attn_flops(cfg: dict, kv_tokens: int) -> int:
    """QK^T and PV of one query token per row-step, every layer."""
    n_layers, _, nh, _, hd, _, _ = _dims(cfg)
    return n_layers * 4 * nh * hd * kv_tokens


def attn_bytes(cfg: dict, kv_tokens: int, row_steps: int) -> int:
    """HBM bytes of the paged attention kernel: K and V at live length,
    the query read and the output written, bf16, every layer."""
    n_layers, _, nh, nkv, hd, _, _ = _dims(cfg)
    return n_layers * BF16 * (2 * nkv * hd * kv_tokens
                              + 2 * nh * hd * row_steps)


def decode_flops(cfg: dict, row_steps: int, kv_tokens: int) -> int:
    """Model FLOPs of decode steps: matmuls of every active row-step, the
    LM head included, plus attention at live length."""
    return (matmul_flops_per_token(cfg) * row_steps
            + attn_flops(cfg, kv_tokens))
