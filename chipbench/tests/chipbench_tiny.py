"""Tiny stand-ins for the chip cells, for the CPU tests."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"name": "tiny", "source": "test", "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "tie_word_embeddings": True,
        "rope_theta": 1000000, "rms_norm_eps": 1e-06, "hidden_act": "silu",
        "torch_dtype": "bfloat16"}
# the widths the CPU tests of the reference use: larger than TINY so that
# bf16 and float32 differ by rounding, not by the model's size
SMALL = dict(TINY, name="small", hidden_size=128, intermediate_size=256,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             vocab_size=1024, tie_word_embeddings=False)
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def data(kind: str, name: str) -> dict:
    """``chipbench/<kind>/<name>.json``."""
    import json
    return json.loads((ROOT / "chipbench" / kind / f"{name}.json")
                      .read_text())


def table1_spec(rate: float = 4.0, limit: float = 0.5, cfg=TINY,
                mean_limit=None):
    from chipbench import harness

    base = harness.load_spec("qwen3-0.6b.table1")
    limits = {"logit_gap": limit}
    if mean_limit is not None:
        limits["logit_gap_mean"] = mean_limit
    mix = dict(base.mix, prompt_len=[16, 32], budgets=[0, 40, 0, 0, 44, 10])
    cell = {"engine": {"rows": 8, "capacity": 96, "pool_blocks": 48,
                       "block_size": 16, "chunk": 8},
            "rate_per_s": rate, "admit_cap": 2,
            "check": {"requests": 4, "limits": limits},
            "trace": {"start_s": 0.5, "seconds": 1.0}}
    return harness.Spec("tiny.table1", 1, cfg, mix, cell, base.end_to_end,
                        base.per_layer)
