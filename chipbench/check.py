"""What decides ``correct``: the served tokens against the reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the
longest, runs through the float32 reference (``reference/qwen3.py``)
over its prompt and served tokens. The number compared is the widest gap
by which a served token's logit lies below the reference's best logit at
that position: greedy decoding in bfloat16 may pick a near tie, never a
token far down. Beside it, the mean of that gap over every position
checked: a lower precision flips more near ties, and further down, at
every position, so the mean separates the program from a lower precision
where the widest gap of one position does not. A control reads, at the
same positions, the gaps of the tokens that the reference computed on a
lower grid (``fp8``, ``int8``) puts first.
"""
from __future__ import annotations

import math

import numpy as np

from .reference import qwen3


def sample(records: list, k: int, rng) -> list:
    """``k`` finished records: the longest (most tokens, then the lowest
    rid) and ``k - 1`` more drawn by ``rng``."""
    done = sorted((r for r in records if r.finished), key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (r.n_tokens, -r.rid))
    rest = [r for r in done if r is not longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _rows(samples, prompts: dict, width: int, n_rows: int):
    """Token rows [n_rows, width] (prompt then served tokens, zero padded)
    and, per sample, the positions whose logits pick each served token."""
    tokens = np.zeros((n_rows, width), np.int32)
    where = []
    for i, rec in enumerate(samples):
        seq = np.concatenate([prompts[rec.rid],
                              np.asarray(rec.tokens, np.int32)])
        tokens[i, :len(seq)] = seq
        p = len(prompts[rec.rid])
        where.append((i, p - 1, p - 1 + len(rec.tokens)))
    return tokens, where


def readings(gaps: np.ndarray) -> dict:
    """The numbers compared, from the gaps of every position checked."""
    return {"logit_gap": float(gaps.max()),
            "logit_gap_mean": float(gaps.mean())}


def logit_gaps(cfg: dict, seed: int, samples: list, prompts: dict,
               width: int, n_rows: int, controls=()) -> dict:
    """The :func:`readings` of the served tokens (``program``) and of the
    first choices of the reference on each grid of ``controls``
    (``control_<grid>``)."""
    import jax.numpy as jnp

    tokens, where = _rows(samples, prompts, width, n_rows)
    served = np.concatenate([np.asarray(r.tokens, np.int32)
                             for r in samples])
    w = qwen3.head_weight(cfg, seed)

    def rows(h):
        return jnp.concatenate([h[i, a:b] for i, a, b in where])

    h = rows(qwen3.hidden(cfg, seed, tokens))
    gaps, _ = qwen3.head_gap(h, w, served)
    out = {"program": readings(gaps), "positions": int(len(served))}
    for quant in controls:
        hq = rows(qwen3.hidden(cfg, seed, tokens, quant=quant))
        _, firsts = qwen3.head_gap(hq, w, served, quant=quant)
        gaps_c, _ = qwen3.head_gap(h, w, firsts)
        out[f"control_{quant}"] = readings(gaps_c)
    return out


def width_for(capacity: int) -> int:
    """Reference row width: the engine's per-row capacity rounded up to
    128, so that one compiled reference serves every run of a cell."""
    return int(math.ceil(capacity / 128) * 128)
