"""A queue deeper than the window drains: the engine's ``rows`` plus the
cell's ``backlog``, all due when the window opens, with thinking budgets
log-uniform over ``budget_loguniform``. With ``steady_state`` the first
``rows`` are admitted in set-up part-way through their budgets: a drawn
share of the thinking already sits in the prompt and the row keeps the
rest of its budget, as a row of a saturated server is uniform over its
progress."""
import numpy as np

from chipbench.traffic import Request, prompts, quantiles


def generate(mix: dict, cell: dict, seed: int, seconds: float,
             vocab: int) -> list:
    rng = np.random.default_rng(seed)
    rows = cell["engine"]["rows"]
    n = rows + int(cell["backlog"])
    lo, hi = mix["budget_loguniform"]
    budgets = np.exp(np.log(lo) + quantiles(n) * np.log(hi / lo))
    budgets = rng.permutation(np.round(budgets).astype(int))
    texts = prompts(mix, n, rng, vocab)
    progress = rng.permutation(quantiles(rows))
    steady = bool(mix.get("steady_state"))
    out = []
    for i in range(n):
        prime = steady and i < rows
        done = int(progress[i] * budgets[i]) if prime else 0
        thought = rng.integers(0, vocab, size=done, dtype=np.int32)
        out.append(Request(rid=i, due=0.0,
                           prompt=np.concatenate([texts[i], thought]),
                           budget=int(budgets[i]) - done,
                           answer=int(mix["answer_tokens"]), done=done,
                           prime=prime))
    return out


def widest_prompt(mix: dict) -> int:
    longest = mix["prompt_len"][1]
    if mix.get("steady_state"):
        longest += mix["budget_loguniform"][1]
    return longest
