"""Open-loop Poisson arrivals over the window at the cell's
``rate_per_s``: the gaps are the exponential distribution's quantiles at
``(i + 1/2) / n`` in the seed's order, the task types the weights' exact
shares, the prompt lengths the uniform quantiles of ``prompt_len``."""
import numpy as np

from chipbench.traffic import Request, prompts, quantiles, shares


def generate(mix: dict, cell: dict, seed: int, seconds: float,
             vocab: int) -> list:
    rng = np.random.default_rng(seed)
    rate = float(cell["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = rng.permutation(-np.log1p(-quantiles(n)) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due *= seconds / gaps.sum()          # n arrivals inside the window
    types = shares(mix["task_weights"], n, rng)
    texts = prompts(mix, n, rng, vocab)
    budgets = np.asarray(mix["budgets"])
    return [Request(rid=i, due=float(due[i]), prompt=texts[i],
                    budget=int(budgets[types[i]]),
                    answer=int(mix["answer_tokens"]), task=int(types[i]))
            for i in range(n)]
