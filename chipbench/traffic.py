"""One generator for every traffic mix; a mix is a data file.

``traffic/<mix>.json`` gives the mix's shape and the cell file gives its
fixed rate or backlog. The mix's ``arrivals`` names the arrival process:
``arrivals/<name>.py``, found by name, whose ``generate(mix, cell, seed,
seconds, vocab)`` returns the run's requests. A new process is a new file
there; a new mix of a known process is a new data file alone.

A mix with ``drain_s`` is followed: every request due in the window is
served to completion, for at most ``drain_s`` seconds after the window
closes, and one that does not finish counts as failed. A mix without it
stops when the window closes (a backlog deeper than a window drains).

Every process draws its sizes stratified: every seed gets the same
multiset of gaps, task types and lengths in another order, so the seed
moves the order of the work and not its amount. The logic of the arrival,
type and prompt draws follows
``repro.queueing_sim.workload.generate_stream``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Request:
    rid: int
    due: float            # seconds after the window opens
    prompt: np.ndarray    # int32 token ids
    budget: int           # thinking tokens still to serve
    answer: int           # answer tokens after the budget
    task: int = 0
    done: int = 0         # thinking tokens already in the prompt
    prime: bool = False   # admitted in set-up, before the window opens

    @property
    def n_out(self) -> int:
        """Tokens the engine emits for this request."""
        return self.budget + self.answer


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def shares(weights, n: int, rng) -> np.ndarray:
    """``n`` type labels in exact proportion to ``weights`` (largest
    remainders), in the seed's order."""
    w = np.asarray(weights, float) / np.sum(weights)
    counts = np.floor(w * n).astype(int)
    rest = np.argsort(-(w * n - counts), kind="stable")[:n - counts.sum()]
    counts[rest] += 1
    return rng.permutation(np.repeat(np.arange(len(w)), counts))


def prompts(mix: dict, n: int, rng, vocab: int) -> list:
    """``n`` prompts of random ids, lengths the uniform quantiles of
    ``prompt_len``, in the seed's order."""
    lo, hi = mix["prompt_len"]
    lens = lo + np.floor(quantiles(n) * (hi - lo + 1)).astype(int)
    lens = rng.permutation(lens)
    return [rng.integers(0, vocab, size=int(m), dtype=np.int32)
            for m in lens]


def process(name: str, root: Path = ROOT):
    """The arrival process ``<root>/chipbench/arrivals/<name>.py``."""
    path = root / "chipbench" / "arrivals" / f"{name}.py"
    if not path.exists():
        raise SystemExit(f"no arrival process {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_arrivals_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate(mix: dict, cell: dict, seed: int, seconds: float,
             vocab: int, root: Path = ROOT) -> list:
    """The requests of one run, in order of their due times."""
    return process(mix["arrivals"], root).generate(mix, cell, seed, seconds,
                                                   vocab)


def widest_prompt(mix: dict, root: Path = ROOT) -> int:
    """The longest prompt a request of the mix can bring: the process's
    ``widest_prompt(mix)`` where it defines one, else ``prompt_len``'s."""
    mod = process(mix["arrivals"], root)
    if hasattr(mod, "widest_prompt"):
        return int(mod.widest_prompt(mix))
    return int(mix["prompt_len"][1])


def prefill_widths(mix: dict, cell: dict, root: Path = ROOT) -> list:
    """Every prefill width a request of this mix can be admitted at: the
    engine pads a group to a power-of-two multiple of the block, capped
    at the capacity."""
    eng = cell["engine"]
    longest = widest_prompt(mix, root)
    out, w = [], eng["block_size"]
    while True:
        out.append(min(w, eng["capacity"]))
        if w >= min(longest, eng["capacity"]):
            return out
        w *= 2
