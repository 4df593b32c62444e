"""The generator: deterministic per seed, true to its stated parameters,
and a mix, an arrival process, a cell or a metric added by a file is
found by name; the knee rule gives the recorded sweeps' knees."""
import gzip
import json
import shutil
from collections import Counter

import numpy as np
import pytest

from chipbench_tiny import ROOT, data

from chipbench import harness, sweep, traffic

BIG_SEED = 2**31 + 977
MIXES = {"qwen3-0.6b.table1": "table1",
         "qwen3-0.6b.long_reasoning": "long_reasoning"}


def _spec(cell):
    return harness.Spec(cell, 1, {}, data("traffic", MIXES[cell]),
                        data("cells", cell), [], [])


def _key(reqs):
    return [(r.rid, r.due, r.budget, r.answer, r.done, r.prompt.tolist())
            for r in reqs]


@pytest.mark.parametrize("cell", ["qwen3-0.6b.table1",
                                  "qwen3-0.6b.long_reasoning"])
def test_same_seed_same_requests(cell):
    s = _spec(cell)
    a = traffic.generate(s.mix, s.cell, BIG_SEED, 30.0, 151936)
    b = traffic.generate(s.mix, s.cell, BIG_SEED, 30.0, 151936)
    c = traffic.generate(s.mix, s.cell, BIG_SEED + 1, 30.0, 151936)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    # another seed reorders the same work
    assert sorted(r.n_out + r.done for r in a) == \
        sorted(r.n_out + r.done for r in c)


def test_table1_matches_its_parameters():
    s = _spec("qwen3-0.6b.table1")
    rate, seconds = s.cell["rate_per_s"], 30.0
    reqs = traffic.generate(s.mix, s.cell, BIG_SEED, seconds, 151936)
    n = round(rate * seconds)
    assert len(reqs) == n
    due = np.array([r.due for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0) and due[-1] < seconds
    # mean gap is the rate's; the gaps spread as an exponential's do
    gaps = np.diff(due)
    assert abs(gaps.mean() * rate - 1) < 0.05
    assert 0.85 < gaps.std() / gaps.mean() < 1.1
    types = Counter(r.task for r in reqs)
    assert max(types.values()) - min(types.values()) <= 1
    budgets = s.mix["budgets"]
    assert all(r.budget == budgets[r.task] and r.answer == 8 for r in reqs)
    lens = [len(r.prompt) for r in reqs]
    assert min(lens) >= 16 and max(lens) <= 128
    assert abs(np.mean(lens) - 72) < 2
    assert all(r.prompt.max() < 151936 for r in reqs)


def test_long_reasoning_matches_its_parameters():
    s = _spec("qwen3-0.6b.long_reasoning")
    rows = s.cell["engine"]["rows"]
    reqs = traffic.generate(s.mix, s.cell, BIG_SEED, 30.0, 151936)
    assert len(reqs) == rows + s.cell["backlog"]
    total = np.array([r.budget + r.done for r in reqs])
    assert total.min() >= 1024 and total.max() <= 4096
    # log-uniform: the mean of the log is the middle of the range
    assert abs(np.log(total).mean() - np.log(2048)) < 0.02
    primed, fresh = reqs[:rows], reqs[rows:]
    assert all(r.done == 0 for r in fresh)
    progress = sorted(r.done / (r.budget + r.done) for r in primed)
    assert progress[0] < 0.05 and progress[-1] > 0.95
    for r in primed:
        assert 16 + r.done <= len(r.prompt) <= 128 + r.done
        assert len(r.prompt) + r.n_out - 1 <= s.cell["engine"]["capacity"]
    assert traffic.prefill_widths(s.mix, s.cell)[-1] == \
        s.cell["engine"]["capacity"]


def _copy(tmp_path):
    """A checkout in ``tmp_path``, and the bytes of every file of the
    benchmark's own in it (``BENCHMARK.json`` takes new entries)."""
    src = ROOT / "chipbench"
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(src, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return {p.relative_to(tmp_path): p.read_bytes()
            for p in (tmp_path / "chipbench").rglob("*") if p.is_file()}


def _add_cell(tmp_path, name, mix):
    """Add the mix ``name``, a 0.6B cell of it and its workload entry."""
    here = tmp_path / "chipbench"
    (here / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    cell = json.loads((here / "cells" / "qwen3-0.6b.table1.json")
                      .read_text())
    (here / "cells" / f"qwen3-0.6b.{name}.json").write_text(
        json.dumps(cell))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": f"qwen3-0.6b.{name}",
                               "config": "qwen3-0.6b", "traffic": name,
                               "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.load_spec(f"qwen3-0.6b.{name}", root=tmp_path)


def _unchanged(tmp_path, before):
    for rel, data in before.items():
        assert (tmp_path / rel).read_bytes() == data


def test_a_new_mix_is_found_by_name(tmp_path):
    before = _copy(tmp_path)
    mix = dict(data("traffic", "table1"), prompt_len=[100, 120])
    spec = _add_cell(tmp_path, "table1_longer", mix)
    reqs = traffic.generate(spec.mix, spec.cell, 5, 10.0, 151936,
                            spec.root)
    assert min(len(r.prompt) for r in reqs) >= 100
    _unchanged(tmp_path, before)


BURSTY = '''"""On/off bursts: the cell's rate, scaled by ``on_factor`` in the
first ``on_share`` of every ``period_s`` and silent in the rest."""
import numpy as np

from chipbench.traffic import Request, prompts, quantiles


def generate(mix, cell, seed, seconds, vocab):
    rng = np.random.default_rng(seed)
    on = mix["period_s"] * mix["on_share"]
    n = int(round(cell["rate_per_s"] * seconds))
    spots = rng.permutation(quantiles(n)) * seconds * mix["on_share"]
    due = np.sort(spots // on * mix["period_s"] + spots % on)
    texts = prompts(mix, n, rng, vocab)
    return [Request(rid=i, due=float(due[i]), prompt=texts[i], budget=30,
                    answer=int(mix["answer_tokens"])) for i in range(n)]


def widest_prompt(mix):
    return 256
'''


def test_a_new_arrival_process_is_found_by_name(tmp_path):
    before = _copy(tmp_path)
    (tmp_path / "chipbench" / "arrivals" / "bursty.py").write_text(BURSTY)
    mix = dict(data("traffic", "table1"), arrivals="bursty",
               period_s=2.0, on_share=0.25)
    spec = _add_cell(tmp_path, "table1_bursty", mix)
    reqs = traffic.generate(spec.mix, spec.cell, 5, 10.0, 151936,
                            spec.root)
    assert len(reqs) == 128
    phase = np.array([r.due for r in reqs]) % 2.0
    assert phase.max() < 0.5
    assert traffic.prefill_widths(spec.mix, spec.cell, spec.root) == \
        [16, 32, 64, 128, 256]
    _unchanged(tmp_path, before)
    with pytest.raises(SystemExit, match="no arrival process"):
        traffic.generate(dict(mix, arrivals="missing"), spec.cell, 5, 10.0,
                         151936, spec.root)


def test_followed_mixes_say_so_in_data():
    # a mix is followed to completion where its file gives a drain limit
    assert data("traffic", "table1")["drain_s"] > 0
    assert "drain_s" not in data("traffic", "long_reasoning")


@pytest.mark.parametrize("cell", ["qwen3-0.6b.table1", "qwen3-8b-18l.table1"])
def test_the_knee_rule_gives_the_cells_rates(cell):
    with gzip.open(ROOT / "chipbench" / "data" / "sweeps.json.gz", "rt") as f:
        rows = json.load(f)[cell]["rates"]
    found = sweep.knee(rows)
    c = data("cells", cell)
    for key in ("knee_per_s", "ttft_limit_ms", "tpot_limit_ms"):
        assert found[key] == c["sweep"][key]
    assert c["rate_per_s"] == pytest.approx(0.8 * found["knee_per_s"])


def test_every_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(m["name"]).read)
    for w in bench["workloads"]:
        spec = harness.load_spec(w["name"])
        assert any(m["name"] == "setup_s" for m in spec.end_to_end)
        assert len(spec.end_to_end) >= 2 and spec.per_layer
