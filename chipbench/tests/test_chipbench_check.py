"""What decides ``correct``: the control fails the limit, sound runs pass
it, and a run whose timed path is broken underneath comes out not
correct. At a tiny size on the CPU, with the harness's look for a chip
skipped; the chip readings at the cells' own sizes are in PERF.md."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import PEAKS, ROOT, SMALL, table1_spec

from chipbench import check, harness, loop, model, peaks, traffic

# the tiny cells' own limits, set like a cell's from readings of SMALL
# widths on the CPU over six seeds (these three, 5, 21, 22): the
# program's widest gap at most 0.037, the fp8 control's at least 0.25;
# the program's mean gap at most 4.5e-4, the int8 control's at least
# 1.0e-3 (its widest gap, 0.044-0.099, is not separated from the
# program's: the mean is what fails int8)
LIMIT = 0.1
MEAN_LIMIT = 6e-4
SEEDS = (2**31 + 3, 11, 12)


def _served(cfg, spec, seed, seconds=2.0):
    engine = model.build_engine(cfg, spec.cell["engine"], seed)
    harness.warm(engine, spec)
    reqs = traffic.generate(spec.mix, spec.cell, seed, seconds,
                            cfg["vocab_size"])
    lp = loop.OpenLoop(engine, reqs, admit_cap=spec.cell["admit_cap"])
    lp.run(seconds, follow_s=60.0)
    return list(lp.records.values()), {r.rid: r.prompt for r in reqs}


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_where_the_program_passes(seed):
    spec = table1_spec(rate=6.0, limit=LIMIT, cfg=SMALL)
    records, prompts = _served(SMALL, spec, seed)
    rng = np.random.default_rng(seed)
    samples = check.sample(records, 4, rng)
    assert samples[0].n_tokens == max(r.n_tokens for r in records)
    gaps = check.logit_gaps(SMALL, seed, samples, prompts, 128, 4,
                            controls=("fp8", "int8"))
    assert gaps["positions"] > 50
    prog, fp8, int8 = (gaps[k] for k in ("program", "control_fp8",
                                         "control_int8"))
    assert prog["logit_gap"] <= LIMIT < fp8["logit_gap"]
    assert prog["logit_gap_mean"] <= MEAN_LIMIT < int8["logit_gap_mean"]
    assert MEAN_LIMIT < fp8["logit_gap_mean"]


def _broken(monkeypatch, fault):
    """Build engines whose fused decode is broken as ``fault`` says."""
    build = model.build_engine

    def broken_build(*a, **k):
        eng = build(*a, **k)
        scan = eng._scan

        def bad_scan(params, token, cache, alive, remaining, keys, gidx,
                     *, chunk):
            if fault == "state_unchanged":
                # the step runs on a copy (the engine donates its cache)
                # and hands the old KV back
                toks, _ = scan(params, token, jax.tree.map(jnp.copy, cache),
                               alive, remaining, keys, gidx, chunk=chunk)
                return toks, cache
            toks, new = scan(params, token, cache, alive, remaining, keys,
                             gidx, chunk=chunk)
            if fault == "token_altered":
                return toks.at[0].set((toks[0] + 1) % eng.cfg.vocab_size), new
            if fault == "half_batch":
                # every other row (slot 0, which every lone request takes,
                # among them) left out of the step
                return toks.at[:, 0::2].set(0), new
            raise ValueError(fault)

        eng._scan = bad_scan
        return eng

    monkeypatch.setattr(model, "build_engine", broken_build)


def _spec():
    return table1_spec(rate=6.0, limit=LIMIT, cfg=SMALL,
                       mean_limit=MEAN_LIMIT)


def _run(spec, seed=5):
    return harness.run_cell(spec, seed, 2.0, False, 0.0, peaks=PEAKS,
                            cache=False)


def test_sound_run_is_correct():
    out = _run(_spec())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 12
    assert list(out["checked"]) == ["logit_gap", "logit_gap_mean",
                                    "token_count_wrong"]
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "system_time_mean_s", "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered",
                                   "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    _broken(monkeypatch, fault)
    out = _run(_spec())
    assert not out["correct"]
    assert out["checked"]["logit_gap"]["value"] > LIMIT


def test_unknown_device_has_no_peaks():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen3-0.6b.table1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "needs 1 TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
