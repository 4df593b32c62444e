"""Driver smoke tests: serve.py, train.py, and a single dry-run combo —
the deliverable entry points exercised end-to-end inside the suite."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

# children compile on the CPU and keep nothing in the compile cache
ENV = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
           JAX_ENABLE_COMPILATION_CACHE="false")


def _run(args, timeout=540):
    return subprocess.run([sys.executable, "-m"] + args, env=ENV,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=os.getcwd())


def test_serve_driver_virtual():
    proc = _run(["repro.launch.serve", "--queries", "300"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    start = next(i for i, l in enumerate(lines) if l.strip() == "{")
    rep = json.loads("\n".join(lines[start:]))
    assert rep["n"] == 300
    assert rep["mean_system_time"] == pytest.approx(
        rep["pk_predicted_system_time"], rel=0.5)
    assert rep["per_task_budget"]["GSM8K"] > 300


def test_serve_driver_real_engine_reduced():
    """The served path chip_smoke.py runs (LLMServer over the paged
    continuous engine, wall clock), rehearsed on the CPU at reduced
    widths."""
    proc = _run(["repro.launch.serve", "--real-engine", "--reduced",
                 "--queries", "8"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    start = next(i for i, l in enumerate(lines) if l.strip() == "{")
    rep = json.loads("\n".join(lines[start:]))
    assert rep["n"] == 8 and rep["tokens_generated"] > 0
    eng = rep["engine"]
    assert eng["paged"] and eng["dtype"] == "float32"
    assert eng["slots"] == 8 and eng["capacity"] == 1024
    assert eng["device"] == "cpu" and not eng["decode_kernel"]


def test_chip_smoke_refuses_cpu():
    """Without a TPU chip_smoke.py exits non-zero, names the platform it
    found, and prints no verdict."""
    proc = subprocess.run([sys.executable, "chip_smoke.py"], env=ENV,
                          capture_output=True, text=True, timeout=120,
                          cwd=os.getcwd())
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_budgets_are_the_cpu_solve():
    """chip_smoke.py holds the chip's allocator to these budgets."""
    from repro.core import paper_problem
    from repro.core.allocator import solve

    got = solve(paper_problem()).lengths_int
    assert np.array_equal(got, _chip_smoke().CPU_BUDGETS), got


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache is
    the checkout's own .jax_cache."""
    import jax
    from repro import compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    got = compile_cache.enable()
    if env_dir is None:
        want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert got == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        assert got == env_dir and updates == []


def test_train_driver_reduced(tmp_path):
    proc = _run(["repro.launch.train", "--arch", "olmo-1b", "--reduced",
                 "--steps", "8", "--batch", "2", "--seq", "32",
                 "--ckpt", str(tmp_path / "ck"), "--ckpt-every", "4"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "step     7" in proc.stdout or "step 7" in proc.stdout.replace(
        "   ", " ")
    assert (tmp_path / "ck" / "meta.json").exists()


def test_dryrun_driver_single_combo(tmp_path):
    """One real production-mesh combo through the CLI (512 host devices)."""
    proc = _run(["repro.launch.dryrun", "--arch", "qwen3-0.6b",
                 "--shape", "decode_32k", "--mesh", "pod",
                 "--out", str(tmp_path)])
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(
        (tmp_path / "qwen3-0.6b__decode_32k__pod__dryrun.json").read_text())
    assert out["ok"] and out["n_chips"] == 256
    assert out["memory_analysis"]["temp_size_in_bytes"] > 0
