"""Paper Fig 2: accuracy-curve calibration quality.

We regenerate noisy samples from the Table I curves (the paper's raw
measurements are not published) and verify the calibration pipeline
recovers curves that match pointwise."""
from __future__ import annotations

import numpy as np

from repro import compile_cache
from repro.core import paper_tasks
from repro.core.calibration import calibrate_taskset

from .common import emit


def main() -> None:
    tasks = paper_tasks()
    budgets = np.array([0, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096,
                        8192, 16384])
    rng = np.random.default_rng(0)
    A, b, D = (np.asarray(t) for t in (tasks.A, tasks.b, tasks.D))
    t0, c = np.asarray(tasks.t0), np.asarray(tasks.c)
    acc = A[:, None] * (1 - np.exp(-b[:, None] * budgets[None])) + D[:, None]
    acc_noisy = np.clip(acc + rng.normal(0, 0.01, acc.shape), 0, 1)
    lat = t0[:, None] + c[:, None] * budgets[None]
    lat_noisy = lat * (1 + rng.normal(0, 0.01, lat.shape))
    refit = calibrate_taskset(tasks.names, budgets, acc_noisy, lat_noisy)
    rA, rb, rD = (np.asarray(t) for t in (refit.A, refit.b, refit.D))
    racc = rA[:, None] * (1 - np.exp(-rb[:, None] * budgets[None])) + rD[:, None]
    for i, n in enumerate(tasks.names):
        rmse = float(np.sqrt(np.mean((racc[i] - acc[i]) ** 2)))
        emit(f"fig2.curve_rmse.{n}", f"{rmse:.4f}",
             f"b_true={b[i]:.2e},b_fit={rb[i]:.2e}")
    lat_err = float(np.max(np.abs(np.asarray(refit.c) - c) / c))
    emit("fig2.latency_c_max_rel_err", f"{lat_err:.4f}", "")


if __name__ == "__main__":
    compile_cache.enable()
    main()
