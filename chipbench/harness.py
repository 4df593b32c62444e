"""One run of one cell: set-up, the measured window, the check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, ``traffic/<mix>.json``, ``cells/<cell>.json`` and
a reader in ``metrics/`` for each metric the cell reports.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import check, loop, model, traffic
from .peaks import peaks as peak_table

ROOT = Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache: inside the checkout, at a fixed path
CACHE = ROOT / ".jax_cache"
# the paged decode kernel's op name in a TPU trace: its jitted function's
KERNEL = "paged_decode_attention"


@dataclasses.dataclass
class Spec:
    name: str
    chips: int
    cfg: dict
    mix: dict
    cell: dict
    end_to_end: list        # metric entries of BENCHMARK.json
    per_layer: list
    root: Path = ROOT


@dataclasses.dataclass
class RunData:
    """What the metric readers see."""
    cfg: dict
    loop: loop.OpenLoop
    setup_s: float
    window_compiles: int
    trace: dict
    peaks: dict


def info(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_spec(name: str, root: Path = ROOT) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    here = root / "chipbench"
    return Spec(
        name=name, chips=int(w["chips"]),
        cfg=json.loads((root / conf["file"]).read_text()),
        mix=json.loads((here / "traffic" / f"{w['traffic']}.json")
                       .read_text()),
        cell=json.loads((here / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def reader(metric: str):
    """``metrics/<metric>.py``, else ``metrics/<quantity>.py`` for
    ``<quantity>.<split>``."""
    for mod in (metric, metric.split(".")[0]):
        if (Path(__file__).parent / "metrics" / f"{mod}.py").exists():
            return importlib.import_module(f"{__package__}.metrics.{mod}")
    raise SystemExit(f"no reader for metric {metric!r}")


# ---------------------------------------------------------------- set-up
def warm(engine, spec: Spec) -> None:
    """Compile, or load from the cache, every prefill shape the cell's
    traffic can admit at (group sizes 1, 2, 4 .. ``admit_cap`` at the
    prompt widths, single rows at the wider widths of steady-state
    prefixes) and the decode chunk: dummy rows that emit one token and
    retire at the next ``step_chunk``."""
    mix, cell = spec.mix, spec.cell
    cap = loop.pow2_floor(cell["admit_cap"])
    shapes = []
    for width in traffic.prefill_widths(mix, cell, spec.root):
        ks = ([1 << i for i in range(cap.bit_length())]
              if width <= mix["prompt_len"][1] else [1])
        shapes += [(k, width) for k in ks]
    rid = -1
    bs = engine.block_size
    for k, width in shapes:
        need = k * loop.blocks_needed(width, 1, bs)
        if (engine.n_active + k > engine.max_slots
                or engine.allocator.reserved + need > engine.n_blocks):
            engine.step_chunk()
        reqs = []
        for _ in range(k):
            reqs.append((rid, np.zeros(width, np.int32), 0, 1))
            rid -= 1
        if not all(engine.admit_many(reqs)):
            raise RuntimeError(f"warm-up could not admit {k} x {width}")
    engine.step_chunk()
    if engine.n_active:
        raise RuntimeError("warm-up rows did not retire")


class _Profiler:
    """Starts and stops ``jax.profiler`` at chunk boundaries of the
    window, ``start_s`` after it opens, for ``seconds``."""

    def __init__(self, start_s: float, seconds: float):
        self.start_s, self.seconds = start_s, seconds
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.t0 = None
        self.done = False

    def __call__(self, lp, now: float) -> None:
        import jax
        if self.done:
            return
        if self.t0 is None and now >= lp.t_open + self.start_s:
            jax.profiler.start_trace(self.dir)
            self.t0 = now
            lp.tracing = True
        elif self.t0 is not None and now >= self.t0 + self.seconds:
            self.stop(lp)

    def stop(self, lp) -> None:
        import jax
        if self.t0 is not None and not self.done:
            jax.profiler.stop_trace()
            self.done = True
            lp.tracing = False


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# --------------------------------------------------------------- compile
class _Compiles:
    """Backend compiles (host-clock stamps) and persistent-cache writes
    (programs compiled because the cache did not hold them), counted
    from the first :func:`_listen` of the process."""
    stamps: list = []
    misses = 0
    on = False


def _listen() -> None:
    import jax

    if _Compiles.on:
        return
    _Compiles.on = True

    def duration(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            _Compiles.stamps.append(time.perf_counter())

    def event(name, **kw):
        if name == "/jax/compilation_cache/cache_misses":
            _Compiles.misses += 1

    jax.monitoring.register_event_duration_secs_listener(duration)
    jax.monitoring.register_event_listener(event)


def use_cache() -> str:
    """Keep JAX's persistent compilation cache at :data:`CACHE`, every
    program in it."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return str(CACHE)


# ------------------------------------------------------------------- run
def run_cell(spec: Spec, seed: int, seconds: float, trace: bool,
             t_start: float, *, peaks: dict | None = None,
             cache: bool = True, keep_trace=None, controls=(),
             rate=None) -> dict:
    """One run; returns the result line's object (without ``device``),
    with the loop (``loop``, its engine let go) and every reading of the
    check (``gaps``).

    ``peaks`` defaults to the table's entry for the device; a CPU test
    passes its own, with ``cache=False`` to leave the persistent
    compilation cache off. ``keep_trace(plain)`` is handed the loaded
    trace before it is reduced (``record_trace.py``). ``controls`` adds
    the reference on those grids to the check (``calibrate.py``);
    ``rate`` replaces the cell's rate (``sweep.py``)."""
    import jax

    from repro.obs import jax_hooks

    if cache:
        info(phase="cache", dir=use_cache())
    _listen()
    n_comp0, n_miss0 = len(_Compiles.stamps), _Compiles.misses
    if peaks is None:
        peaks = peak_table(jax.devices()[0].device_kind)

    cfg, mix = spec.cfg, spec.mix
    cell = spec.cell if rate is None else dict(spec.cell, rate_per_s=rate)
    engine = model.build_engine(cfg, cell["engine"], seed)
    reqs = traffic.generate(mix, cell, seed, seconds, cfg["vocab_size"],
                            spec.root)
    warm(engine, spec)
    lp = loop.OpenLoop(engine, [r for r in reqs if not r.prime],
                       admit_cap=cell["admit_cap"],
                       annotate=_annotate if trace else None)
    lp.prime([r for r in reqs if r.prime])
    jax.block_until_ready(engine.cache)
    # what set-up made lives on: keep the collector from walking it in
    # the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    info(phase="setup", setup_s=setup_s, requests=len(reqs),
         backend_compiles=len(_Compiles.stamps) - n_comp0,
         compiled=_Compiles.misses - n_miss0)

    prof = None
    if trace:
        tc = cell["trace"]
        prof = _Profiler(min(tc["start_s"], seconds / 3),
                         min(tc["seconds"], seconds / 3))
    counts0, n_comp1 = jax_hooks.trace_counts(), len(_Compiles.stamps)
    follow = mix.get("drain_s")
    try:
        lp.run(seconds, follow_s=follow, on_boundary=prof)
    finally:
        if prof is not None:
            prof.stop(lp)
    counts1 = jax_hooks.trace_counts()
    window_compiles = sum(counts1.get(k, 0) - counts0.get(k, 0)
                          for k in counts1)
    chunks = [c for c in lp.chunks if lp.t_open <= c.t0 < lp.t_close]
    info(phase="window", chunks=len(lp.chunks),
         chunk_ms_mean=(sum(c.t1 - c.t0 for c in chunks) * 1e3
                        / max(len(chunks), 1)),
         admissions=len(lp.admissions), window_compiles=window_compiles,
         backend_compiles_in_window=len(_Compiles.stamps) - n_comp1,
         drain_s=max(0.0, time.perf_counter() - lp.t_close))

    reduced = {}
    if prof is not None:
        from . import trace_reduce
        try:
            if prof.done:
                plain = trace_reduce.load(prof.dir)
                if keep_trace is not None:
                    keep_trace(plain)
                reduced = trace_reduce.reduce(plain, kernel=KERNEL)
        finally:
            shutil.rmtree(prof.dir, ignore_errors=True)
        info(phase="trace", **reduced)

    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.local_devices())
    run = RunData(cfg=cfg, loop=lp, setup_s=setup_s,
                  window_compiles=window_compiles, trace=reduced,
                  peaks=peaks)
    entries = spec.per_layer if trace else spec.end_to_end
    metrics = {}
    for m in entries:
        v = reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    if follow is not None:
        due = [r for r in lp.records.values()
               if lp.t_open <= r.due < lp.t_close]
        attempted, failed = len(due), sum(not r.finished for r in due)
    else:
        worked = [r for r in lp.records.values() if r.n_tokens]
        attempted, failed = len(worked), 0

    # the check runs with the program's state freed
    gc.unfreeze()
    records = list(lp.records.values())
    prompts = {r.rid: r.prompt for r in reqs}
    lp.engine = None
    del engine, run
    gc.collect()
    ck = cell["check"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
    samples = check.sample(records, ck["requests"], rng)
    gaps = check.logit_gaps(cfg, seed, samples, prompts,
                            check.width_for(cell["engine"]["capacity"]),
                            ck["requests"], controls=controls) \
        if samples else None
    checked = {name: {"value": gaps["program"][name] if gaps else None,
                      "limit": limit}
               for name, limit in ck["limits"].items()}
    wrong = sum(r.finished and len(r.tokens) != r.n_out for r in records)
    checked["token_count_wrong"] = {"value": wrong, "limit": 0}
    correct = all(v["value"] is not None and v["value"] <= v["limit"]
                  for v in checked.values())
    info(phase="check", samples=len(samples),
         positions=gaps["positions"] if gaps else 0)
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "memory_peak_bytes": int(memory_peak),
            "trace": reduced, "checked": checked, "gaps": gaps, "loop": lp}
