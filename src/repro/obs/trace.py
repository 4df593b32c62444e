"""Per-request span recording with a Chrome trace-event / Perfetto exporter.

The serving engine, replay harness, and benchmarks record *spans* — named
intervals with microsecond timestamps — onto a :class:`Tracer`, which
exports the standard Chrome trace-event JSON (``chrome://tracing`` /
https://ui.perfetto.dev). Two clocks coexist in one trace as separate
processes:

* ``pid=VIRTUAL_PID`` — the simulated queueing timeline (arrival / admit /
  prefill / decode / retire per request, re-solve instants). Timestamps
  are the *model's* seconds, passed explicitly by the producer.
* ``pid=WALL_PID`` — the monotonic wall clock (jit dispatches, decode
  chunks, controller re-solves), recorded by :meth:`Tracer.span` around
  real work.

One span, two sinks: every event stamped on the wall clock
(:meth:`Tracer.span`, and :meth:`Tracer.counter` / :meth:`Tracer.instant`
with ``ts_s=None``) also enters ``jax.profiler.TraceAnnotation`` under its
name, with its args (a counter's values) as stats. While a profile runs
(``jax.profiler.start_trace``), the event therefore lands in the
profiler's host plane on the device trace's clock, where a reduction can
set it against what the device did; with no profile running it costs
about a microsecond. Virtual-clock events (:meth:`Tracer.complete`, an
explicit ``ts_s``) lie on another timeline and stay in the Chrome JSON
only. ``jax.profiler`` is imported on a tracer's first wall event, so this
module imports without JAX.

Every event that belongs to a request carries ``args={"rid": ...}`` so the
span tree can be validated programmatically (:func:`spans_by_request`,
:func:`validate_request_trees`) — the acceptance contract is that a replay
run's trace covers admit -> prefill -> decode -> retire for every
completed request.

Disabled-path cost contract: producers hold ``tracer=None`` (or
:data:`NULL_TRACER`) by default and guard every recording site with a
single ``is not None`` / ``tracer.enabled`` check, so a run without
observability pays one pointer comparison per would-be event and allocates
nothing. :class:`NullTracer` additionally makes every method a no-op so
unconditional call sites stay safe; it never annotates the profiler.

This module also owns the ONE wall-clock timing helper
(:func:`timecall`) shared by ``serving.server.LLMServer`` and
``serving.replay.ReplayHarness``: both measure engine service time on the
same monotonic clock (``time.perf_counter``) with the same warmup-
exclusion semantics (``warmup`` untimed calls first, so jit compilation is
never billed to a request's service time).
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager, nullcontext

__all__ = ["Tracer", "NullTracer", "NULL_TRACER", "VIRTUAL_PID", "WALL_PID",
           "monotonic", "timecall", "spans_by_request",
           "validate_request_trees"]

VIRTUAL_PID = 1     # simulated queueing timeline (model seconds)
WALL_PID = 2        # monotonic wall clock (engine dispatches, re-solves)

_PID_NAMES = {VIRTUAL_PID: "queueing timeline (virtual clock)",
              WALL_PID: "engine (wall clock)"}


def monotonic() -> float:
    """The repo's single monotonic wall clock (seconds)."""
    return time.perf_counter()


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation``, or a no-op stand-in where JAX is
    not installed."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return lambda name, **args: nullcontext()
    return TraceAnnotation


def timecall(fn, *args, warmup: int = 0, **kwargs):
    """Run ``fn(*args, **kwargs)`` and return ``(result, seconds)``.

    The shared service-timing helper: a monotonic clock
    (``time.perf_counter``) and explicit warmup exclusion — ``warmup``
    untimed calls run first so one-time costs (jit compilation, cache
    population) never contaminate the measured call. ``LLMServer`` (wall
    mode) and ``ReplayHarness.run_engine`` both measure through this, so
    the real-engine twin and the serving benches share identical timing
    semantics.
    """
    for _ in range(max(int(warmup), 0)):
        fn(*args, **kwargs)
    t0 = monotonic()
    out = fn(*args, **kwargs)
    return out, monotonic() - t0


class Tracer:
    """Append-only event recorder exporting Chrome trace-event JSON.

    Virtual-timeline producers pass explicit ``ts_s`` (seconds on the
    simulated clock); wall producers use the :meth:`span` context manager
    (monotonic clock anchored at tracer construction). Timestamps are
    stored in microseconds, the trace-event unit. Wall events also enter
    the profiler's host plane (module docstring).
    """

    enabled = True

    def __init__(self):
        self._events: list = []
        self._wall0 = monotonic()
        self._named_pids: set = set()
        self._annotation = None     # jax.profiler.TraceAnnotation, lazily

    # ------------------------------------------------------------- plumbing
    def __len__(self) -> int:
        return len(self._events)

    def _wall_us(self) -> float:
        return (monotonic() - self._wall0) * 1e6

    def _name_pid(self, pid: int) -> None:
        if pid not in self._named_pids and pid in _PID_NAMES:
            self._named_pids.add(pid)
            self._events.append({"ph": "M", "name": "process_name",
                                 "pid": pid, "tid": 0,
                                 "args": {"name": _PID_NAMES[pid]}})

    def _push(self, ev: dict) -> None:
        self._name_pid(ev.get("pid", VIRTUAL_PID))
        self._events.append(ev)

    def _annotate(self, name: str, args):
        """The profiler's host span for a wall event, its args as stats."""
        if self._annotation is None:
            self._annotation = _profiler_annotation()
        return self._annotation(name, **(args or {}))

    # ------------------------------------------------------------ recording
    def complete(self, name: str, ts_s: float, dur_s: float, *, tid: int = 0,
                 pid: int = VIRTUAL_PID, cat: str = "", args=None) -> None:
        """A complete ("X") span: ``[ts_s, ts_s + dur_s]`` in seconds."""
        ev = {"ph": "X", "name": name, "pid": pid, "tid": tid,
              "ts": ts_s * 1e6, "dur": max(dur_s, 0.0) * 1e6}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = dict(args)
        self._push(ev)

    def instant(self, name: str, ts_s: float | None = None, *, tid: int = 0,
                pid: int = VIRTUAL_PID, cat: str = "", args=None) -> None:
        """An instant ("i") event; ``ts_s=None`` stamps the wall clock
        (and the profiler's host plane)."""
        if ts_s is None:
            with self._annotate(name, args):
                pass
        ts = self._wall_us() if ts_s is None else ts_s * 1e6
        ev = {"ph": "i", "name": name, "pid": pid, "tid": tid, "ts": ts,
              "s": "t"}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = dict(args)
        self._push(ev)

    def counter(self, name: str, ts_s: float | None = None, *, tid: int = 0,
                pid: int = VIRTUAL_PID, **values) -> None:
        """A counter ("C") sample rendered as a stacked track; ``ts_s=None``
        stamps the wall clock (and the profiler's host plane, with the
        values as stats)."""
        if ts_s is None:
            with self._annotate(name, values):
                pass
        ts = self._wall_us() if ts_s is None else ts_s * 1e6
        self._push({"ph": "C", "name": name, "pid": pid, "tid": tid,
                    "ts": ts, "args": {k: float(v)
                                       for k, v in values.items()}})

    @contextmanager
    def span(self, name: str, *, tid: int = 0, pid: int = WALL_PID,
             cat: str = "", args=None):
        """Wall-clock span around real work (engine dispatch, re-solve),
        also a span of the profiler's host plane."""
        t0 = self._wall_us()
        try:
            with self._annotate(name, args):
                yield self
        finally:
            ev = {"ph": "X", "name": name, "pid": pid, "tid": tid,
                  "ts": t0, "dur": self._wall_us() - t0}
            if cat:
                ev["cat"] = cat
            if args:
                ev["args"] = dict(args)
            self._push(ev)

    # ------------------------------------------------------------- exporting
    def to_chrome(self) -> dict:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        return {"traceEvents": list(self._events),
                "displayTimeUnit": "ms"}

    def dump(self, path: str) -> str:
        """Write the trace JSON to ``path``; returns the path."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


class NullTracer(Tracer):
    """No-op tracer: every recording method returns immediately.

    Producers that cannot hold ``None`` (unconditional call sites) use
    :data:`NULL_TRACER`; the cost per would-be event is one attribute
    lookup and an empty method call — no allocation, no list growth.
    """

    enabled = False

    def __init__(self):
        super().__init__()

    def complete(self, *a, **k):
        pass

    def instant(self, *a, **k):
        pass

    def counter(self, *a, **k):
        pass

    @contextmanager
    def span(self, *a, **k):
        yield self

    def _push(self, ev):
        pass


NULL_TRACER = NullTracer()


# --------------------------------------------------------------------------
# Trace validation (the acceptance contract of the replay exporter)
# --------------------------------------------------------------------------

def spans_by_request(trace: dict) -> dict:
    """Index a Chrome trace by request id.

    Returns ``{rid: {name: (ts_us, dur_us)}}`` over all "X" events whose
    ``args`` carry a ``rid``, plus instants as ``(ts_us, 0.0)``.
    """
    out: dict = {}
    for ev in trace.get("traceEvents", []):
        rid = (ev.get("args") or {}).get("rid")
        if rid is None or ev.get("ph") not in ("X", "i"):
            continue
        out.setdefault(rid, {})[ev["name"]] = (
            float(ev["ts"]), float(ev.get("dur", 0.0)))
    return out


def validate_request_trees(trace: dict, rids, *,
                           phases=("request", "admit", "prefill", "decode",
                                   "retire"), tol_us: float = 1.0) -> dict:
    """Assert every request's span tree covers admit -> prefill -> decode
    -> retire inside its enclosing ``request`` span.

    Checks, per rid: all ``phases`` present; the child phases tile the
    ``request`` interval in order (each child starts where the previous
    ended, within ``tol_us``); ``retire`` sits at the request's end.
    Returns ``{"n_requests": ..., "n_events": ...}`` on success, raises
    ``AssertionError`` naming the first offending request otherwise.
    """
    idx = spans_by_request(trace)
    rids = list(rids)
    seq = [p for p in phases if p not in ("request", "retire")]
    for rid in rids:
        spans = idx.get(rid)
        assert spans is not None, f"request {rid}: no spans in trace"
        missing = [p for p in phases if p not in spans]
        assert not missing, f"request {rid}: missing phases {missing}"
        ts0, dur = spans["request"]
        cursor = ts0
        for name in seq:
            ts, d = spans[name]
            assert abs(ts - cursor) <= tol_us, (
                f"request {rid}: {name} starts at {ts}, expected {cursor}")
            cursor = ts + d
        assert abs(cursor - (ts0 + dur)) <= tol_us, (
            f"request {rid}: phases end at {cursor}, request ends at "
            f"{ts0 + dur}")
        rt, _ = spans["retire"]
        assert abs(rt - (ts0 + dur)) <= tol_us, (
            f"request {rid}: retire at {rt}, request ends at {ts0 + dur}")
    return {"n_requests": len(rids),
            "n_events": len(trace.get("traceEvents", []))}
