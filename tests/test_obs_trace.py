"""Chrome trace-event export, span trees, and timing helpers.

Pins the ``obs.trace`` contracts:

* ``to_chrome`` emits the Chrome trace-event / Perfetto schema (complete
  "X" events with microsecond ts/dur, counter "C" events, process-name
  metadata) and ``dump`` round-trips through JSON;
* ``timecall`` returns (result, seconds) on the monotonic clock with
  warmup calls excluded — the single timing helper behind LLMServer wall
  mode and ReplayHarness engine services;
* ``validate_request_trees`` accepts exactly the well-formed span trees
  (admit -> prefill -> decode tiling the request span, retire at its
  end) and names the offender otherwise;
* an instrumented ``LLMServer`` run exports one validated tree per
  completed request, and the ``ServingReport`` percentile fields agree
  with ``np.percentile`` on the report's own samples;
* ``NullTracer`` records nothing;
* wall events (spans, and counters and instants stamped on the wall
  clock) also land in the profiler's host plane under their names, with
  their args as stats, while virtual-clock events, ``NullTracer`` and the
  Chrome export are untouched by a running profile.
"""
import glob
import json

import numpy as np
import pytest

from repro.core import paper_problem
from repro.obs.trace import (NULL_TRACER, VIRTUAL_PID, WALL_PID, NullTracer,
                             Tracer, monotonic, spans_by_request, timecall,
                             validate_request_trees)
from repro.queueing_sim import generate_stream
from repro.serving import LLMServer, ServerConfig


@pytest.fixture(scope="module")
def prob():
    return paper_problem()


# ------------------------------------------------------------------ exporter

def test_to_chrome_schema(tmp_path):
    tr = Tracer()
    tr.complete("work", ts_s=1.0, dur_s=0.5, tid=3, cat="test",
                args={"rid": 7})
    tr.instant("mark", ts_s=1.2)
    tr.counter("depth", ts_s=1.1, queue=4)
    with tr.span("wall-work", cat="host"):
        pass
    doc = tr.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert {"X", "i", "C", "M"} <= phases
    x = next(e for e in evs if e["ph"] == "X" and e["name"] == "work")
    assert x["ts"] == pytest.approx(1.0e6)
    assert x["dur"] == pytest.approx(0.5e6)
    assert x["pid"] == VIRTUAL_PID and x["tid"] == 3
    assert x["args"]["rid"] == 7
    wall = next(e for e in evs if e["name"] == "wall-work")
    assert wall["pid"] == WALL_PID and wall["dur"] >= 0
    c = next(e for e in evs if e["ph"] == "C")
    assert c["args"]["queue"] == 4
    # round-trip through dump
    p = tr.dump(str(tmp_path / "trace.json"))
    assert json.load(open(p)) == doc
    assert len(tr) == len(evs)


def test_null_tracer_records_nothing():
    tr = NullTracer()
    tr.complete("x", ts_s=0.0, dur_s=1.0)
    tr.instant("y")
    tr.counter("z", v=1)
    with tr.span("w"):
        pass
    assert len(tr) == 0
    assert tr.to_chrome()["traceEvents"] == []
    assert not NULL_TRACER.enabled


# ------------------------------------------------------------------- timing

def test_timecall_returns_result_and_seconds():
    out, dt = timecall(lambda a, b: a + b, 2, b=3)
    assert out == 5
    assert dt >= 0.0


def test_timecall_warmup_excluded():
    calls = []

    def fn():
        calls.append(monotonic())
        return len(calls)

    out, dt = timecall(fn, warmup=2)
    assert out == 3            # 2 warmup calls + 1 timed call
    assert dt >= 0.0


# --------------------------------------------------------------- validation

def _well_formed(tr, rid, t0=0.0):
    tr.complete("request", ts_s=t0, dur_s=1.0, args={"rid": rid})
    tr.complete("admit", ts_s=t0, dur_s=0.2, args={"rid": rid})
    tr.complete("prefill", ts_s=t0 + 0.2, dur_s=0.1, args={"rid": rid})
    tr.complete("decode", ts_s=t0 + 0.3, dur_s=0.7, args={"rid": rid})
    tr.instant("retire", ts_s=t0 + 1.0, args={"rid": rid})


def test_validate_request_trees_accepts_well_formed():
    tr = Tracer()
    for rid in range(3):
        _well_formed(tr, rid, t0=float(rid))
    info = validate_request_trees(tr.to_chrome(), range(3))
    assert info["n_requests"] == 3


def test_validate_request_trees_rejects_gap_and_missing():
    tr = Tracer()
    _well_formed(tr, 0)
    tr.complete("request", ts_s=5.0, dur_s=1.0, args={"rid": 1})
    with pytest.raises(AssertionError, match="missing"):
        validate_request_trees(tr.to_chrome(), [0, 1])
    tr2 = Tracer()
    _well_formed(tr2, 0)
    # decode leaves a 0.2 s gap before the request end
    tr2.complete("request", ts_s=10.0, dur_s=1.0, args={"rid": 1})
    tr2.complete("admit", ts_s=10.0, dur_s=0.2, args={"rid": 1})
    tr2.complete("prefill", ts_s=10.2, dur_s=0.1, args={"rid": 1})
    tr2.complete("decode", ts_s=10.3, dur_s=0.5, args={"rid": 1})
    tr2.instant("retire", ts_s=11.0, args={"rid": 1})
    with pytest.raises(AssertionError):
        validate_request_trees(tr2.to_chrome(), [0, 1])


def test_spans_by_request_indexes_by_rid():
    tr = Tracer()
    _well_formed(tr, 42)
    tr.complete("unrelated", ts_s=0.0, dur_s=1.0)  # no rid -> ignored
    idx = spans_by_request(tr.to_chrome())
    assert set(idx) == {42}
    assert set(idx[42]) == {"request", "admit", "prefill", "decode",
                            "retire"}


# ------------------------------------------------- instrumented server run

def test_server_run_exports_validated_trees(prob):
    tr = Tracer()
    stream = generate_stream(prob.tasks, prob.server.lam, 300, seed=5)
    srv = LLMServer(prob, ServerConfig(online_adaptation=False), tracer=tr)
    rep = srv.run(stream)
    n = len(stream.queries)
    info = validate_request_trees(tr.to_chrome(), range(n))
    assert info["n_requests"] == n
    # report percentiles are exact sample percentiles of the server's waits
    waits = np.array([c.wait_time for c in srv.completed])
    for key, q in (("p50", 50.0), ("p90", 90.0), ("p99", 99.0)):
        assert rep.wait_percentiles[key] == pytest.approx(
            float(np.percentile(waits, q, method="inverted_cdf")))
    assert set(rep.system_time_percentiles) == {"p50", "p90", "p99",
                                                "p99_9"}


# ------------------------------------------------ the profiler's host plane

def _record(tr):
    with tr.span("obs_test.span", args={"rows": 3}):
        tr.counter("obs_test.kv", pid=WALL_PID, reserved_tokens=96,
                   tokens_in_use=40)
    tr.instant("obs_test.mark", args={"rid": 7})
    tr.complete("obs_test.virtual", ts_s=1.0, dur_s=0.5)
    tr.counter("obs_test.virtual_counter", ts_s=1.0, depth=2)


def _profiled_host_events(tmp_path, record) -> dict:
    """Run ``record()`` under ``jax.profiler`` and return the events of
    the profile's ``/host:`` planes: ``{name: [(start_ns, dur_ns,
    {stat: value})]}``."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        record()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns, dict(ev.stats)))
    return out


def test_wall_events_land_in_the_profilers_host_plane(tmp_path):
    host = _profiled_host_events(tmp_path, lambda: _record(Tracer()))
    (s0, d0, span_stats), = host["obs_test.span"]
    (s1, d1, kv_stats), = host["obs_test.kv"]
    (_, _, mark_stats), = host["obs_test.mark"]
    assert span_stats == {"rows": 3}
    assert kv_stats == {"reserved_tokens": 96, "tokens_in_use": 40}
    assert mark_stats == {"rid": 7}
    assert s0 <= s1 and s1 + d1 <= s0 + d0      # the counter in the span
    # virtual-clock events lie on another timeline: not annotated
    assert "obs_test.virtual" not in host
    assert "obs_test.virtual_counter" not in host


def test_null_tracer_adds_nothing_to_the_profile(tmp_path):
    host = _profiled_host_events(tmp_path, lambda: _record(NULL_TRACER))
    assert not [name for name in host if name.startswith("obs_test.")]


def test_chrome_export_unchanged_by_a_running_profile(tmp_path):
    def events(tr):      # wall timestamps differ from run to run
        return [{k: v for k, v in ev.items() if k not in ("ts", "dur")}
                for ev in tr.to_chrome()["traceEvents"]]

    plain, profiled = Tracer(), Tracer()
    _record(plain)
    _profiled_host_events(tmp_path, lambda: _record(profiled))
    assert events(profiled) == events(plain)
    kv = next(ev for ev in profiled.to_chrome()["traceEvents"]
              if ev["name"] == "obs_test.kv")
    assert kv["ph"] == "C" and kv["pid"] == WALL_PID
    assert kv["args"] == {"reserved_tokens": 96.0, "tokens_in_use": 40.0}
