"""A CPU rehearsal of the open loop at a tiny size, on a clock of its own:
it counts and never times."""
import numpy as np

from chipbench_tiny import TINY, data, table1_spec

from chipbench import harness, loop, model, traffic


class FakeClock:
    """Advances 1 ms a reading; sleeping jumps it forward."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t

    def sleep(self, s):
        self.t += s


def test_open_loop_counts():
    from repro.obs import jax_hooks

    spec = table1_spec(rate=6.0)
    seconds = 4.0
    engine = model.build_engine(TINY, spec.cell["engine"], 7)
    harness.warm(engine, spec)
    reqs = traffic.generate(spec.mix, spec.cell, 7, seconds, 512)
    clock = FakeClock()
    lp = loop.OpenLoop(engine, reqs, admit_cap=spec.cell["admit_cap"],
                       clock=clock, sleep=clock.sleep)
    before = jax_hooks.trace_counts()
    lp.run(seconds, follow_s=60.0)
    # the warm-up covered every shape the loop admitted at
    assert jax_hooks.trace_counts() == before

    recs = lp.records
    assert len(recs) == len(reqs) == round(6.0 * seconds)
    for r in reqs:
        rec = recs[r.rid]
        assert rec.finished
        assert rec.n_tokens == len(rec.tokens) == r.budget + 8
        assert sum(n for _, n in rec.arrivals) == rec.n_tokens
        assert rec.due <= rec.t_first <= rec.t_last
    # tokens arrive only when an admission or a chunk returns
    ends = {a.t1 for a in lp.admissions} | {c.t1 for c in lp.chunks}
    assert all(t in ends for rec in recs.values() for t, _ in rec.arrivals)
    # admissions come in power-of-two groups up to the cap
    assert {a.n for a in lp.admissions} <= {1, 2}
    assert sum(a.n for a in lp.admissions) == len(reqs)
    # every token after the first came from a chunk; the chunk counts add up
    assert sum(c.tokens_out for c in lp.chunks) == \
        sum(r.n_tokens - 1 for r in recs.values())
    assert all(c.steps == 8 and c.row_steps <= 8 * c.n_active
               for c in lp.chunks)
    # live KV counted per row-step: at least the prompt plus one
    assert all(c.kv_tokens >= c.row_steps * 17 for c in lp.chunks)
    assert engine.n_active == 0 and engine.allocator.reserved == 0


def test_backlog_primes_rows_and_stops_at_close():
    spec = table1_spec()
    mix = dict(data("traffic", "long_reasoning"),
               budget_loguniform=[40, 80], prompt_len=[16, 32])
    cell = dict(spec.cell, backlog=6,
                engine=dict(spec.cell["engine"], capacity=128,
                            pool_blocks=80))
    spec = harness.Spec("tiny.long", 1, TINY, mix, cell, [], [])
    engine = model.build_engine(TINY, cell["engine"], 9)
    harness.warm(engine, spec)
    reqs = traffic.generate(mix, cell, 9, 1.0, 512)
    rows = cell["engine"]["rows"]
    assert [r.prime for r in reqs] == [True] * rows + [False] * 6
    clock = FakeClock()
    lp = loop.OpenLoop(engine, reqs[rows:], admit_cap=2, clock=clock,
                       sleep=clock.sleep)
    lp.prime(reqs[:rows])
    assert engine.n_active == rows
    lp.run(0.3, follow_s=None)
    assert lp.chunks and lp.chunks[-1].t0 < lp.t_close
    served = [r for r in lp.records.values() if r.finished]
    for rec in served:
        req = next(q for q in reqs if q.rid == rec.rid)
        assert len(rec.tokens) == req.budget + 8
    assert np.all([c.n_active <= rows for c in lp.chunks])
