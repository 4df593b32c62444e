"""Paged KV cache: block-table decode pinned against the dense slot path.

Contracts under test:

* paged decode (``attn_decode_paged`` through the continuous engine) is
  token-for-token identical to the dense stacked path with greedy
  sampling — across backbones, ragged budgets, chunk boundaries, and the
  Pallas scalar-prefetch kernel (interpret mode on CPU),
* block-pool exhaustion is back-pressure (admission returns False and the
  request queues), never a crash; retiring slots return their blocks and
  the free list is restored exactly,
* the paged entry points compile once and serve every budget / block
  layout as data (``obs.jax_hooks`` compile counters),
* randomized churn preserves the allocator invariants (no double
  allocation, reservation accounting, full recovery after drain),
* with a ``Tracer`` attached the engine records its admission and decode
  spans (each chunk tiled by prep, dispatch, sync and unpack) and a
  ``continuous.kv`` counter of KV reserved against KV in use and of the
  blocks the paged kernel streams against the tables' capacity, and serves
  the same tokens as without one.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params, reduced
from repro.models.attention import PagedKVCache, init_paged_cache
from repro.obs import Tracer, jax_hooks
from repro.serving.continuous import BlockAllocator, ContinuousBatchingEngine

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # pragma: no cover - optional dependency
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("qwen3-0.6b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def requests():
    rng = np.random.default_rng(0)
    return [(i,
             rng.integers(1, 97, size=int(rng.integers(3, 20))).astype(
                 np.int32),
             int(rng.integers(1, 12)), 4) for i in range(10)]


def drain(eng, reqs, use_step=False, chunk=None):
    """Admit-all/step loop mirroring LLMServer._run_continuous."""
    pending = list(reqs)
    done = {}
    while pending or eng.n_active:
        if pending:
            flags = eng.admit_many(pending)
            pending = [r for r, ok in zip(pending, flags) if not ok]
        fin = eng.step() if use_step else eng.step_chunk(chunk)
        for s in fin:
            done[s.rid] = s
    return {k: v.tokens for k, v in done.items()}


# ------------------------------------------------------------- equality pins
def test_paged_matches_slot_token_for_token(setup, requests):
    cfg, params = setup
    slot = ContinuousBatchingEngine(cfg, params, max_slots=4, capacity=64,
                                    chunk=5)
    paged = ContinuousBatchingEngine(cfg, params, max_slots=4, capacity=64,
                                     chunk=5, paged=True, block_size=8)
    assert paged.pool_tokens == slot.pool_tokens    # equal KV memory
    assert drain(paged, requests) == drain(slot, requests)


def test_paged_step_matches_step_chunk(setup, requests):
    cfg, params = setup

    def mk():
        return ContinuousBatchingEngine(cfg, params, max_slots=4,
                                        capacity=64, chunk=5, paged=True,
                                        block_size=8)

    ref = drain(mk(), requests)
    assert drain(mk(), requests, use_step=True) == ref
    # chunk boundaries move, tokens don't
    assert drain(mk(), requests, chunk=1) == ref
    assert drain(mk(), requests, chunk=13) == ref


def test_paged_kernel_matches_reference(setup, requests):
    cfg, params = setup
    ref = drain(ContinuousBatchingEngine(cfg, params, max_slots=4,
                                         capacity=64, chunk=5, paged=True,
                                         block_size=8), requests)
    kern = drain(ContinuousBatchingEngine(cfg, params, max_slots=4,
                                          capacity=64, chunk=5, paged=True,
                                          block_size=8,
                                          use_decode_kernel=True), requests)
    assert kern == ref


def test_paged_moe_backbone(requests):
    cfg = reduced(get_config("deepseek-moe-16b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    slot = ContinuousBatchingEngine(cfg, params, max_slots=3, capacity=64,
                                    chunk=4)
    paged = ContinuousBatchingEngine(cfg, params, max_slots=3, capacity=64,
                                     chunk=4, paged=True, block_size=8)
    reqs = requests[:6]
    assert drain(paged, reqs) == drain(slot, reqs)


def test_paged_int8_matches_slot_int8(setup, requests):
    cfg, params = setup
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    slot = ContinuousBatchingEngine(cfg8, params, max_slots=4, capacity=64,
                                    chunk=5)
    paged = ContinuousBatchingEngine(cfg8, params, max_slots=4, capacity=64,
                                     chunk=5, paged=True, block_size=8)
    assert drain(paged, requests) == drain(slot, requests)
    # pool really is int8 + f32 scales
    pc = paged.cache["layers"]
    assert pc.k.dtype == jnp.int8 and pc.k_scale is not None
    assert pc.k_scale.dtype == jnp.float32


def test_paged_rejects_recurrent_backbones():
    cfg = reduced(get_config("rwkv6-1.6b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged KV"):
        ContinuousBatchingEngine(cfg, params, paged=True)


# --------------------------------------------------------- admission/blocks
def test_pool_exhaustion_queues_not_crashes(setup, requests):
    cfg, params = setup
    slot_ref = drain(ContinuousBatchingEngine(cfg, params, max_slots=4,
                                              capacity=64, chunk=5),
                     requests)
    eng = ContinuousBatchingEngine(cfg, params, max_slots=6, capacity=64,
                                   chunk=5, paged=True, block_size=8,
                                   n_blocks=6)
    flags = eng.admit_many(requests)
    assert 0 < sum(flags) < len(requests)     # some admitted, some queued
    out = drain(eng, requests)                # re-offer until served
    assert out == slot_ref                    # back-pressure never changes
    #                                           tokens, only timing
    assert eng.allocator.n_free == 6 and eng.allocator.reserved == 0
    assert eng.check_block_invariants()
    assert (eng._tables_host == eng.n_blocks).all()


def test_free_list_reuse_after_retire(setup):
    cfg, params = setup
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, capacity=32,
                                   chunk=4, paged=True, block_size=8,
                                   n_blocks=8)
    prompt = np.arange(1, 9, dtype=np.int32)
    assert eng.admit(0, prompt, budget=4, max_extra=2)
    first_blocks = set(eng._slot_blocks[0])
    while eng.n_active:
        eng.step_chunk()
        first_blocks |= set(eng._slot_blocks[0])
    assert eng.allocator.n_free == 8
    assert eng.check_block_invariants()
    # the freed blocks are handed to the next request (LIFO reuse)
    assert eng.admit(1, prompt, budget=4, max_extra=2)
    reused = set(eng._slot_blocks[0]) | set(eng._slot_blocks[1])
    assert reused & first_blocks


def test_one_compile_serves_all_budgets(setup, requests):
    """The paged decode/insert entry points must not re-trace per budget
    or per block layout — tables and lengths are data."""
    cfg, params = setup
    jax_hooks.reset()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, capacity=64,
                                   chunk=5, paged=True, block_size=8)
    drain(eng, requests)
    # decode scan: block tables / lengths / budgets are all data
    assert jax_hooks.assert_max_compiles("continuous.scan", 1) == 1
    # prefill+insert retrace only per padded prompt shape, never per budget
    assert jax_hooks.trace_counts().get("continuous.insert_paged", 0) >= 1
    jax_hooks.reset()


def test_prefill_buckets_bound_compiles(setup):
    """Sixteen distinct prompt lengths compile the prefill once per bucket
    (power-of-two multiples of the block, up to the capacity), never once
    per length."""
    cfg, params = setup
    jax_hooks.reset()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, capacity=128,
                                   chunk=4, paged=True, block_size=8)
    lengths = [2 + 7 * i for i in range(16)]            # 2 .. 107
    buckets = {eng._prefill_len(n) for n in lengths}
    assert buckets == {8, 16, 32, 64, 128}
    for rid, n in enumerate(lengths):
        assert eng.admit(rid, np.arange(1, n + 1, dtype=np.int32) % 97,
                         budget=2, max_extra=1)
        while eng.n_active:
            eng.step_chunk()
    assert jax_hooks.assert_max_compiles("continuous.prefill",
                                         len(buckets)) == len(buckets)
    jax_hooks.reset()


def test_occupancy_gauges(setup, requests):
    cfg, params = setup
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, capacity=64,
                                   chunk=5, paged=True, block_size=8)
    assert eng.tokens_in_use == 0 and eng.pool_fill == 0.0
    eng.admit_many(requests[:4])
    assert eng.tokens_in_use == sum(s.cache_len for s in eng.slots if s)
    assert 0.0 < eng.pool_fill <= 1.0
    assert eng.blocks_in_use == eng.allocator.n_allocated > 0
    while eng.n_active:
        eng.step_chunk()
    assert eng.tokens_in_use == 0 and eng.blocks_in_use == 0


# ------------------------------------------------------------ engine spans
CHUNK_PARTS = ("continuous.prep", "continuous.dispatch", "continuous.sync",
               "continuous.unpack")
ADMIT_PARTS = ("continuous.blocks", "continuous.prefill",
               "continuous.insert", "continuous.first_sync")


def _parts(spans, parent, names):
    """The spans named in ``names`` that lie inside ``parent``, by start."""
    end = parent["ts"] + parent["dur"]
    return sorted((e for e in spans if e["name"] in names
                   and parent["ts"] <= e["ts"] and e["ts"] + e["dur"] <= end),
                  key=lambda e: e["ts"])


def test_engine_spans_and_kv_counter(setup, requests):
    cfg, params = setup
    jax_hooks.reset()
    tr = Tracer()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, capacity=64,
                                   chunk=5, paged=True, block_size=8,
                                   tracer=tr)
    kv_expected, pending, done = [], list(requests), {}
    while pending or eng.n_active:
        if pending:
            flags = eng.admit_many(pending)
            pending = [r for r, ok in zip(pending, flags) if not ok]
        kv_expected.append((
            float(eng.allocator.reserved * eng.block_size),
            float(eng.tokens_in_use),
            float(sum(-(-s.cache_len // 8) for s in eng.slots if s)),
            float(4 * eng.n_bt)))
        for s in eng.step_chunk():
            done[s.rid] = s.tokens
    events = tr.to_chrome()["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]

    # every chunk is tiled, in order, by its four parts
    chunks = [e for e in spans if e["name"] == "continuous.decode_chunk"]
    assert len(chunks) == len(kv_expected)
    for c in chunks:
        parts = _parts(spans, c, CHUNK_PARTS)
        assert [e["name"] for e in parts] == list(CHUNK_PARTS)
        edges = [c["ts"]] + [x for e in parts
                             for x in (e["ts"], e["ts"] + e["dur"])]
        edges.append(c["ts"] + c["dur"])
        gaps = [edges[i + 1] - edges[i] for i in range(0, len(edges), 2)]
        assert all(0 <= g < 1000 for g in gaps), gaps      # microseconds

    # every admission holds its four parts in order and names its rows
    admits = [e for e in spans if e["name"] == "continuous.admit"]
    assert sorted(r for a in admits for r in a["args"]["rids"]) == \
        sorted(rid for rid, *_ in requests)
    for a in admits:
        parts = _parts(spans, a, ADMIT_PARTS)
        assert [e["name"] for e in parts] == list(ADMIT_PARTS)
        assert all(p["ts"] + p["dur"] <= q["ts"]
                   for p, q in zip(parts, parts[1:]))
        assert a["args"]["rows"] == len(a["args"]["rids"])

    # one KV sample per chunk, inside its prep, equal to the engine's
    # reservation and occupancy as the chunk began, and to the blocks the
    # paged kernel streams against the tables' capacity (4 rows x 8)
    kv = [e for e in events if e["ph"] == "C" and e["name"] == "continuous.kv"]
    assert [tuple(e["args"][k] for k in ("reserved_tokens", "tokens_in_use",
                                         "streamed_blocks", "table_blocks"))
            for e in kv] == kv_expected
    assert all(r >= u > 0 for r, u, _, _ in kv_expected)
    assert all(0 < n <= t == 32 for _, _, n, t in kv_expected)
    for sample, c in zip(kv, chunks):
        prep = _parts(spans, c, CHUNK_PARTS)[0]
        assert prep["ts"] <= sample["ts"] <= prep["ts"] + prep["dur"]

    # each entry point compiled once per shape it served, tracer on
    shapes = {(a["args"]["rows"], a["args"]["S"]) for a in admits}
    bounds = {"continuous.scan": 1, "continuous.prefill": len(shapes),
              "continuous.insert_paged": len(shapes)}
    counts = jax_hooks.trace_counts()
    assert set(counts) == set(bounds)
    for label, n in counts.items():
        jax_hooks.assert_max_compiles(label, bounds[label])
    jax_hooks.reset()

    # the tracer changes no token
    plain = ContinuousBatchingEngine(cfg, params, max_slots=4, capacity=64,
                                     chunk=5, paged=True, block_size=8)
    assert done == drain(plain, requests)


# ------------------------------------------------------------ allocator unit
def test_block_allocator_basics():
    al = BlockAllocator(4)
    assert al.n_free == 4 and al.can_reserve(4) and not al.can_reserve(5)
    assert al.reserve(3)
    assert not al.reserve(2)          # over-reservation refused
    got = al.alloc(3)
    assert len(set(got)) == 3 and al.n_free == 1 and al.n_allocated == 3
    al.free(got[:2])
    assert al.n_free == 3
    al.free(got[2:])
    al.release(3)
    assert al.n_free == 4 and al.reserved == 0


def test_block_allocator_randomized_churn():
    """Fragmentation invariants under random reserve/alloc/free cycles:
    blocks are never double-allocated, the free list never exceeds the
    pool, and a full drain restores the initial state."""
    rng = np.random.default_rng(7)
    al = BlockAllocator(32)
    live = []              # (blocks, reserved)
    for _ in range(500):
        if live and rng.random() < 0.45:
            blocks, res = live.pop(rng.integers(len(live)))
            al.free(blocks)
            al.release(res)
        else:
            n = int(rng.integers(1, 6))
            if al.reserve(n):
                blocks = al.alloc(n)
                live.append((blocks, n))
        held = [b for bl, _ in live for b in bl]
        assert len(held) == len(set(held))              # no double alloc
        assert al.check_balance(in_use=len(held))       # conservation
        assert al.reserved == sum(r for _, r in live)
    for blocks, res in live:
        al.free(blocks)
        al.release(res)
    assert al.n_free == 32 and al.reserved == 0


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.tuples(st.booleans(), st.integers(1, 5)),
                    min_size=1, max_size=60),
           st.integers(8, 48))
    def test_block_allocator_property(ops, n_blocks):
        al = BlockAllocator(n_blocks)
        live = []
        for is_free, n in ops:
            if is_free and live:
                blocks, res = live.pop()
                al.free(blocks)
                al.release(res)
            elif al.reserve(n):
                live.append((al.alloc(n), n))
            held = [b for bl, _ in live for b in bl]
            assert len(held) == len(set(held))
            assert al.check_balance(in_use=len(held))
        for blocks, res in live:
            al.free(blocks)
            al.release(res)
        assert al.n_free == n_blocks and al.reserved == 0


# ----------------------------------------------------------- cache plumbing
def test_init_paged_cache_shapes(setup):
    cfg, _ = setup
    pc = init_paged_cache(cfg, batch=3, n_blocks=10, block_size=4, n_bt=6)
    assert isinstance(pc, PagedKVCache)
    assert pc.k.shape == (cfg.n_layers, 10, cfg.n_kv_eff, 4, cfg.hd)
    assert pc.block_tables.shape == (3, 6)
    assert bool((pc.block_tables == 10).all())      # all-sentinel at init
    assert pc.n_blocks == 10 and pc.block_size == 4 and pc.capacity == 24
