"""Continuous batching: requests join and leave the decode batch in flight.

The paper's M/G/1 server admits one query at a time; production engines
(Orca, vLLM) decode a rolling batch where each slot holds an independent
request at its own cache position. This module implements that on top of
the per-row-position decode path (``attn_decode`` with a vector
``length``):

* a fixed pool of ``max_slots`` cache rows,
* **batched admission**: up to k queued requests prefill in ONE padded
  B=k dispatch (``admit_many``), and all k rows are inserted with a single
  vectorized slot-scatter — one jitted, donation-aware ``_insert`` over a
  slot-index vector instead of a per-request per-leaf Python scatter,
* one shared decode step advances every active slot, either per token
  (``step``, the reference) or as a fused ``lax.scan`` emitting up to
  ``chunk`` tokens per dispatch (``step_chunk``) with per-slot budget and
  alive masks carried as device state,
* strict per-slot budget enforcement (the paper's control knob),
* slots retire when budget + answer tokens complete.

Paged mode (``paged=True``): the KV cache is a shared pool of fixed-size
blocks (:class:`~..models.attention.PagedKVCache`) instead of per-slot
dense ``[C, ...]`` rows, and admission is gated by TOKENS, not rows:

* a request is admitted while its worst-case token need
  (``prompt_len + budget + max_extra - 1``) still fits the unreserved
  pool (:class:`BlockAllocator` reservation) and a decode row is free —
  rows are cheap (no capacity-sized memory behind them), so at equal KV
  memory the paged engine sustains far more concurrent tokens-in-use
  than ``max_slots`` worst-case rows (``benchmarks/paged_bench.py``
  gates this),
* physical blocks are allocated lazily at chunk boundaries
  (``_ensure_blocks``: just enough to cover the next ``chunk`` decode
  steps, capped at the reservation so the free list can never run dry)
  and freed when the slot retires; the block table is authoritative on
  the host and synced to the device as DATA, so one compiled
  ``step_chunk`` serves every budget and every allocation pattern,
* exhaustion is back-pressure, not failure: ``admit_many`` returns False
  for requests that don't fit and the caller re-offers them as blocks
  free up (``LLMServer._run_continuous`` already loops exactly so).

Stochastic sampling (``temperature > 0``) is **chunk-invariant**: token
``g`` of request ``rid`` is always drawn with the key
``fold_in(fold_in(PRNGKey(seed), rid), g)``, so ``step`` and
``step_chunk`` (any chunk size, any admission interleaving) produce
identical streams — the per-slot key depends only on the request id and
the token index, never on batch composition or chunk boundaries.

Padding contract: batched admission right-pads prompts, to a
power-of-two multiple of the block so that a few prefill programs serve
every prompt length (``_prefill_len``); this is exact for attention
backbones (causal masking means the last real token's logits are
unchanged, and pad KV slots are overwritten by decode before the per-row
``length`` mask can expose them). Recurrent/hybrid backbones and sliding
windows fold pads into carried state, so there admissions are batched per
equal prompt length instead (no pads, still one dispatch per group);
capacity-dispatch MoE couples rows through shared per-expert capacity
buffers, so its admissions stay B=1 (dropless MoE impls batch freely).

Donation contract: ``_step`` / ``_scan`` / ``_insert`` consume the engine
cache via ``donate_argnums`` (through ``compat.jit``) where the backend
supports it, so slot caches (or the paged pool) update in place instead of
copying all capacity-sized leaves every token.

Correctness contract (tested): with greedy sampling, a request served in a
rolling batch — admitted in a batch, decoded in chunks, sharing steps with
strangers across admissions and retirements — produces EXACTLY the tokens
it would produce alone; the paged path is pinned token-for-token against
the dense slot path.
"""
from __future__ import annotations

import dataclasses
import math
from contextlib import nullcontext
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import compat
from ..models import decode_step, forward
from ..models.config import ModelConfig

Array = jnp.ndarray

# smallest prefill width of a dense-slot engine (paged: the block size)
PREFILL_GRANULE = 16


@dataclasses.dataclass
class Slot:
    rid: int
    budget: int
    max_extra: int
    generated: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    last_token: int = 0
    prompt_len: int = 0
    key: Optional[np.ndarray] = None   # folded per-request PRNG key [2]

    @property
    def cache_len(self) -> int:
        """Tokens currently held in KV for this slot (prompt + decode
        writes; the prefill's first emitted token is not yet written)."""
        return self.prompt_len + max(self.generated - 1, 0)


class BlockAllocator:
    """LIFO free-list + reservation accounting over the paged KV pool.

    Reservation happens at ADMISSION (worst-case blocks for the request's
    full prompt + budget + answer), physical allocation lazily at chunk
    boundaries. Because the sum of reservations never exceeds the pool,
    a lazy ``alloc`` can never fail mid-flight — exhaustion only ever
    surfaces as an admission refusal, which queues the request.
    """

    def __init__(self, n_blocks: int):
        self.n_blocks = n_blocks
        self._free = list(range(n_blocks - 1, -1, -1))  # pop() -> block 0 first
        self.reserved = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_allocated(self) -> int:
        return self.n_blocks - len(self._free)

    def can_reserve(self, n: int) -> bool:
        return self.reserved + n <= self.n_blocks

    def reserve(self, n: int) -> bool:
        if not self.can_reserve(n):
            return False
        self.reserved += n
        return True

    def release(self, n: int) -> None:
        self.reserved -= n
        assert self.reserved >= 0

    def alloc(self, n: int) -> list:
        assert n <= len(self._free), "allocation beyond reservation"
        return [self._free.pop() for _ in range(n)]

    def free(self, blocks) -> None:
        self._free.extend(blocks)
        assert len(self._free) <= self.n_blocks

    def check_balance(self, in_use: Optional[int] = None) -> bool:
        """Standing audit of the pool accounting; raises on violation.

        Invariants: every free block is unique and in range (a double
        ``free`` is the classic leak-by-aliasing), ``free + allocated ==
        n_blocks`` (with ``in_use`` the caller's independent count of
        blocks held — the engine passes its per-slot block lists), and
        reservations stay within the pool. Chaos tests call this after
        every fault scenario; ``tests/test_paged.py`` after every drain.
        """
        free = self._free
        if len(set(free)) != len(free):
            raise AssertionError("duplicate block on the free list")
        if free and not all(0 <= b < self.n_blocks for b in free):
            raise AssertionError("out-of-range block on the free list")
        if not 0 <= self.reserved <= self.n_blocks:
            raise AssertionError(
                f"reservation accounting broken: {self.reserved} not in "
                f"[0, {self.n_blocks}]")
        if in_use is not None and len(free) + int(in_use) != self.n_blocks:
            raise AssertionError(
                f"block leak: {len(free)} free + {in_use} in use "
                f"!= {self.n_blocks} total")
        return True


def _fold_sample(key: Array, g: Array, logits: Array,
                 temperature: float) -> Array:
    """Chunk-invariant stochastic sampling for one slot.

    Token ``g`` of the request owning ``key`` is drawn with
    ``fold_in(key, g)`` — a pure function of (request id, token index),
    independent of chunk size, batch composition, or admission order.
    Math matches ``models.sampling.sample`` (f32 logits / temperature,
    Gumbel argmax via ``jax.random.categorical``).
    """
    return jax.random.categorical(
        jax.random.fold_in(key, g),
        logits.astype(jnp.float32) / temperature).astype(jnp.int32)


class ContinuousBatchingEngine:
    def __init__(self, cfg: ModelConfig, params, max_slots: int = 4,
                 capacity: int = 512, chunk: int = 8,
                 use_decode_kernel: bool = False, tracer=None,
                 paged: bool = False, block_size: int = 16,
                 n_blocks: Optional[int] = None,
                 temperature: float = 0.0, seed: int = 0,
                 faults=None):
        if use_decode_kernel:
            cfg = dataclasses.replace(cfg, use_decode_kernel=True)
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.chunk = chunk
        self.temperature = float(temperature)
        self.seed = int(seed)
        self._base_key = None    # built lazily; greedy never touches PRNG
        # optional wall-span tracing of admission/decode dispatches; one
        # `is not None` check per dispatch when disabled. Jit labels feed
        # the obs.jax_hooks compile counters (per compile, not per call).
        self.tracer = tracer
        # optional repro.faults injector bank: its on_decode_step hook
        # fires at every step/chunk boundary (even while idle, so a
        # pool-pressure reservation can't outlive its hold window)
        self.faults = faults
        self.paged = paged
        from ..models import init_decode_cache
        from ..models.attention import init_paged_cache
        if paged:
            if not self._can_page():
                raise ValueError(
                    "paged KV requires a full-attention backbone "
                    "(attn/moe, no sliding window, no shared attention)")
            self.block_size = block_size
            self.n_bt = max(1, math.ceil(capacity / block_size))
            self.capacity = self.n_bt * block_size
            # default pool = the slot path's aggregate KV memory
            self.n_blocks = (max_slots * self.n_bt if n_blocks is None
                             else n_blocks)
            self.allocator = BlockAllocator(self.n_blocks)
            self._slot_blocks = [[] for _ in range(max_slots)]
            self._slot_reserved = [0] * max_slots
            self._tables_host = np.full((max_slots, self.n_bt),
                                        self.n_blocks, np.int32)
            self._tables_dirty = False
            self.cache = {"layers": init_paged_cache(
                cfg, max_slots, self.n_blocks, block_size, self.n_bt)}
        else:
            self.block_size = None
            self.n_blocks = None
            self.allocator = None
            self.capacity = capacity
            # per-slot positions: broadcast every `length` leaf to [L..., B]
            self.cache = self._with_vector_lengths(
                init_decode_cache(cfg, max_slots, capacity))
        self.slots: list = [None] * max_slots
        self._prefill = compat.jit(self._prefill_impl,
                                   static_argnames=("capacity",),
                                   label="continuous.prefill")
        self._step = compat.jit(self._step_impl, donate_argnums=(2,),
                                label="continuous.step")
        self._scan = compat.jit(self._scan_impl, donate_argnums=(2,),
                                static_argnames=("chunk",),
                                label="continuous.scan")
        self._insert = compat.jit(self._insert_impl, donate_argnums=(1,),
                                  label="continuous.insert")
        self._insert_paged = compat.jit(self._insert_paged_impl,
                                        donate_argnums=(1,),
                                        label="continuous.insert_paged")
        self._sample = compat.jit(self._sample_impl,
                                  label="continuous.sample")

    # ------------------------------------------------------------ internals
    def _can_page(self) -> bool:
        """Paged decode covers the full-attention backbones: per-position
        KV with causal masking (blocks are position-addressed). Ring
        buffers (sliding window) and recurrent/hybrid state stay dense."""
        return (self.cfg.backbone_kind in ("attn", "moe")
                and not self.cfg.has_shared_attn
                and self.cfg.sliding_window is None)

    def _with_vector_lengths(self, cache):
        def fix(t):
            if hasattr(t, "_replace") and hasattr(t, "length"):
                ln = jnp.broadcast_to(t.length[..., None],
                                      t.length.shape + (self.max_slots,))
                return t._replace(length=ln)
            return t
        return jax.tree.map(fix, cache,
                            is_leaf=lambda n: hasattr(n, "_replace")
                            and hasattr(n, "length"))

    def _prefill_impl(self, params, tokens, lengths, *, capacity):
        """Right-padded B=k prefill; returns per-row greedy first tokens
        (gathered at each row's true last position), the gathered last
        logits (for stochastic first-token sampling), and the prefill
        cache. ``capacity`` is static: the slot path prefills at the
        engine capacity, the paged path at the padded prompt length
        (blocks are scattered from the exact rows, no dense padding)."""
        out = forward(self.cfg, params, tokens, return_cache=True,
                      cache_capacity=capacity)
        rows = jnp.arange(tokens.shape[0])
        last = out.logits[rows, lengths - 1]
        return (jnp.argmax(last, axis=-1).astype(jnp.int32), last,
                out.cache)

    def _step_impl(self, params, token, cache):
        out = decode_step(self.cfg, params, token, cache)
        return out.logits, out.cache

    def _sample_impl(self, logits, keys, gidx):
        """Vectorized chunk-invariant sampling: logits [B, V], keys
        [B, 2] uint32, gidx [B] -> tokens [B]."""
        return jax.vmap(_fold_sample, in_axes=(0, 0, 0, None))(
            keys, gidx, logits, self.temperature)

    def _scan_impl(self, params, token, cache, alive, remaining, keys,
                   gidx, *, chunk):
        """Fused multi-token decode: ``chunk`` steps in one dispatch.

        Per-slot alive/remaining masks ride the scan carry; retired slots
        keep decoding on their own (discarded) continuation — their rows
        are dead weight until the next admission overwrites them (and in
        paged mode their writes land on the block-table sentinel and are
        dropped) — which keeps shapes static. Dead-row inputs never
        influence live rows for the row-independent architectures the
        exactness contract covers. Emits the raw next-token matrix
        [chunk, S]; the host takes ``min(chunk, remaining)`` tokens per
        slot, mirroring ``step``. ``gidx`` carries each slot's emission
        index so stochastic sampling folds the same per-token key the
        per-token path folds.
        """
        greedy = self.temperature <= 0.0

        def body(carry, _):
            token, cache, alive, remaining, gidx = carry
            out = decode_step(self.cfg, params, token[:, None], cache,
                              static_layers=True)
            logits, cache = out.logits, out.cache
            if greedy:
                nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            else:
                nxt = jax.vmap(_fold_sample, in_axes=(0, 0, 0, None))(
                    keys, gidx, logits[:, 0, :], self.temperature)
            gidx = gidx + 1
            remaining = remaining - alive.astype(jnp.int32)
            alive = alive & (remaining > 0)
            return (nxt, cache, alive, remaining, gidx), nxt

        (token, cache, alive, remaining, gidx), toks = jax.lax.scan(
            body, (token, cache, alive, remaining, gidx), None, length=chunk)
        return toks, cache

    def _insert_impl(self, row_cache, cache, slot_idx, lengths):
        """Vectorized slot-scatter: insert k prefilled rows into ``cache``
        at ``slot_idx`` [k] in one fused update (all leaves, all rows).

        The batch axis of every leaf is the node's stack-prefix depth,
        recovered from the broadcast ``length`` leaf (shape [stack..., B]);
        ``lengths`` [k] carries each row's TRUE prompt length so padded
        prefills land with exact per-row positions.
        """
        def ins(dst, src):
            if not (hasattr(dst, "_replace") and hasattr(dst, "length")):
                return dst
            axis = dst.length.ndim - 1          # stack-prefix depth
            new = {}
            for f in dst._fields:
                d, s = getattr(dst, f), getattr(src, f)
                if f == "length":
                    new[f] = d.at[..., slot_idx].set(
                        lengths.astype(d.dtype))
                else:
                    idx = [slice(None)] * d.ndim
                    idx[axis] = slot_idx
                    new[f] = d.at[tuple(idx)].set(s)
            return dst._replace(**new)

        return jax.tree.map(
            ins, cache, row_cache,
            is_leaf=lambda n: hasattr(n, "_replace") and hasattr(n, "length"))

    def _insert_paged_impl(self, row_cache, cache, slot_idx, lengths,
                           rows_bt):
        """Scatter k prefilled rows into the paged pool in one update.

        ``row_cache`` leaves are [L, k, S, ...] (prefill at capacity = the
        padded prompt length S); ``rows_bt`` [k, n_bt] are the slots' new
        block-table rows (prompt blocks assigned, rest sentinel). Logical
        position p of row r lands at ``pool[:, rows_bt[r, p // bs], :,
        p % bs]``; pad positions (p >= lengths[r]) scatter through the
        sentinel and are dropped.
        """
        row = row_cache["layers"]    # dense prefill rows [L, k, S, ...]
        pc = cache["layers"]
        P, bs = pc.n_blocks, pc.block_size
        n_bt = pc.block_tables.shape[1]
        S = row.k.shape[2]
        k = row.k.shape[1]
        ppos = jnp.arange(S)
        bidx = jnp.minimum(ppos // bs, n_bt - 1)
        blk = jnp.where(ppos[None, :] < lengths[:, None],
                        rows_bt[jnp.arange(k)[:, None], bidx[None, :]],
                        P)                                   # [k, S]
        off = jnp.broadcast_to(ppos % bs, blk.shape)         # [k, S]

        layers = jnp.arange(pc.k.shape[0])[:, None, None, None]
        heads = jnp.arange(pc.k.shape[2])

        def scatter(pool, val):
            # pool [L, P, nkv, bs, ...] <- val [L, k, S, nkv, ...], one
            # (layer, row, position, head) vector per update
            return pool.at[layers, blk[None, :, :, None], heads,
                           off[None, :, :, None]].set(val, mode="drop")

        new = {"k": scatter(pc.k, row.k), "v": scatter(pc.v, row.v)}
        if pc.k_scale is not None:
            new["k_scale"] = scatter(pc.k_scale, row.k_scale)
            new["v_scale"] = scatter(pc.v_scale, row.v_scale)
        pc = pc._replace(
            block_tables=pc.block_tables.at[slot_idx].set(rows_bt),
            length=pc.length.at[:, slot_idx].set(
                lengths[None, :].astype(pc.length.dtype)),
            **new)
        return {"layers": pc}

    def _batch_rows(self) -> int:
        """How many requests one admission prefill may batch exactly.

        Capacity-dispatch MoE routes the whole flattened batch through
        shared per-expert capacity buffers, so rows (and pads) compete for
        slots and a token that survives solo can be dropped in a batch —
        those admissions stay B=1 to keep the served-alone contract.
        """
        if (self.cfg.backbone_kind == "moe"
                and self.cfg.moe.impl == "capacity"):
            return 1
        return self.max_slots

    def _can_pad_batch(self) -> bool:
        """Right-padded ragged prefill is exact only when per-position state
        never flows forward past the pads (pure attention, no window) and
        rows don't couple through shared routing buffers."""
        return (self.cfg.backbone_kind in ("attn", "moe")
                and self._batch_rows() > 1
                and not self.cfg.has_shared_attn
                and self.cfg.sliding_window is None)

    # -------------------------------------------------- paged block plumbing
    def _reserve_tokens(self, prompt_len: int, budget: int,
                        max_extra: int) -> int:
        """Worst-case KV tokens a request ever holds: the prompt plus one
        write per decode step (the final emitted token is never written)."""
        return prompt_len + max(budget + max_extra - 1, 0)

    def _reserve_blocks(self, prompt_len: int, budget: int,
                        max_extra: int) -> int:
        return max(1, math.ceil(
            self._reserve_tokens(prompt_len, budget, max_extra)
            / self.block_size))

    def _grow_slot_blocks(self, i: int, cover_tokens: int) -> None:
        """Assign physical blocks to slot ``i`` up to ``cover_tokens``
        logical positions (capped at the slot's reservation)."""
        need = min(math.ceil(cover_tokens / self.block_size),
                   self._slot_reserved[i])
        have = len(self._slot_blocks[i])
        if need <= have:
            return
        new = self.allocator.alloc(need - have)
        self._tables_host[i, have:need] = new
        self._slot_blocks[i].extend(new)
        self._tables_dirty = True

    def _ensure_blocks(self, steps: int) -> None:
        """Alloc-on-chunk-boundary: every live slot gets blocks covering
        its next ``steps`` decode writes. Reservation caps the cover, so
        over-allocation for slots retiring mid-chunk is bounded and the
        free list cannot run dry (writes past the cap are dropped on the
        sentinel — they belong to discarded post-retire tokens)."""
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            self._grow_slot_blocks(i, s.cache_len + steps)

    def _sync_tables(self) -> None:
        if self._tables_dirty:
            pc = self.cache["layers"]
            self.cache["layers"] = pc._replace(
                block_tables=jnp.asarray(self._tables_host))
            self._tables_dirty = False

    def _retire_slot(self, i: int) -> None:
        """Free-on-retire: return the slot's blocks and reservation, and
        sentinel its table row so any dead-row writes are dropped."""
        self.slots[i] = None
        if not self.paged:
            return
        self.allocator.free(self._slot_blocks[i])
        self.allocator.release(self._slot_reserved[i])
        self._slot_blocks[i] = []
        self._slot_reserved[i] = 0
        self._tables_host[i, :] = self.n_blocks
        self._tables_dirty = True

    def _slot_key(self, rid: int) -> np.ndarray:
        if self._base_key is None:
            self._base_key = jax.random.PRNGKey(self.seed)
        return np.asarray(jax.random.fold_in(self._base_key, rid))

    def _keys_gidx(self):
        """Per-row (key, next emission index) arrays for the sampler;
        empty rows get a throwaway key (their tokens are discarded)."""
        zero = np.zeros(2, np.uint32)
        keys = np.stack([s.key if s is not None and s.key is not None
                         else zero for s in self.slots])
        gidx = np.asarray([s.generated if s else 0 for s in self.slots],
                          np.int32)
        return jnp.asarray(keys), jnp.asarray(gidx)

    # ------------------------------------------------------------------ api
    def admit(self, rid: int, prompt: np.ndarray, budget: int,
              max_extra: int = 4) -> bool:
        """Prefill a request and place it in a free slot; False if full."""
        return self.admit_many([(rid, prompt, budget, max_extra)])[0]

    def admit_many(self, requests: Sequence[Tuple]) -> list:
        """Admit up to ``len(requests)`` queued requests in batched
        prefills. Each request is ``(rid, prompt, budget, max_extra)``.
        Returns per-request admission flags; admission order is FIFO over
        the argument list and stops at the first request that does not fit
        (out of rows, or — paged — out of pool tokens).

        Admission always emits the prefill's first token, so every
        request produces ``max(budget + max_extra, 1)`` tokens; degenerate
        ``budget + max_extra <= 1`` slots retire on the next step without
        consuming decode work (identical under ``step`` and
        ``step_chunk``).
        """
        free = [i for i, s in enumerate(self.slots) if s is None]
        flags = [False] * len(requests)
        batch = []
        for j, req in enumerate(requests):
            if len(batch) >= len(free):
                break
            if self.paged:
                rid, prompt, budget, max_extra = req
                if len(prompt) > self.capacity:
                    break
                nres = self._reserve_blocks(len(prompt), budget, max_extra)
                if not self.allocator.reserve(nres):
                    break
                self._slot_reserved[free[len(batch)]] = nres
            batch.append((free[len(batch)], req))
            flags[j] = True
        if not batch:
            return flags
        if self._can_pad_batch():
            groups = [batch]
        else:       # exactness for recurrent/hybrid/windowed: no pads
            by_len: dict = {}
            for item in batch:
                by_len.setdefault(len(item[1][1]), []).append(item)
            groups = list(by_len.values())
        rows = self._batch_rows()
        if rows < max(len(g) for g in groups):   # e.g. capacity-dispatch MoE
            groups = [g[i:i + rows] for g in groups
                      for i in range(0, len(g), rows)]
        for group in groups:
            self._admit_group(group)
        return flags

    def _prefill_len(self, S: int) -> int:
        """Prefill width for a group whose longest prompt is ``S``.

        Where right-padding is exact (:meth:`_can_pad_batch`) the width is
        rounded up to a power-of-two multiple of the block
        (``block_size``, or :data:`PREFILL_GRANULE` for dense slots),
        capped at the capacity, so a few prefill programs serve every
        prompt length; elsewhere a group shares one length and prefills
        at exactly that.
        """
        if not self._can_pad_batch():
            return S
        w = self.block_size or PREFILL_GRANULE
        while w < S:
            w *= 2
        return max(S, min(w, self.capacity))

    def _admit_group(self, group) -> None:
        lengths = np.asarray([len(req[1]) for _, req in group],
                             dtype=np.int32)
        S = self._prefill_len(int(lengths.max()))
        tokens = np.zeros((len(group), S), dtype=np.int32)
        for r, (_, req) in enumerate(group):
            tokens[r, :lengths[r]] = req[1]
        sampling = self.temperature > 0.0
        keys = (np.stack([self._slot_key(req[0]) for _, req in group])
                if sampling else None)
        ctx = (self.tracer.span("continuous.admit", cat="engine",
                                args={"rows": len(group), "S": S})
               if self.tracer is not None else nullcontext())
        with ctx:
            slot_idx = jnp.asarray([slot for slot, _ in group], jnp.int32)
            if self.paged:
                # assign the prompt's blocks up front so the insert
                # scatter lands on real blocks
                for slot, (_, prompt, _, _) in group:
                    self._grow_slot_blocks(slot, len(prompt))
                self._sync_tables()
                firsts, last, row_cache = self._prefill(
                    self.params, jnp.asarray(tokens), jnp.asarray(lengths),
                    capacity=S)
                rows_bt = jnp.asarray(
                    self._tables_host[[slot for slot, _ in group]])
                self.cache = self._insert_paged(
                    row_cache, self.cache, slot_idx, jnp.asarray(lengths),
                    rows_bt)
            else:
                firsts, last, row_cache = self._prefill(
                    self.params, jnp.asarray(tokens), jnp.asarray(lengths),
                    capacity=self.capacity)
                self.cache = self._insert(row_cache, self.cache, slot_idx,
                                          jnp.asarray(lengths))
            if sampling:    # first token is emission index g = 0
                firsts = self._sample(last, jnp.asarray(keys),
                                      jnp.zeros(len(group), jnp.int32))
        firsts = np.asarray(firsts)
        for r, (slot, (rid, prompt, budget, max_extra)) in enumerate(group):
            first = int(firsts[r])
            self.slots[slot] = Slot(
                rid=rid, budget=budget, max_extra=max_extra, generated=1,
                tokens=[first], last_token=first,
                prompt_len=int(lengths[r]),
                key=(keys[r] if sampling else None))

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def tokens_in_use(self) -> int:
        """KV tokens currently held by live requests (prompt + generated
        so far) — the occupancy the paged pool is gated on."""
        return sum(s.cache_len for s in self.slots if s is not None)

    @property
    def pool_tokens(self) -> int:
        """Total KV token capacity (pool blocks, or slot rows x capacity)."""
        if self.paged:
            return self.n_blocks * self.block_size
        return self.max_slots * self.capacity

    @property
    def pool_fill(self) -> float:
        """Fraction of the KV pool held by live requests."""
        return self.tokens_in_use / max(self.pool_tokens, 1)

    @property
    def blocks_in_use(self) -> int:
        return self.allocator.n_allocated if self.paged else 0

    def check_block_invariants(self) -> bool:
        """Audit the paged pool against this engine's slot state.

        Cross-checks :meth:`BlockAllocator.check_balance` with the
        engine's independent count of held blocks (the per-slot block
        lists) and verifies the slot reservations are covered by the
        allocator's reservation counter (strict equality only when no
        external tenant — e.g. ``repro.faults.PoolPressure`` — holds a
        reservation, hence ``>=``). No-op ``True`` on non-paged engines;
        chaos tests call it after every fault scenario.
        """
        if not self.paged:
            return True
        held = sum(len(b) for b in self._slot_blocks)
        self.allocator.check_balance(in_use=held)
        slot_res = sum(self._slot_reserved)
        if self.allocator.reserved < slot_res:
            raise AssertionError(
                f"slot reservations {slot_res} exceed allocator "
                f"reservation counter {self.allocator.reserved}")
        return True

    def step(self) -> list:
        """One decode step for all active slots; returns finished Slots.

        Per-token reference path: one dispatch + one host sync per token.
        ``step_chunk`` is the fused fast path with identical semantics.
        """
        if self.faults is not None:
            self.faults.on_decode_step(self)
        if self.n_active == 0:
            return []
        if self.paged:
            self._ensure_blocks(1)
            self._sync_tables()
        token = jnp.asarray([[s.last_token if s else 0]
                             for s in self.slots], jnp.int32)
        logits, self.cache = self._step(self.params, token, self.cache)
        if self.temperature > 0.0:
            keys, gidx = self._keys_gidx()
            nxt = np.asarray(self._sample(logits[:, 0, :], keys, gidx))
        else:
            nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1))
        finished = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            if s.generated < s.budget + s.max_extra:
                s.tokens.append(int(nxt[i]))
                s.last_token = int(nxt[i])
                s.generated += 1
            if s.generated >= s.budget + s.max_extra:
                finished.append(s)
                self._retire_slot(i)
        return finished

    def step_chunk(self, chunk: Optional[int] = None) -> list:
        """Advance every active slot by up to ``chunk`` tokens in ONE
        dispatch (fused ``lax.scan``); returns Slots that finished inside
        the chunk. Admissions happen at chunk boundaries; a slot whose
        remaining budget is shorter than the chunk retires mid-chunk (its
        surplus steps are masked on device and discarded here; paged
        surplus writes drop on the sentinel past the reservation).
        """
        if self.faults is not None:
            self.faults.on_decode_step(self)
        chunk = self.chunk if chunk is None else chunk
        if self.n_active == 0 or chunk <= 0:
            return []
        if self.paged:
            self._ensure_blocks(chunk)
            self._sync_tables()
        token = jnp.asarray([s.last_token if s else 0 for s in self.slots],
                            jnp.int32)
        alive = jnp.asarray([s is not None for s in self.slots])
        remaining = jnp.asarray(
            [s.budget + s.max_extra - s.generated if s else 0
             for s in self.slots], jnp.int32)
        keys, gidx = self._keys_gidx()
        ctx = (self.tracer.span("continuous.decode_chunk", cat="engine",
                                args={"chunk": chunk,
                                      "occupancy": self.n_active,
                                      "tokens_in_use": self.tokens_in_use})
               if self.tracer is not None else nullcontext())
        with ctx:
            toks, self.cache = self._scan(self.params, token, self.cache,
                                          alive, remaining, keys, gidx,
                                          chunk=chunk)
            toks = np.asarray(toks)                  # [chunk, S]
        finished = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            n_take = min(chunk, s.budget + s.max_extra - s.generated)
            if n_take > 0:
                s.tokens.extend(int(t) for t in toks[:n_take, i])
                s.generated += n_take
                s.last_token = int(toks[n_take - 1, i])
            if s.generated >= s.budget + s.max_extra:
                finished.append(s)
                self._retire_slot(i)
        return finished
