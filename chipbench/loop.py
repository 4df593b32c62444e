"""A thin open-loop serving loop over ``ContinuousBatchingEngine``.

Requests join a FIFO queue when they come due. At every chunk boundary
the loop admits what fits (free rows and unreserved pool blocks, in
order, stopping at the first that does not fit) through ``admit_many``,
in groups of power-of-two sizes up to ``admit_cap`` so that set-up can
warm every prefill shape, and then runs one ``step_chunk``. It stamps on
the host clock each request's due time, its first token (when
``admit_many`` returns, which waits for the token) and the arrival of
each later chunk's tokens (when ``step_chunk`` returns). Latencies run
from the due time, so a stall counts against every request behind it.

The loop never reads the clock for anything but stamps and the arrival
schedule; ``clock`` and ``sleep`` are parameters so that a test can run
it on a clock of its own.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from contextlib import nullcontext
from typing import Callable, Optional


@dataclasses.dataclass
class Record:
    rid: int
    due: float                 # host time the request came due
    n_out: int                 # tokens it should get
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    n_tokens: int = 0
    arrivals: list = dataclasses.field(default_factory=list)  # (t, n)
    tokens: Optional[list] = None        # set when it finishes

    @property
    def finished(self) -> bool:
        return self.tokens is not None


@dataclasses.dataclass
class Chunk:
    t0: float
    t1: float
    steps: int                 # decode steps the call ran
    n_active: int              # rows active when it started
    row_steps: int             # steps of active rows the algorithm needs
    kv_tokens: int             # KV positions those row-steps attend
    tokens_out: int            # tokens it delivered
    traced: bool


@dataclasses.dataclass
class Admission:
    t0: float
    t1: float
    n: int
    traced: bool


def pow2_floor(n: int) -> int:
    return 1 << (n.bit_length() - 1)


def blocks_needed(prompt_len: int, n_out: int, block: int) -> int:
    """Blocks the engine reserves for a request: its prompt plus one KV
    write per decode step (the last token emitted is never written)."""
    return max(1, math.ceil((prompt_len + max(n_out - 1, 0)) / block))


class OpenLoop:
    def __init__(self, engine, requests, *, admit_cap: int,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 annotate: Optional[Callable] = None):
        self.engine = engine
        self.requests = sorted(requests, key=lambda r: (r.due, r.rid))
        self.admit_cap = pow2_floor(admit_cap)
        self.clock = clock
        self.sleep = sleep
        self.annotate = annotate or (lambda name: nullcontext())
        self.records: dict = {}
        self.chunks: list = []
        self.admissions: list = []
        self.queue: collections.deque = collections.deque()
        self.tracing = False
        self.t_open = self.t_close = None

    # ------------------------------------------------------------ admission
    def _fits(self) -> int:
        eng = self.engine
        rows = eng.max_slots - eng.n_active
        free = eng.allocator.n_blocks - eng.allocator.reserved
        n = 0
        for req in self.queue:
            need = blocks_needed(len(req.prompt), req.n_out, eng.block_size)
            if n == rows or need > free:
                break
            free -= need
            n += 1
        return n

    def admit(self, group) -> float:
        """Admit ``group`` in one ``admit_many``; every request must fit."""
        t0 = self.clock()
        with self.annotate("chipbench.admit"):
            flags = self.engine.admit_many(
                [(r.rid, r.prompt, r.budget, r.answer) for r in group])
        t1 = self.clock()
        if not all(flags):
            raise RuntimeError(f"engine refused an admission that fits: "
                               f"{[r.rid for r in group]} -> {flags}")
        self.admissions.append(Admission(t0, t1, len(group), self.tracing))
        for r in group:
            rec = self.records[r.rid]
            rec.t_first = rec.t_last = t1
            rec.n_tokens = 1
            rec.arrivals.append((t1, 1))
        return t1

    def admit_ready(self) -> None:
        n = self._fits()
        while n:
            k = pow2_floor(min(n, self.admit_cap))
            self.admit([self.queue.popleft() for _ in range(k)])
            n -= k

    # --------------------------------------------------------------- decode
    def step(self) -> None:
        eng = self.engine
        before = {}
        row_steps = kv_tokens = 0
        for s in eng.slots:
            if s is None:
                continue
            before[s.rid] = s.generated
            n = max(0, min(eng.chunk, s.budget + s.max_extra - s.generated))
            row_steps += n
            kv_tokens += n * s.cache_len + n * (n + 1) // 2
        t0 = self.clock()
        with self.annotate("chipbench.step"):
            finished = eng.step_chunk()
        t1 = self.clock()
        with self.annotate("chipbench.host"):
            out = 0
            live = [s for s in eng.slots if s is not None] + list(finished)
            for s in live:
                n = s.generated - before[s.rid]
                rec = self.records[s.rid]
                if n:
                    rec.arrivals.append((t1, n))
                    rec.n_tokens += n
                    rec.t_last = t1
                    out += n
            for s in finished:
                self.records[s.rid].tokens = list(s.tokens)
            self.chunks.append(Chunk(t0, t1, eng.chunk, len(before),
                                     row_steps, kv_tokens, out,
                                     self.tracing))

    # ----------------------------------------------------------------- run
    def prime(self, requests) -> None:
        """Admit ``requests`` one at a time before the window opens (the
        rows a steady-state window starts on)."""
        for r in requests:
            self._record(r, due=self.clock())
            self.admit([r])

    def _record(self, r, due: float) -> None:
        self.records[r.rid] = Record(rid=r.rid, due=due, n_out=r.n_out)

    def run(self, window_s: float, *, follow_s: Optional[float],
            on_boundary: Optional[Callable] = None) -> None:
        """Serve requests due in ``[0, window_s)`` after now. With
        ``follow_s`` the loop then follows every request due in the window
        to completion, for at most ``follow_s`` more seconds; without it,
        the loop stops when the window closes."""
        t0 = self.t_open = self.clock()
        self.t_close = t0 + window_s
        stop = self.t_close + (follow_s or 0.0)
        pending = collections.deque(self.requests)
        while True:
            now = self.clock()
            if now >= stop:
                break
            if on_boundary is not None:
                on_boundary(self, now)
            while pending and t0 + pending[0].due <= now:
                r = pending.popleft()
                self._record(r, due=t0 + r.due)
                self.queue.append(r)
            if self.queue:
                self.admit_ready()
            if self.engine.n_active:
                self.step()
            elif pending:
                with self.annotate("chipbench.idle"):
                    self.sleep(max(0.0, t0 + pending[0].due - self.clock()))
            elif self.queue:
                raise RuntimeError("requests queued that no free engine "
                                   "could ever admit")
            else:
                break
