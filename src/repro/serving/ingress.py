"""Async ingress with continuous batching in front of the unified
pipeline (ROADMAP: "async request streams").

``ServingPipeline.serve`` is batch-at-a-time: the whole request set
arrives at once, runs stage by stage, and a tier sits idle while earlier
chunks decode. A real deployment sees a *stream* — requests arrive
individually or in small bursts, each with its own arrival time — and
the serving layer only pays off (paper §3.3) when tiers stay saturated.

This module closes that gap:

  * ``RequestState``  — one in-flight request: tokens, arrival time,
    the cascade position it is waiting on, accumulated cost, and
    per-request telemetry (queue wait, end-to-end latency, chunk count).
  * ``IngressQueue``  — arrival-ordered admission queue. Producers
    ``submit`` requests (optionally with an ``asyncio`` future that
    resolves when the request finishes); the batcher pops whatever has
    arrived by "now".
  * ``ContinuousBatcher`` — the admission loop. Each tick it (a) admits
    newly-arrived requests: cache lookup (per-admission embed + nearest
    neighbour) resolves hits immediately, misses enter tier 0's wait
    queue; (b) packs up to ``max_chunk`` waiting requests of ONE tier
    into the next chunk and runs it through ``repro.core.cascade.
    tier_step`` — the same compaction step the offline executor uses.
    New arrivals land in wait queues while earlier chunks are decoding,
    so a tier's next chunk is packed from everything waiting on it, not
    just the survivors of one closed batch.

Scheduling policy (classic continuous batching): a tier is dispatched
when its queue can fill a chunk, when its head-of-line waiter has aged
past the ``holdback`` window (so partial chunks still ship under light
load), or unconditionally once the stream is draining (queue closed,
nothing left to arrive). Among dispatchable tiers, overdue heads win
(oldest first), then the fullest queue — half-empty chunks cost the
same padded-bucket compute as full ones, so occupancy IS throughput.
Within a tier, requests are served FIFO. Chunks reuse the bucketed
``GenerationEngine`` shapes, so mixed-size chunks stay O(log) compiles.

``ContinuousBatcher`` dispatches one chunk at a time on ONE thread: it
is the serial reference implementation (and benchmark baseline) for the
SLO-aware parallel scheduler in ``repro.serving.sched``, which runs the
same admission stages and the same ``tier_step`` with one worker per
tier, deadline-driven holdback, and bounded-queue backpressure.
``serve_stream``/``aserve`` default to the parallel scheduler;
``parallel=False`` selects this batcher.

Equivalence guarantee (tested in tests/test_ingress.py): for a fixed
request set under greedy decoding — row-wise tier ``answer``/``scorer``
callables, which all repo tiers are — the continuous path returns
bit-identical answers and costs to ``ServingPipeline.serve``. Per-tier
costs are row-wise ``ApiCost`` terms and per-request cost is summed in
ascending tier order on float64 in both paths. The one deliberate
divergence: a duplicate query that *arrives after* its twin completes
hits the completion cache here, where ``serve`` (which looks up the
whole batch upfront) would miss — strictly fewer tier calls, never a
different answer for non-duplicates.
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
import heapq
import time
from typing import Iterator, Sequence

import numpy as np

from repro.core import telemetry
from repro.core.cascade import tier_step


def poisson_arrivals(n: int, rate: float, seed: int = 0) -> np.ndarray:
    """n arrival offsets (seconds) of a Poisson process at ``rate``/s —
    the shared trace generator for the stream CLI, example and bench."""
    if n < 0:
        raise ValueError(f"n must be >= 0, got {n}")
    if rate <= 0:
        raise ValueError(f"rate must be > 0 requests/s, got {rate}")
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def pad_pow2_rows(toks: np.ndarray) -> tuple[np.ndarray, int]:
    """Pad a burst/chunk to the next power-of-two row count by
    replicating the last row. Streams produce arbitrary batch sizes;
    jitted embed/scorer callables would otherwise recompile per
    distinct size, charging multi-second XLA compiles to per-request
    latency mid-stream. Row-wise callables make the padding exact —
    the filler rows are sliced off every output. Returns
    ``(padded, original_row_count)``."""
    b = len(toks)
    b_pad = 1
    while b_pad < b:
        b_pad *= 2
    if b_pad == b:
        return toks, b
    return np.concatenate([toks, np.repeat(toks[-1:], b_pad - b, 0)]), b


def stage1_lookup(pipeline, reqs, cache_lock=None, need_emb=False):
    """The admission stage both stream backends share: stack the burst's
    token rows, embed them (pow2-padded), and probe the completion
    cache. Returns ``(hit_mask, cached_answers, emb, embed_s, cache_s)``
    — ``emb`` is None when the pipeline has no cache, unless
    ``need_emb`` forces the embed anyway (the contextual router routes
    on embeddings even for cache-less pipelines). ``cache_lock``
    serializes the lookup against concurrent inserts (the parallel
    scheduler's workers); the embed call itself needs no lock (only the
    admission thread runs it)."""
    toks = np.stack([r.tokens for r in reqs])
    hit_mask = np.zeros(len(reqs), bool)
    cached = emb = None
    embed_s = cache_s = 0.0
    if pipeline.cache is not None or need_emb:
        padded, b = pad_pow2_rows(toks)
        t0 = time.perf_counter()
        emb = np.asarray(pipeline._block(pipeline.embed(padded)))[:b]
        embed_s = time.perf_counter() - t0
    if pipeline.cache is not None:
        t0 = time.perf_counter()
        pipeline._cache_refresh()   # governor-owned similarity threshold
        if cache_lock is not None:
            with cache_lock:
                hit_mask, cached = pipeline.cache.lookup(emb)
        else:
            hit_mask, cached = pipeline.cache.lookup(emb)
        cache_s = time.perf_counter() - t0
    return hit_mask, cached, emb, embed_s, cache_s


def fold_stream_result(pipeline, requests: Sequence[RequestState], *,
                       tier_counts: Sequence[int], cache_hits: int,
                       cache_misses: int, latency: dict, total_s: float,
                       ingress: dict, strategy: dict | None = None):
    """Fold a finished stream into a ``ServeResult`` bit-compatible with
    ``ServingPipeline.serve`` (answers/cost/stopped_at indexed by
    submission order) — shared by the serial ``ContinuousBatcher`` and
    the parallel ``repro.serving.sched.TierScheduler``. Requests shed by
    an overload policy appear with ``answer None`` / ``stopped_at -2`` /
    zero cost."""
    from repro.serving.pipeline import ServeResult, _merge_answers

    reqs = sorted(requests, key=lambda r: r.rid)
    undone = [r for r in reqs if not r.done]
    if undone:
        raise RuntimeError(f"{len(undone)} requests still in flight")
    n = len(reqs)
    cost = np.asarray([r.cost for r in reqs], np.float64)
    stopped = np.asarray([r.stopped_at for r in reqs], np.int32)
    vals = np.empty(n, dtype=object)          # keeps array answers intact
    for i, r in enumerate(reqs):
        vals[i] = r.answer
    answers = _merge_answers(n, [(np.arange(n), vals)])
    toks = (np.stack([r.tokens for r in reqs]) if n
            else np.zeros((0, 1), np.int32))
    lat = dict(latency)
    lat["total"] = total_s
    return ServeResult(
        answers=answers, cost=cost, stopped_at=stopped,
        tier_counts=list(tier_counts),
        tier_names=[s.name for s in pipeline.tiers],
        cache_hits=cache_hits, cache_misses=cache_misses,
        prompt_tokens_saved=pipeline._prompt_saved(tier_counts),
        baseline_cost=pipeline._baseline_cost(toks) if n else 0.0,
        latency=lat, ingress=ingress, strategy=strategy)


@dataclasses.dataclass
class RequestState:
    """One in-flight request and its telemetry."""

    rid: int                        # submission index == result row
    tokens: np.ndarray              # (L,) token row
    arrival: float = 0.0            # seconds since stream start
    tier_pos: int = -1              # cascade position waited on; -1 = none
    answer: object = None
    cost: float = 0.0
    stopped_at: int = -1            # cascade position; -1 = cache hit
    score: float = float("nan")     # accept-time reliability score
    deadline: float | None = None   # absolute SLO deadline (stream clock)
    shed: bool = False              # dropped by the overload policy
    degraded: bool = False          # overload-degraded (reduced entry bar)
    entry: int = 0                  # cascade entry position (router)
    pred_accept: float | None = None  # router's accept prob at the entry
    probs: np.ndarray | None = None   # (m,) per-tier accept probabilities
                                      # (router) — speculation candidates
    t_admitted: float | None = None
    t_done: float | None = None
    t_enqueued: float = 0.0         # entered the current tier's wait queue
    tier_wait: float = 0.0          # summed waits in tier queues, entered ->
                                    # popped into a chunk, over every tier
    n_chunks: int = 0               # tier chunks this request rode in
    emb: np.ndarray | None = None   # cache-stage embedding (misses only)
    future: asyncio.Future | None = None
    # failover fallback (repro.serving.resilience, populated only when
    # the scheduler runs resilient): the best-scoring answer an earlier
    # tier produced but the scorer rejected — served as a degraded
    # answer when every remaining tier is down
    fb_answer: object = None
    fb_score: float = float("-inf")
    fb_tier: int = -1
    # shadow audit (repro.serving.guarantee): a clone re-running a
    # served query on the reference tier. Shadow rows never resolve a
    # future, never count in tier_counts/fold_stream_result, and their
    # cost lands on the controller's shadow meter
    shadow: bool = False
    orig_answer: object = None      # the served answer being audited
    orig_stop: int = -1             # position the served answer came from

    @property
    def done(self) -> bool:
        return self.t_done is not None

    @property
    def latency(self) -> float | None:
        """End-to-end: arrival -> answer."""
        return None if self.t_done is None else self.t_done - self.arrival

    @property
    def queue_wait(self) -> float | None:
        """Arrival -> first admission (cache lookup)."""
        return (None if self.t_admitted is None
                else self.t_admitted - self.arrival)


class IngressQueue:
    """Arrival-ordered request queue feeding the continuous batcher.

    Requests submitted with an ``arrival`` offset (seconds since stream
    start) become visible to ``due`` once the batcher's clock passes it;
    ties pop in submission order. ``close()`` tells the batcher no
    further submissions are coming, so it can drain and stop.
    """

    def __init__(self):
        self._heap: list[tuple[float, int, RequestState]] = []
        self._n = 0
        self._width: int | None = None
        self.closed = False

    def submit(self, tokens, arrival: float = 0.0, *,
               with_future: bool = False,
               deadline: float | None = None) -> RequestState:
        """``deadline`` is an absolute SLO deadline on the stream clock
        (seconds); the scheduler's ``SLOConfig.deadline_s`` supplies a
        per-request default when None."""
        if self.closed:
            raise RuntimeError("queue is closed")
        tokens = np.asarray(tokens)
        # one stream = one token width, like serve's (n, L) matrix —
        # chunks np.stack rows, so a mismatch would crash deep in the
        # batcher; right-pad shorter queries with the pipeline pad token
        if self._width is None:
            self._width = tokens.shape[-1]
        elif tokens.shape[-1] != self._width:
            raise ValueError(
                f"token width {tokens.shape[-1]} != stream width "
                f"{self._width}; right-pad queries to a common width")
        r = RequestState(rid=self._n, tokens=tokens,
                         arrival=float(arrival), deadline=deadline)
        if with_future:
            r.future = asyncio.get_running_loop().create_future()
        heapq.heappush(self._heap, (r.arrival, r.rid, r))
        self._n += 1
        return r

    def submit_burst(self, tokens: np.ndarray,
                     arrivals: Sequence[float] | None = None,
                     **kw) -> list[RequestState]:
        """tokens (b, L); arrivals (b,) offsets (default: all at t=0)."""
        if arrivals is None:
            arrivals = np.zeros(len(tokens))
        if len(arrivals) != len(tokens):
            raise ValueError(f"{len(tokens)} token rows but "
                             f"{len(arrivals)} arrival times")
        return [self.submit(t, a, **kw) for t, a in zip(tokens, arrivals)]

    def close(self):
        self.closed = True

    def due(self, now: float) -> list[RequestState]:
        """Pop every request whose arrival time has passed."""
        out = []
        while self._heap and self._heap[0][0] <= now:
            out.append(heapq.heappop(self._heap)[2])
        return out

    def next_arrival(self) -> float | None:
        return self._heap[0][0] if self._heap else None

    def __len__(self) -> int:
        return len(self._heap)


class ContinuousBatcher:
    """Continuous-batching admission loop over a ``ServingPipeline``.

    Drives the pipeline's three stages per-admission / per-chunk instead
    of per-closed-batch; see the module docstring. One batcher serves
    one stream and is then consumed (``result()``); build a fresh one
    per trace. Per-request state (tokens + telemetry) is kept for the
    final ``result()`` fold, so an indefinitely-open ``serve_async``
    stream should be rotated onto a fresh batcher periodically rather
    than run unbounded.
    """

    #: cap on idle sleeps so a producer submitting "later" is never
    #: missed for long (seconds)
    IDLE_POLL = 0.02

    def __init__(self, pipeline, max_chunk: int | None = None,
                 holdback: float = 0.02):
        self.pipeline = pipeline
        self.max_chunk = int(pipeline.batch_size if max_chunk is None
                             else max_chunk)
        self.holdback = float(holdback)
        if self.max_chunk < 1:
            raise ValueError("max_chunk must be >= 1")
        m = len(pipeline.tiers)
        self._tiers = pipeline._cascade_tiers()
        self._waiting: list[collections.deque] = [collections.deque()
                                                  for _ in range(m)]
        self._requests: list[RequestState] = []   # all, by rid order seen
        self.tier_counts = [0] * m                # requests entering tier j
        self.chunks_per_tier = [0] * m
        self._fill: list[float] = []              # chunk occupancy fractions
        self.cache_hits = 0
        self.cache_misses = 0
        self.latency = {"embed": 0.0, "cache": 0.0, "cascade": 0.0,
                        "insert": 0.0}
        self.telemetry = telemetry.StreamTelemetry(m)

    _pad_rows = staticmethod(pad_pow2_rows)   # compat alias

    # -- admission: per-burst cache lookup ---------------------------------
    def admit(self, reqs: Sequence[RequestState], now: float):
        """Stage-1 a burst of new arrivals: embed + cache lookup; hits
        finish immediately, misses enter tier 0's wait queue."""
        if not reqs:
            return
        with telemetry.span(telemetry.ADMIT):
            hit_mask, cached, emb, embed_s, cache_s = stage1_lookup(
                self.pipeline, reqs)
        self.latency["embed"] += embed_s
        self.latency["cache"] += cache_s
        self.cache_hits += int(hit_mask.sum())
        self.cache_misses += int((~hit_mask).sum())
        for i, r in enumerate(reqs):
            r.t_admitted = now
            self._requests.append(r)
            if hit_mask[i]:
                r.answer = cached[i]
                r.stopped_at = -1
                self._finish(r, now)
            else:
                if emb is not None:
                    r.emb = emb[i]
                self._enqueue(r, 0, now)

    def _enqueue(self, r: RequestState, j: int, now: float):
        r.tier_pos = j
        r.t_enqueued = now
        self.tier_counts[j] += 1
        self._waiting[j].append(r)

    def _finish(self, r: RequestState, now: float):
        r.t_done = now
        if r.future is not None and not r.future.done():
            r.future.set_result(r)

    # -- dispatch policy ---------------------------------------------------
    def has_work(self) -> bool:
        return any(self._waiting)

    def _pick_tier(self, now: float, *, drain: bool) -> int | None:
        """Which tier gets the next chunk — or None to hold back and let
        partial chunks fill (occupancy is throughput: a half-empty chunk
        costs the same padded-bucket compute as a full one)."""
        cand = [j for j, q in enumerate(self._waiting) if q]
        if not cand:
            return None
        overdue = [j for j in cand
                   if now - self._waiting[j][0].t_enqueued >= self.holdback]
        if overdue:                       # aged heads win, oldest first
            return min(overdue, key=lambda j: self._waiting[j][0].rid)
        full = [j for j in cand if len(self._waiting[j]) >= self.max_chunk]
        if full:                          # then the fullest queue
            return max(full, key=lambda j: len(self._waiting[j]))
        if drain:                         # nothing else will ever arrive
            return max(cand, key=lambda j: (len(self._waiting[j]),
                                            -self._waiting[j][0].rid))
        return None

    def _hold_expiry(self, now: float) -> float:
        """Seconds until the oldest waiting head ages past ``holdback``."""
        heads = [q[0].t_enqueued for q in self._waiting if q]
        if not heads:
            return self.IDLE_POLL
        return max(min(heads) + self.holdback - now, 0.0)

    def step(self, j: int, clock) -> list[RequestState]:
        """Pack and run ONE chunk on tier ``j``; returns the requests
        finished by this chunk."""
        q = self._waiting[j]
        batch = [q.popleft() for _ in range(min(self.max_chunk, len(q)))]
        start = clock()
        for r in batch:
            r.tier_wait += start - r.t_enqueued
        toks, b = pad_pow2_rows(np.stack([r.tokens for r in batch]))
        pipe = self.pipeline
        last = j == len(self._tiers) - 1
        rec = telemetry.ChunkCounters()
        t0 = time.perf_counter()
        with telemetry.counting(rec), telemetry.span(
                telemetry.CHUNK, tier=j, rows=len(batch)):
            ans, cost, scores, accept = tier_step(
                self._tiers[j], toks, j, scorer=pipe._pos_scorer,
                threshold=None if last else pipe.thresholds[j], last=last)
        ans, cost, scores, accept = ans[:b], cost[:b], scores[:b], accept[:b]
        self.latency["cascade"] += time.perf_counter() - t0
        self.chunks_per_tier[j] += 1
        self._fill.append(len(batch) / self.max_chunk)
        now = clock()
        self.telemetry.fold(j, rec, batch, start, now)
        finished = []
        for i, r in enumerate(batch):
            r.n_chunks += 1
            r.cost += float(cost[i])
            if accept[i]:
                r.answer = ans[i]
                r.score = float(scores[i])
                r.stopped_at = j
                self._finish(r, now)
                finished.append(r)
            else:
                self._enqueue(r, j + 1, now)
        if pipe.cache is not None and finished:
            t0 = time.perf_counter()
            pipe._cache_insert(np.stack([r.emb for r in finished]),
                               np.asarray([r.answer for r in finished]),
                               np.asarray([r.score for r in finished]))
            for r in finished:              # the embedding served its
                r.emb = None                # purpose; don't retain it
            self.latency["insert"] += time.perf_counter() - t0
        return finished

    # -- drivers -----------------------------------------------------------
    def _ticks(self, queue: IngressQueue, clock) -> Iterator[float]:
        """The scheduling loop as a generator: runs admission + chunk
        steps inline and yields the seconds to sleep whenever idle; the
        sync/async drivers differ only in how they sleep. Terminates
        when the queue is closed and everything in flight has drained.
        """
        with telemetry.span(telemetry.STREAM):
            yield from self._tick_loop(queue, clock)

    def _tick_loop(self, queue: IngressQueue, clock) -> Iterator[float]:
        while True:
            self.admit(queue.due(clock()), clock())
            drain = queue.closed and len(queue) == 0
            j = self._pick_tier(clock(), drain=drain)
            if j is not None:
                self.step(j, clock)
                # zero-pause yield between chunks: the sync driver skips
                # it, the async driver hands the event loop to producers
                # so an open stream can keep submitting mid-backlog
                yield 0.0
                continue
            if self.has_work():            # holding back for chunk fill:
                now = clock()              # wake on arrival or age expiry
                pause = self._hold_expiry(now)
                nxt = queue.next_arrival()
                if nxt is not None:
                    pause = min(pause, max(nxt - now, 0.0))
                yield min(pause, self.IDLE_POLL)
                continue
            nxt = queue.next_arrival()
            if nxt is not None:
                yield min(max(nxt - clock(), 0.0), self.IDLE_POLL)
            elif queue.closed:
                return
            else:
                yield self.IDLE_POLL       # open stream, nothing due yet

    def run_trace(self, tokens: np.ndarray,
                  arrivals: Sequence[float] | None = None, *,
                  clock=None):
        """Synchronous trace replay: requests (rows of ``tokens``)
        become visible at their ``arrivals`` offsets on a wall clock,
        and the loop sleeps through genuinely idle gaps. An injected
        monotonic ``clock`` replaces the wall clock (tests; it must
        eventually pass every arrival offset or the trace never
        drains). Returns the folded ``ServeResult``."""
        self.telemetry.start()
        t_start = time.perf_counter()

        if clock is None:
            def clock() -> float:
                return time.perf_counter() - t_start

        queue = IngressQueue()
        queue.submit_burst(tokens, arrivals)
        queue.close()
        for pause in self._ticks(queue, clock):
            if pause > 0:
                time.sleep(pause)
        return self.result(clock())

    async def serve_async(self, queue: IngressQueue, clock=None):
        """Asyncio driver over an (optionally still-open) queue:
        producers may keep submitting — with ``with_future=True`` each
        request's future resolves the moment it finishes — until
        ``queue.close()`` lets the loop drain and return the folded
        ``ServeResult``."""
        self.telemetry.start()
        t_start = time.perf_counter()
        if clock is None:
            def clock() -> float:
                return time.perf_counter() - t_start
        for pause in self._ticks(queue, clock):
            # always yield control so producers can run, even at pause=0
            await asyncio.sleep(pause)
        return self.result(clock())

    # -- folding into ServeResult ------------------------------------------
    def stats(self) -> dict:
        """Ingress telemetry over every request seen so far."""
        done = [r for r in self._requests if r.done]
        lat = np.asarray([r.latency for r in done], np.float64)
        wait = np.asarray([r.queue_wait for r in done], np.float64)
        return {
            "request_latency": lat,
            "queue_wait": wait,
            "chunks_per_tier": list(self.chunks_per_tier),
            "chunk_occupancy": float(np.mean(self._fill)) if self._fill
            else 0.0,
            "n_chunks": int(sum(self.chunks_per_tier)),
            **self.telemetry.publish(done),
        }

    def result(self, total_s: float):
        """Fold the finished stream into a ``ServeResult`` bit-compatible
        with ``ServingPipeline.serve`` (answers/cost/stopped_at indexed
        by submission order)."""
        return fold_stream_result(
            self.pipeline, self._requests, tier_counts=self.tier_counts,
            cache_hits=self.cache_hits, cache_misses=self.cache_misses,
            latency=self.latency, total_s=total_s, ingress=self.stats())
