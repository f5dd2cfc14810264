"""SLO-aware parallel tier scheduler: concurrent per-tier workers over
the shared cascade step.

The serial ``ContinuousBatcher`` (``repro.serving.ingress``) dispatches
one chunk at a time on one thread: while tier 0 decodes, tier 1 sits
idle even when its queue could fill a chunk. This module replaces that
dispatch loop with one **worker thread per cascade tier**, all driving
the same ``repro.core.cascade.tier_step`` — so with >= 2 tiers backed by
real models, chunks decode concurrently and the cascade's wall clock
approaches the busiest tier's, not the sum of all tiers'.

Layering (the new layer sits between ingress and the cascade executor):

    IngressQueue  ->  TierScheduler (admission + per-tier workers)
                           |  tier_step (shared compaction step)
                           v
                      per-tier wait queues, escalation j -> j+1

Scheduling policy (``sched.policy``):

  * **adaptive holdback** — a tier ships a partial chunk when the
    head-of-line request's predicted completion (now + safety x EWMA
    service time, ``sched.estimator``) would miss its deadline, when the
    head has aged past ``max_holdback_s``, or when nothing upstream can
    ever top the chunk up (drain). Full chunks ship immediately.
  * **bounded queues + backpressure** — with ``queue_cap`` set,
    escalation into a full downstream queue blocks that tier's worker
    (escalations flow strictly forward, so blocking cannot deadlock);
    the stall propagates upstream until admission applies the overload
    policy: ``reject`` sheds arrivals, ``degrade`` admits them at a
    degraded entry — the cheapest tier whose *predicted* accept
    probability clears a reduced bar when a contextual router is
    attached, tier 0 otherwise — with the answer accepted regardless
    of score (the paper's cost/accuracy dial applied to load).

With a ``ServingStrategy`` on the pipeline (``repro.serving.strategy``)
the scheduler additionally routes each admitted miss to its predicted
entry tier, reads governor-adjusted thresholds at dispatch, and feeds
every finished request's cost back to the governor; with no strategy
every decision is bit-identical to the fixed cascade.

With per-tier device placement (``repro.sharding.placement``) each
tier's model is pinned to its own ``jax.Device`` (``TierSpec.device``),
so the workers' concurrent chunks decode on disjoint devices instead of
timesharing one — tier overlap is then limited by the tiers themselves,
not by a shared device queue. The pins are recorded in
``stats()["tier_devices"]``; placement never changes results
(tests/test_placement.py), only where they are computed.

With per-tier mesh slices (``repro.sharding.tier_mesh``,
``TierSpec.mesh``) each worker dispatches its chunks to its tier's
*slice* instead: the tier backend device_puts the compacted chunk
across the slice boundary (batch split over the slice's "data" axis)
and runs it as a pjit-sharded computation — same worker model, the
per-tier device becomes a per-tier device *set*, recorded in
``stats()["tier_meshes"]``. Data-parallel slices never change results
either (the sharded legs of tests/test_placement.py).

Concurrency contract (see ``tier_step``): each tier's ``invoke`` is
only ever entered by that tier's worker, so tier backends (e.g. a
``GenerationEngine``) need no internal locking — but two ``TierSpec``
entries must not share one stateful backend object. The pipeline's
shared scorer is serialized with a lock; completion-cache lookups
(admission thread) and inserts (workers) share another.

Equivalence guarantee (tests/test_sched.py, tests/test_ingress.py): for
a fixed request set under greedy decoding — row-wise tier ``answer``/
``scorer`` callables, which all repo tiers are — the parallel scheduler
returns bit-identical answers and per-request costs to
``ServingPipeline.serve``: a request's cost is still its own row-wise
``ApiCost`` terms summed in ascending tier order on float64, regardless
of which chunks it rode or what was decoding concurrently.

**Speculative cascade execution** (``SLOConfig.speculate``): a tier
worker with an empty queue may *pre-invoke* rows still decoding on
earlier tiers, picked by the contextual router's predicted-reject
probabilities (``policy.speculation_candidate``) under an idle-device
budget (``policy.may_speculate``). The speculative result is parked in
``_spec_ready``; if the row really escalates here, ``_run_chunk`` hands
it to ``tier_step(prefilled=...)`` — the cold invoke is skipped and the
tier's wall-clock overlaps the upstream decode — and if the row is
accepted upstream instead, the entry is cancelled and its device-seconds
count as waste. Scoring, the accept rule, escalation, and cost charging
all still run through the identical ``tier_step`` path on commit, and a
speculative chunk runs on the *same* worker thread as the tier's real
chunks (the one-thread-per-backend contract holds), so speculation can
only move wall-clock: answers, charged cost, ``stopped_at`` and
``tier_counts`` are bit-identical to ``speculate=False`` (the
speculative legs of tests/test_placement.py). The known tradeoff: a
real arrival during a speculative chunk waits for it to finish —
bounded by one chunk's service time, gated by the policy dials.

**Fault tolerance** (``repro.serving.resilience``): with
``SLOConfig.retry``/``SLOConfig.breaker`` set (or fault-injected tiers
wired in), a ``TierFault`` from an invoke is a *routing signal*, not a
crash. The invoke is retried under the bounded, deadline-aware
``RetryPolicy``; the final outcome feeds the tier's circuit breaker; and
a chunk whose tier still fails escalates forward — the cascade structure
IS the failover path. Rows waiting on a tier whose breaker is open skip
it without invoking (``_skip_open_tier_locked``); a failed *last* tier
resolves each row from the best-scoring answer an earlier tier produced
(a degraded answer) or as an accounted shed, so every admitted request
always resolves. A breaker trip cancels speculation parked against the
tier (and engine-level prefill futures via ``EnginePool.cancel_all``
when the pipeline exposes a pool). With no resilience dials the
TierFault path is structurally unreachable and the scheduler is
bit-identical to the pre-resilience one (the zero-fault legs of
tests/test_placement.py).

**Telemetry** (``repro.core.telemetry``): the served window runs under
the ``serve.stream`` span, each admission under ``sched.admit`` and each
chunk under ``sched.chunk``. Each chunk's worker opens a counter record
that the engine and ``tier_step`` add their host time to; the record is
folded into its tier's totals under the lock, with a chunk span record
and one visit record per request. ``stats()`` publishes them with each
answered request's summed tier-queue wait (``tier_wait``, entering a
tier's queue to its chunk being popped); the serial batcher publishes
the same keys.
"""
from __future__ import annotations

import asyncio
import collections
import threading
import time
from typing import Sequence

import numpy as np

from repro.core import telemetry
from repro.core.cascade import CascadeTier, tier_step
from repro.serving.ingress import (IngressQueue, RequestState,
                                   fold_stream_result, pad_pow2_rows,
                                   stage1_lookup)
from repro.serving.resilience import (FaultyTier, TierFault, TierHealth,
                                      invoke_with_retry)
from repro.serving.sched.estimator import TierEstimator
from repro.serving.sched.policy import (ADMIT, DEGRADE, SLOConfig,
                                        admit_decision, holdback_timeout,
                                        may_speculate, rank_speculation,
                                        speculation_candidate)


class TierScheduler:
    """Parallel, SLO-aware scheduler over a ``ServingPipeline``.

    One scheduler serves one stream and is then consumed (``result()``);
    build a fresh one per trace. Drop-in for ``ContinuousBatcher``:
    ``run_trace(tokens, arrivals)`` replays a closed trace,
    ``serve_async(queue)`` drives a live (possibly still-open)
    ``IngressQueue`` with per-request futures.
    """

    #: cap on idle waits so time-based triggers (holdback expiry,
    #: deadline pressure, late arrivals) are never missed for long
    IDLE_POLL = 0.02

    def __init__(self, pipeline, max_chunk: int | None = None,
                 slo: SLOConfig | None = None):
        self.pipeline = pipeline
        self.slo = slo or SLOConfig()
        self.max_chunk = int(pipeline.batch_size if max_chunk is None
                             else max_chunk)
        if self.max_chunk < 1:
            raise ValueError("max_chunk must be >= 1")
        m = len(pipeline.tiers)
        if m == 0:
            raise ValueError("pipeline has no tiers")
        self._tiers = pipeline._cascade_tiers()
        # contextual strategy (repro.serving.strategy): entry-tier
        # routing at admission, governor-adjusted thresholds at
        # dispatch, predicted-score degradation under overload; None
        # keeps every decision bit-identical to the fixed cascade
        self._strategy = pipeline.strategy
        # window-assignment routing (repro.serving.assign): admitted
        # misses are buffered into arrival windows and entry-routed by
        # the budgeted assignment solver at drain; the buffer is only
        # touched on the driver thread (admit + drain), enqueue happens
        # under the lock like every other path
        self._assign = (self._strategy is not None
                        and getattr(self._strategy, "mode", "entry")
                        == "assign")
        self._win_buf = None
        if self._assign:
            from repro.serving.assign import WindowBuffer
            self._win_buf = WindowBuffer(self._strategy.assigner.cfg)
        # accuracy guarantee (repro.serving.guarantee): finished rows
        # are shadow-sampled onto the reference (top) tier as clone
        # requests riding the normal worker machinery; None keeps the
        # request path structurally identical
        self._guarantee = (getattr(self._strategy, "guarantee", None)
                           if self._strategy is not None else None)
        self._shadow_rid = -1           # clone rids: negative, unique

        # one lock + condition guards every field below; chunk compute,
        # embedding and cache traffic all happen OUTSIDE it
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        self._scorer_mu = threading.Lock()   # shared scorer (tier_step)
        self._cache_mu = threading.Lock()    # lookup (admission) vs insert

        self._waiting: list[collections.deque] = [collections.deque()
                                                  for _ in range(m)]
        self._busy = [0] * m            # rows inside a running chunk
        self._inflight = 0              # admitted, not yet finished
        self._ingress_drained = False   # no further arrivals possible
        self._stop = False
        self._error: BaseException | None = None
        self._threads: list[threading.Thread] = []
        self._clock = None

        # telemetry (all under _mu)
        self._requests: list[RequestState] = []
        self.tier_counts = [0] * m
        self.chunks_per_tier = [0] * m
        self._fill: list[float] = []
        self.queue_peak = [0] * m
        self.estimators = [TierEstimator() for _ in range(m)]
        self.cache_hits = 0
        self.cache_misses = 0
        self.shed_count = 0
        self.degraded_count = 0
        self.deadline_hits = 0
        self.deadline_total = 0
        self.latency = {"embed": 0.0, "cache": 0.0, "cascade": 0.0,
                        "insert": 0.0}
        if self._assign:
            self.latency["assign"] = 0.0
        # per-tier host counters and chunk/visit span records
        # (repro.core.telemetry), folded under _mu
        self.telemetry = telemetry.StreamTelemetry(m)

        # speculation state (all under _mu; see module docstring).
        # _decoding[j]: rid -> request for rows inside tier j's running
        # chunk — the candidate pool downstream tiers speculate over.
        # _spec_ready[t]: rid -> (answer, cost, row_s) pre-invoked on
        # tier t, awaiting commit (row escalates to t) or cancel (row
        # accepted upstream). _spec_inflight[t]: rids being pre-invoked
        # right now. Every _spec_ready entry resolves: a row's position
        # only ever increases, so it either reaches t (consumed by
        # _take_speculation) or is accepted at some j < t with
        # t <= j + spec_depth (cancelled by _run_chunk's scan).
        self._decoding: list[dict] = [dict() for _ in range(m)]
        self._spec_ready: list[dict] = [dict() for _ in range(m)]
        self._spec_inflight: list[set] = [set() for _ in range(m)]
        self.spec_issued = 0        # rows pre-invoked
        self.spec_committed = 0     # rows whose pre-invoke was consumed
        self.spec_cancelled = 0     # rows pre-invoked in vain
        self.spec_wasted_s = 0.0    # device-seconds of cancelled rows
        self.spec_busy_s = [0.0] * m   # speculative busy time per tier
        self.spec_chunks = [0] * m

        # resilience (repro.serving.resilience): per-tier circuit
        # breakers, retry, and failover-past-failed-tier semantics.
        # _resilient is the single gate, and it is an explicit opt-in
        # (a retry or breaker dial): False keeps every code path —
        # including the TierFault catch in _run_chunk — structurally
        # identical to the pre-resilience scheduler, so disabled runs
        # stay bit-identical AND a fault-injected run without the dials
        # still crashes (the bench's no-resilience baseline).
        self._health = (TierHealth(m, self.slo.breaker)
                        if self.slo.breaker is not None else None)
        self._resilient = (self.slo.retry is not None
                           or self._health is not None)
        self._sleep = time.sleep    # no-op under an injected clock
        self.retry_count = 0        # failed attempts that were retried
        self.retry_backoff_s = 0.0  # added latency spent backing off
        self.failover_count = 0     # rows escalated past a failed tier
        self.fallback_count = 0     # last-tier failures answered from an
                                    # earlier tier's best-scoring answer
        self.res_shed = 0           # last-tier failures with no fallback
        self.spec_aborted = 0       # speculative invokes killed by faults

    # -- admission (driver thread) -----------------------------------------
    def _admit(self, reqs: Sequence[RequestState], now: float):
        """Stage-1 a burst of arrivals: embed + cache lookup (and, with
        a contextual router, entry-tier prediction) outside the lock;
        then, under it, resolve hits, apply the overload policy, and
        queue each admitted miss on its entry tier (tier 0 without a
        router — bit-identical to the fixed cascade)."""
        if not reqs:
            return
        strat = self._strategy
        routed = (strat is not None and not self._assign
                  and getattr(strat, "router", None) is not None)
        with telemetry.span(telemetry.ADMIT):
            hit_mask, cached, emb, embed_s, cache_s = stage1_lookup(
                self.pipeline, reqs, cache_lock=self._cache_mu,
                need_emb=routed or self._assign)
            entries = probs = None
            if routed:
                entries, probs = strat.route(emb)
        m = len(self._tiers)
        keep_emb = self.pipeline.cache is not None
        with self._cv:
            self.latency["embed"] += embed_s
            self.latency["cache"] += cache_s
            self.cache_hits += int(hit_mask.sum())
            self.cache_misses += int((~hit_mask).sum())
            for i, r in enumerate(reqs):
                r.t_admitted = now
                r.deadline = self.slo.deadline_for(r.arrival, r.deadline)
                self._requests.append(r)
                self._inflight += 1
                if hit_mask[i]:
                    r.answer = cached[i]
                    r.stopped_at = -1
                    self._finish_locked(r, now)
                    continue
                if self._assign:
                    # buffer into the arrival window; overload policy
                    # and enqueue happen at drain, once the solver has
                    # picked the entry tier (_drain_window)
                    r.emb = emb[i]
                    self._win_buf.add(r, now, deadline=r.deadline)
                    continue
                j0 = int(entries[i]) if entries is not None else 0
                verdict = admit_decision(
                    len(self._waiting[j0]), self.slo,
                    est=self.estimators[j0], now=now, deadline=r.deadline)
                if verdict == ADMIT or verdict == DEGRADE:
                    if verdict == DEGRADE:
                        # cost-aware degradation: cheapest tier whose
                        # predicted accept clears the reduced bar
                        # (tier 0 without a router, as before). The
                        # re-target must honour the hard 2x bound on
                        # ITS queue too — degrading into a different
                        # tier must not create an unbounded queue.
                        j0 = (strat.degrade_entry(probs[i], m)
                              if probs is not None else 0)
                        cap = self.slo.queue_cap
                        if (cap is not None
                                and len(self._waiting[j0]) >= 2 * cap):
                            r.shed = True
                            r.stopped_at = -2
                            self.shed_count += 1
                            self._finish_locked(r, now)
                            continue
                        r.degraded = True
                        self.degraded_count += 1
                    r.entry = j0
                    if probs is not None:
                        r.pred_accept = float(probs[i, j0])
                        r.probs = probs[i]  # speculation candidates read
                                            # the full per-tier vector
                    if keep_emb:            # only queued misses keep the
                        r.emb = emb[i]      # embedding (insert-on-finish);
                    self._enqueue_locked(r, j0, now)
                else:                       # shed: nothing to insert, so
                    r.shed = True           # don't pin the row for the
                    r.stopped_at = -2       # scheduler's lifetime
                    self.shed_count += 1
                    self._finish_locked(r, now)
            self._cv.notify_all()

    # -- window assignment (driver thread; see repro.serving.assign) -------
    def _window_pressure(self) -> float:
        """Seconds of slack the window must leave before its earliest
        deadline: the safety-scaled predicted service of the whole
        cascade chain (conservative — a drained query may still have to
        climb every tier), so holding an arrival for its window never
        pushes it past an SLO deadline the chain could have met."""
        svc = sum(e.predicted_service() for e in self.estimators)
        return self.slo.service_safety * svc

    def _drain_window(self, now: float, force: bool = False):
        """Drain every currently-due window (a burst that outgrew one
        window drains as several). ``force`` flushes the partial
        remainder once ingress has drained — nothing will top it up."""
        buf = self._win_buf
        while buf is not None and len(buf):
            if not force and not buf.due(now, self._window_pressure()):
                return
            self._solve_window(buf.drain(buf.cfg.window_size), now)

    def _solve_window(self, items: list, now: float):
        """Score + solve ONE arrival window and enqueue the results.
        Runs on the driver thread; scoring and the solver stay outside
        the lock (like stage-1 embed/cache traffic). Shed/degrade still
        apply, per assigned tier, at enqueue time."""
        strat, asg = self._strategy, self._strategy.assigner
        emb_w = np.stack([r.emb for r in items])
        toks = np.stack([r.tokens for r in items])
        t0 = time.perf_counter()
        util = ([e.utilization(now) for e in self.estimators]
                if now > 0 else None)
        res = asg.assign(emb_w, self.pipeline._tier_prices(toks),
                         governor=strat.governor, utilization=util)
        probs = asg.meta.accept_probs(emb_w)
        solve_s = time.perf_counter() - t0
        m = len(self._tiers)
        keep_emb = self.pipeline.cache is not None
        with self._cv:
            self.latency["assign"] += solve_s
            for i, r in enumerate(items):
                if not keep_emb:
                    r.emb = None
                j0 = int(res["assignment"][i])
                verdict = admit_decision(
                    len(self._waiting[j0]), self.slo,
                    est=self.estimators[j0], now=now, deadline=r.deadline)
                if verdict == ADMIT or verdict == DEGRADE:
                    if verdict == DEGRADE:
                        # cost-aware degradation off the meta-model's
                        # accept probabilities (router-compatible)
                        j0 = strat.degrade_entry(probs[i], m)
                        cap = self.slo.queue_cap
                        if (cap is not None
                                and len(self._waiting[j0]) >= 2 * cap):
                            r.shed = True
                            r.stopped_at = -2
                            self.shed_count += 1
                            self._finish_locked(r, now)
                            continue
                        r.degraded = True
                        self.degraded_count += 1
                    r.entry = j0
                    r.pred_accept = float(probs[i, j0])
                    r.probs = probs[i]
                    self._enqueue_locked(r, j0, now)
                else:
                    r.shed = True
                    r.stopped_at = -2
                    self.shed_count += 1
                    self._finish_locked(r, now)
            self._cv.notify_all()

    def _enqueue_locked(self, r: RequestState, j: int, now: float):
        r.tier_pos = j
        r.t_enqueued = now
        if not r.shadow:        # tier_counts reflect service traffic only
            self.tier_counts[j] += 1
        q = self._waiting[j]
        q.append(r)
        if len(q) > self.queue_peak[j]:
            self.queue_peak[j] = len(q)

    def _finish_shadow_locked(self, r: RequestState, now: float):
        """A shadow clone came back from the reference tier: fold the
        comparison into the guarantee controller (cost on the shadow
        meter) and feed the online router retrainer's shadow label at
        the audited stopping position. Clones lost to faults/overload
        abort cleanly — no observation, no telemetry pollution."""
        r.t_done = now
        self._inflight -= 1
        guar = self._guarantee
        if guar is None:
            return
        if r.shed or r.answer is None:
            r.emb = None
            guar.abort()
            return
        agree = bool(np.all(np.asarray(r.answer == r.orig_answer)))
        guar.observe(0.0 if agree else 1.0, r.cost, invoked=True)
        rt = getattr(guar, "retrainer", None)
        if rt is not None and r.emb is not None:
            rt.observe(r.emb, int(r.orig_stop), agree)
            rt.maybe_step()
        r.emb = None

    def _finish_locked(self, r: RequestState, now: float):
        if r.shadow:
            self._finish_shadow_locked(r, now)
            return
        r.t_done = now
        self._inflight -= 1
        if r.deadline is not None and not r.shed:
            self.deadline_total += 1
            if now <= r.deadline:
                self.deadline_hits += 1
        if self._strategy is not None and not r.shed:
            if r.stopped_at == -1:          # cache hit: zero-cost serve
                self._strategy.observe_request(r.cost)
            elif r.degraded:                # forced accept: no signal for
                self._strategy.observe_request(r.cost, entry=r.entry)
            else:                           # the accept-rate telemetry
                self._strategy.observe_request(
                    r.cost, entry=r.entry, pred=r.pred_accept,
                    accepted=(r.stopped_at == r.entry))
            if self._assign and r.stopped_at >= 0:
                # realized counterpart of the window solver's prediction
                self._strategy.assigner.observe(
                    [r.cost], [r.stopped_at == r.entry])
        guar = self._guarantee
        if guar is not None and not r.shed and r.stopped_at >= 0:
            top = len(self._tiers) - 1
            rt = getattr(guar, "retrainer", None)
            if (rt is not None and r.emb is not None
                    and not r.degraded and r.pred_accept is not None
                    and r.entry != top):
                # realized accept at the routed entry as an online label
                # (final position is supervised by shadow agreement
                # only — entering there accepts unconditionally)
                rt.observe(r.emb, int(r.entry), r.stopped_at == r.entry)
                rt.maybe_step()
            if guar.should_sample():
                if r.stopped_at == top:
                    # the served answer IS the reference answer: a free
                    # zero-gap observation, no invoke
                    guar.observe(0.0, 0.0, invoked=False)
                else:
                    cap = self.slo.queue_cap
                    if (cap is not None
                            and len(self._waiting[top]) >= cap):
                        guar.abort()    # overload sheds the audit, never
                    else:               # the service traffic
                        sh = RequestState(
                            rid=self._shadow_rid, tokens=r.tokens,
                            arrival=r.arrival, shadow=True,
                            orig_answer=r.answer,
                            orig_stop=r.stopped_at, emb=r.emb)
                        self._shadow_rid -= 1
                        self._inflight += 1
                        self._enqueue_locked(sh, top, now)
            r.emb = None
        if r.future is not None:
            # workers are plain threads: hand resolution to the loop
            r.future.get_loop().call_soon_threadsafe(
                lambda f=r.future, rr=r: f.done() or f.set_result(rr))

    # -- governor dials ----------------------------------------------------
    def _governor(self):
        strat = self._strategy
        return getattr(strat, "governor", None) if strat is not None else None

    def _effective_chunk(self) -> int:
        """Chunk-size cap with the budget governor's dial applied:
        overspend grows chunks (fuller buckets, better amortization),
        spare budget shrinks them (lower holdback latency). Read at
        each dispatch decision — racing a governor window update just
        means this decision uses the previous window's dial."""
        gov = self._governor()
        return self.max_chunk if gov is None else gov.max_chunk(
            self.max_chunk)

    def _effective_holdback(self) -> float | None:
        """Holdback-window override from the governor's dial (None =
        use the SLOConfig window unchanged)."""
        gov = self._governor()
        return None if gov is None else gov.holdback_s(
            self.slo.max_holdback_s)

    # -- dispatch decision (under _mu) -------------------------------------
    def _upstream_quiet(self, j: int) -> bool:
        """Nothing can ever flow into tier j again: ingress is drained
        and every earlier tier is empty and idle."""
        if not self._ingress_drained:
            return False
        return all(not self._waiting[i] and self._busy[i] == 0
                   for i in range(j))

    def _next_chunk_locked(self, j: int, now: float):
        """(batch, wait_s): the chunk tier j should run now, or the
        seconds to wait before re-deciding (None = nothing queued)."""
        q = self._waiting[j]
        if not q:
            return None, None
        if len(q) >= self._effective_chunk():
            return self._pop_locked(j, now), 0.0
        wait = holdback_timeout(q[0], self.estimators[j], now, self.slo,
                                max_holdback_s=self._effective_holdback())
        if wait <= 0.0 or self._upstream_quiet(j):
            return self._pop_locked(j, now), 0.0
        return None, wait

    def _pop_locked(self, j: int, now: float) -> list[RequestState]:
        q = self._waiting[j]
        batch = [q.popleft()
                 for _ in range(min(self._effective_chunk(), len(q)))]
        for r in batch:
            self.estimators[j].observe_wait(now - r.t_enqueued)
            r.tier_wait += now - r.t_enqueued
        self._busy[j] += len(batch)
        if self.slo.speculate:
            # expose the chunk as downstream speculation candidates for
            # the duration of the decode (cleared in _run_chunk)
            self._decoding[j] = {r.rid: r for r in batch}
        self._cv.notify_all()       # wake workers blocked on a full queue
        return batch

    # -- speculation (see module docstring) --------------------------------
    def _next_speculation_locked(self, t: int, now: float):
        """Rows tier ``t``'s idle worker should pre-invoke now, or None.
        Only consulted when tier t has no real chunk to run; real work
        always wins. Candidates are rows decoding at positions within
        ``spec_depth`` upstream whose router probabilities predict
        rejection all the way here (cold router: every row qualifies),
        excluding rows already speculated on and degraded rows (their
        forced accept upstream makes the pre-invoke guaranteed waste),
        gated by the idle budget with the tier's EWMA-predicted chunk
        time counted up front."""
        if t == 0 or not self.slo.speculate or self._waiting[t]:
            return None
        if self._health is not None and not self._health.available(t, now):
            return None         # never speculate against a tripped tier
        predicted = self.estimators[t].predicted_service(
            self.slo.init_service_s)
        if not may_speculate(self.slo, self.spec_wasted_s, now,
                             predicted_s=predicted):
            return None
        cap = self._effective_chunk()
        rows, pos = [], []
        for i in range(max(0, t - self.slo.spec_depth), t):
            for r in self._decoding[i].values():
                if (r.rid in self._spec_ready[t]
                        or r.rid in self._spec_inflight[t]
                        or r.degraded):
                    continue
                if not speculation_candidate(r.probs, i, t,
                                             self.slo.spec_bar):
                    continue
                rows.append(r)
                pos.append(i)
        if not rows:
            return None
        # idle budget covers one chunk: when more rows qualify, keep
        # the best by expected value (router reject-probability product
        # x predicted service) — queue order only breaks EV ties, so the
        # cold-router path selects exactly what it did before ranking
        rows = rank_speculation(rows, pos, t, predicted, cap)
        for r in rows:
            self._spec_inflight[t].add(r.rid)
        self.spec_issued += len(rows)
        return rows

    def _run_speculation(self, t: int, rows: list[RequestState]):
        """Pre-invoke tier t on ``rows`` (no scheduler lock held) and
        park the per-row (answer, cost) for commit. Runs on tier t's own
        worker thread — the same thread that runs its real chunks — so
        the one-invoke-at-a-time backend contract holds. Rows that were
        accepted upstream while we were invoking are cancelled here."""
        toks, b = pad_pow2_rows(np.stack([r.tokens for r in rows]))
        rec = telemetry.ChunkCounters()
        t0 = time.perf_counter()
        try:
            with telemetry.counting(rec), telemetry.span(
                    telemetry.CHUNK, tier=t, rows=len(rows), speculative=1):
                a, c = self._tiers[t].invoke(toks)
        except TierFault:
            # speculation is opportunistic — no retries, just release
            # the rows (they stay eligible for the real escalation
            # path) and feed the breaker its free failure signal
            with self._cv:
                self.telemetry.fold(t, rec)
                self.spec_aborted += len(rows)
                self.spec_issued -= len(rows)
                for r in rows:
                    self._spec_inflight[t].discard(r.rid)
                self._cv.notify_all()
            if (self._health is not None
                    and self._health.record(t, False, self._clock())):
                self._on_trip(t)
            return
        spent = time.perf_counter() - t0
        if self._health is not None:
            self._health.record(t, True, self._clock())
        a = np.asarray(a)[:b]
        c = np.asarray(c, np.float64)[:b]
        row_s = spent / len(rows)
        with self._cv:
            self.telemetry.fold(t, rec)
            self.spec_busy_s[t] += spent
            self.spec_chunks[t] += 1
            for i, r in enumerate(rows):
                self._spec_inflight[t].discard(r.rid)
                if r.done:          # accepted upstream mid-invoke
                    self.spec_cancelled += 1
                    self.spec_wasted_s += row_s
                else:
                    self._spec_ready[t][r.rid] = (a[i], float(c[i]), row_s)
            self._cv.notify_all()

    def _take_speculation(self, j: int, batch: list[RequestState],
                          padded: int, b: int):
        """Collect parked speculative results for this real chunk as the
        ``tier_step(prefilled=...)`` triple, or None when no row of the
        chunk was speculated on. The pow2 filler rows replicate the last
        true row (``pad_pow2_rows``), so its prefilled answer/cost are
        replicated onto them too — keeping the padded invoke exact."""
        with self._mu:
            ready = self._spec_ready[j]
            hits = [(i, ready.pop(r.rid)) for i, r in enumerate(batch)
                    if r.rid in ready]
            if not hits:
                return None
            self.spec_committed += len(hits)
        mask = np.zeros(padded, bool)
        pa = np.empty(padded, object)
        pc = np.zeros(padded, np.float64)
        for i, (ans, cost, _row_s) in hits:
            mask[i] = True
            pa[i] = ans
            pc[i] = cost
        if mask[b - 1]:
            mask[b:] = True
            for k in range(b, padded):
                pa[k] = pa[b - 1]
            pc[b:] = pc[b - 1]
        return mask, pa, pc

    # -- resilience: retry, breaker feed, failover -------------------------
    def _resilient_tier(self, j: int, deadline: float | None,
                        meta: dict) -> CascadeTier:
        """Tier j's invoke wrapped with the retry policy (bounded,
        deadline-aware, deterministic backoff jitter) and breaker
        outcome recording. ``meta`` accumulates the chunk's retry count
        and backoff seconds for telemetry; the breaker sees the *final*
        outcome of each invoke (an invoke that succeeds on retry is a
        success — the window measures availability, not flakiness)."""
        inner = self._tiers[j]
        pol = self.slo.retry

        def call(chunk):
            fails = [0]

            def _fail(_attempt, _exc):
                fails[0] += 1

            try:
                if pol is None:
                    try:
                        a, c = inner.invoke(chunk)
                    except TierFault as e:
                        _fail(0, e)
                        raise
                    attempts = 1
                else:
                    predicted = self.estimators[j].predicted_service(
                        self.slo.init_service_s)

                    def _waited(w):
                        # per-backoff credit: terminally-failed chunks
                        # keep their wasted backoff seconds too
                        meta["backoff"] += w

                    a, c, attempts, _ = invoke_with_retry(
                        inner, chunk, pol, clock=self._clock,
                        sleep=self._sleep, deadline=deadline,
                        predicted_s=predicted, token=j,
                        on_attempt_fail=_fail, on_backoff=_waited)
            except TierFault:
                meta["retries"] += max(0, fails[0] - 1)
                if (self._health is not None
                        and self._health.record(j, False, self._clock())):
                    self._on_trip(j)
                raise
            meta["retries"] += attempts - 1
            if self._health is not None:
                self._health.record(j, True, self._clock())
            return a, c

        return CascadeTier(inner.name, call)

    def _on_trip(self, t: int):
        """Tier t's breaker tripped: in-flight speculation against it is
        dead weight. Drop its parked speculative results (counted as
        cancelled waste) and cancel engine-level prefill futures through
        the pool's existing ``cancel_all`` when the pipeline exposes
        one."""
        with self._cv:
            for _a, _c, row_s in self._spec_ready[t].values():
                self.spec_cancelled += 1
                self.spec_wasted_s += row_s
            self._spec_ready[t].clear()
            self._cv.notify_all()
        pool = getattr(self.pipeline, "engine_pool", None)
        if pool is not None:
            pool.cancel_all()

    def _resolve_failed_locked(self, r: RequestState, now: float):
        """The last reachable tier failed for this row: serve the
        best-scoring answer an earlier tier produced (a degraded answer
        — availability over accuracy), or account the row as shed when
        no tier ever answered it."""
        if r.shadow:
            # a failed audit clone is silently aborted: no fallback, no
            # shed/degraded accounting — shadow traffic is measurement
            r.shed = True
            self._finish_locked(r, now)
            return
        if r.fb_tier >= 0:
            r.answer = r.fb_answer
            r.score = r.fb_score
            r.stopped_at = r.fb_tier
            r.degraded = True
            self.fallback_count += 1
            self.degraded_count += 1
        else:
            r.shed = True
            r.stopped_at = -2
            self.res_shed += 1
            self.shed_count += 1
        self._finish_locked(r, now)

    def _failover_chunk(self, j: int, batch: list[RequestState],
                        prefilled, meta: dict,
                        rec: telemetry.ChunkCounters):
        """Tier j failed this chunk even after retries: escalate the
        rows forward — the cascade structure IS the failover path — or,
        at the last tier, resolve each row from its recorded fallback
        (or as an accounted shed). The failed invoke returned no
        answers, so nothing is charged for tier j itself."""
        clock = self._clock
        last = j == len(self._tiers) - 1
        now = clock()
        with self._cv:
            self.telemetry.fold(j, rec)     # the work done, not a chunk
            self.retry_count += meta["retries"]
            self.retry_backoff_s += meta["backoff"]
            self.failover_count += len(batch)
            if self.slo.speculate:
                self._decoding[j] = {}
                if prefilled is not None:
                    # pre-invokes consumed by this chunk died with it:
                    # they were counted committed in _take_speculation
                    n_hit = int(np.asarray(
                        prefilled[0], bool)[:len(batch)].sum())
                    self.spec_committed -= n_hit
                    self.spec_cancelled += n_hit
            if last:
                for r in batch:
                    self._resolve_failed_locked(r, now)
            else:
                cap = self.slo.queue_cap
                for r in batch:
                    while (cap is not None
                           and len(self._waiting[j + 1]) >= cap
                           and not self._stop):
                        self._cv.notify_all()
                        self._cv.wait(self.IDLE_POLL)
                    self._enqueue_locked(r, j + 1, clock())
            self._busy[j] -= len(batch)
            self._cv.notify_all()

    def _skip_open_tier_locked(self, j: int, now: float):
        """Tier j's breaker is open: rows waiting on it skip the tier
        and escalate to j+1 (forward-only, no invoke, nothing charged).
        Called with the scheduler lock held, from tier j's own worker.
        The last tier never skips — its worker instead waits out the
        cooldown and lets the half-open probe chunk through (a failed
        probe resolves via the failover path), so a recovering top tier
        starts answering again without a full outage window of sheds."""
        rows = list(self._waiting[j])
        self._waiting[j].clear()
        self._busy[j] += len(rows)      # drain detection holds off
        self.failover_count += len(rows)
        cap = self.slo.queue_cap
        for r in rows:
            while (cap is not None and len(self._waiting[j + 1]) >= cap
                   and not self._stop):
                self._cv.notify_all()
                self._cv.wait(self.IDLE_POLL)
            self._enqueue_locked(r, j + 1, self._clock())
        self._busy[j] -= len(rows)
        self._cv.notify_all()

    @staticmethod
    def _batch_deadline(batch: list[RequestState]) -> float | None:
        """The chunk's binding SLO deadline: the earliest row deadline —
        a retry that would push past it serves nobody in the chunk on
        time."""
        return min((r.deadline for r in batch if r.deadline is not None),
                   default=None)

    # -- the per-tier worker ----------------------------------------------
    def _run_chunk(self, j: int, batch: list[RequestState]):
        """Execute one chunk on tier j (no scheduler lock held)."""
        pipe = self.pipeline
        clock = self._clock
        last = j == len(self._tiers) - 1
        # the governor retunes thresholds between windows: read the
        # current set at dispatch (a plain tuple swap — racing an update
        # just means this chunk uses the previous window's thresholds)
        thresholds = (self._strategy.thresholds(pipe.thresholds)
                      if self._strategy is not None else pipe.thresholds)
        toks, b = pad_pow2_rows(np.stack([r.tokens for r in batch]))
        prefilled = (self._take_speculation(j, batch, len(toks), b)
                     if self.slo.speculate else None)
        meta = {"retries": 0, "backoff": 0.0}
        tier = (self._resilient_tier(j, self._batch_deadline(batch), meta)
                if self._resilient else self._tiers[j])
        rec = telemetry.ChunkCounters()
        start = clock()
        t0 = time.perf_counter()
        try:
            with telemetry.counting(rec), telemetry.span(
                    telemetry.CHUNK, tier=j, rows=len(batch)):
                ans, cost, scores, accept = tier_step(
                    tier, toks, j, scorer=pipe._pos_scorer,
                    threshold=None if last else thresholds[j], last=last,
                    scorer_lock=self._scorer_mu, prefilled=prefilled)
        except TierFault:
            if not self._resilient:     # no resilience layer: fatal, as
                raise                   # any tier exception always was
            self._failover_chunk(j, batch, prefilled, meta, rec)
            return
        ans, cost, scores, accept = (ans[:b], cost[:b], scores[:b],
                                     accept[:b])
        chunk_s = time.perf_counter() - t0
        now = clock()
        finished, escalate, cacheable = [], [], []
        for i, r in enumerate(batch):
            r.n_chunks += 1
            r.cost += float(cost[i])
            # a degraded request takes the cheapest tier's answer even
            # when the scorer would escalate it (overload trades
            # accuracy, not availability)
            if accept[i] or r.degraded:
                r.answer = ans[i]
                r.score = float(scores[i])
                r.stopped_at = j
                finished.append(r)
                # never cache an answer the scorer rejected: a forced
                # degraded answer would otherwise be served to future
                # near-duplicates long after the overload has passed
                # (nor a shadow clone — its answer audits, not serves)
                if accept[i] and not r.shadow:
                    cacheable.append(r)
            else:
                if self._resilient:
                    # remember the best-scoring rejected answer: the
                    # failover fallback if every remaining tier is down
                    s_i = float(scores[i])
                    if s_i > r.fb_score:
                        r.fb_answer, r.fb_score, r.fb_tier = ans[i], s_i, j
                escalate.append(r)
        insert_s = 0.0
        if pipe.cache is not None and cacheable:
            t0 = time.perf_counter()
            with self._cache_mu:
                pipe._cache_insert(
                    np.stack([r.emb for r in cacheable]),
                    np.asarray([r.answer for r in cacheable]),
                    np.asarray([r.score for r in cacheable]))
            insert_s = time.perf_counter() - t0
        # the embedding served its cache purpose — but the guarantee's
        # online retrainer still consumes it as a label feature in
        # _finish_locked, which clears it after use
        if (self._guarantee is None
                or getattr(self._guarantee, "retrainer", None) is None):
            for r in finished:
                r.emb = None
        m = len(self._tiers)
        with self._cv:
            # before the escalations below move t_enqueued on
            self.telemetry.fold(j, rec, batch, start, now)
            self.retry_count += meta["retries"]
            self.retry_backoff_s += meta["backoff"]
            self.estimators[j].observe_chunk(chunk_s, len(batch))
            self.chunks_per_tier[j] += 1
            self._fill.append(len(batch) / self.max_chunk)
            self.latency["cascade"] += chunk_s   # summed busy time: with
            self.latency["insert"] += insert_s   # parallel tiers this can
            if self.slo.speculate:               # exceed wall clock
                self._decoding[j] = {}
            for r in finished:
                self._finish_locked(r, now)
                if self.slo.speculate:
                    # the row stops here: cancel any speculation parked
                    # for it downstream (targets can only be within
                    # spec_depth of some earlier position <= j)
                    hi = min(j + self.slo.spec_depth, m - 1)
                    for t2 in range(j + 1, hi + 1):
                        hit = self._spec_ready[t2].pop(r.rid, None)
                        if hit is not None:
                            self.spec_cancelled += 1
                            self.spec_wasted_s += hit[2]
            # bounded escalation: block (releasing the lock) while the
            # downstream queue is full — strictly forward flow, so this
            # backpressure cannot deadlock; _busy[j] stays raised until
            # the handoff completes so drain detection holds off
            cap = self.slo.queue_cap
            for r in escalate:
                while (cap is not None
                       and len(self._waiting[j + 1]) >= cap
                       and not self._stop):
                    self._cv.notify_all()
                    self._cv.wait(self.IDLE_POLL)
                self._enqueue_locked(r, j + 1, clock())
            self._busy[j] -= len(batch)
            self._cv.notify_all()

    def _worker(self, j: int):
        clock = self._clock
        last = j == len(self._tiers) - 1
        try:
            while True:
                spec = None
                with self._cv:
                    batch = None
                    while batch is None:
                        if self._stop:
                            return
                        now = clock()
                        if (self._health is not None and self._waiting[j]
                                and not self._health.available(j, now)):
                            if not last:    # open breaker: route past it
                                self._skip_open_tier_locked(j, now)
                                continue
                            # last tier: wait out the cooldown — the
                            # half-open probe (or its failover) resolves
                            self._cv.wait(self.IDLE_POLL)
                            continue
                        batch, wait = self._next_chunk_locked(j, now)
                        if batch is not None:
                            break
                        # idle: maybe burn the wait on speculation —
                        # real work always wins the next loop iteration
                        spec = self._next_speculation_locked(j, now)
                        if spec is not None:
                            break
                        timeout = (self.IDLE_POLL if wait is None else
                                   min(max(wait, 1e-4), self.IDLE_POLL))
                        self._cv.wait(timeout)
                if batch is not None:
                    self._run_chunk(j, batch)
                elif spec is not None:
                    self._run_speculation(j, spec)
        except BaseException as e:         # surface worker crashes to the
            with self._cv:                 # driver instead of hanging it
                self._error = e
                self._stop = True
                self._fail_pending_locked(e)
                self._cv.notify_all()

    def _fail_pending_locked(self, exc: BaseException):
        """A worker died: no chunk will ever finish the admitted
        requests still in flight, so fail their futures NOW — a caller
        awaiting one would otherwise hang past the driver's next poll
        (and forever, once the driver re-raised and stopped polling)."""
        for r in self._requests:
            if not r.done and r.future is not None and not r.future.done():
                try:
                    r.future.get_loop().call_soon_threadsafe(
                        lambda f=r.future, e=exc: f.done()
                        or f.set_exception(e))
                except RuntimeError:        # event loop already closed
                    pass

    # -- drivers -----------------------------------------------------------
    def _start(self, clock):
        if self._threads:
            raise RuntimeError("scheduler already started; build a fresh "
                               "TierScheduler per stream")
        self._clock = clock
        for t in self._tiers:               # wire the stream clock into
            if isinstance(t, FaultyTier):   # fault windows and spikes
                t.clock = clock
                t.sleep = self._sleep
        for j in range(len(self._tiers)):
            t = threading.Thread(target=self._worker, args=(j,),
                                 name=f"tier-worker-{j}", daemon=True)
            t.start()
            self._threads.append(t)

    def _shutdown(self):
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=10.0)

    async def serve_async(self, queue: IngressQueue, clock=None):
        """Asyncio driver over an (optionally still-open) queue:
        producers may keep submitting — with ``with_future=True`` each
        request's future resolves the moment it finishes — until
        ``queue.close()`` lets the stream drain. Returns the folded
        ``ServeResult``."""
        self.telemetry.start()
        t_start = time.perf_counter()
        if clock is None:
            def clock() -> float:
                return time.perf_counter() - t_start
        else:
            # an injected clock owns time: backoff and latency-spike
            # waits are recorded in the telemetry, not slept — the test
            # (or its fake clock) advances time itself
            self._sleep = lambda _s: None
        self._start(clock)
        try:
            with telemetry.span(telemetry.STREAM):
                await self._drive(queue, clock)
        finally:
            self._shutdown()
        if self._error is not None:
            raise self._error
        return self.result(clock())

    async def _drive(self, queue: IngressQueue, clock):
        """The admission loop: admit what is due, hand drain state to the
        workers, and return once the stream has drained (or a worker
        died)."""
        while True:
            now = clock()
            self._admit(queue.due(now), now)
            drained = queue.closed and len(queue) == 0
            if self._win_buf is not None:
                # window formation: drain on fill/age/deadline
                # pressure — or force-flush a partial window once
                # no further arrival can ever top it up
                self._drain_window(now, force=drained)
            with self._cv:
                self._ingress_drained = drained
                if self._error is not None:
                    return
                if drained and self._inflight == 0:
                    return
                self._cv.notify_all()
            nxt = queue.next_arrival()
            pause = (self.IDLE_POLL if nxt is None else
                     min(max(nxt - clock(), 0.0), self.IDLE_POLL))
            # always yield so producers run, even at pause=0
            await asyncio.sleep(pause)

    def run_trace(self, tokens: np.ndarray,
                  arrivals: Sequence[float] | None = None, *,
                  clock=None):
        """Synchronous trace replay: requests (rows of ``tokens``)
        become visible at their ``arrivals`` offsets on a wall clock —
        or on an injected monotonic ``clock`` (deadline/holdback tests
        use a fake clock so they can't flake on loaded CI; an injected
        clock must eventually pass every arrival offset or the trace
        never drains). Returns the folded ``ServeResult``."""
        queue = IngressQueue()
        queue.submit_burst(tokens, arrivals)
        queue.close()
        return asyncio.run(self.serve_async(queue, clock=clock))

    # -- folding into ServeResult ------------------------------------------
    def stats(self, total_s: float) -> dict:
        """Ingress + scheduler telemetry (superset of the serial
        batcher's ``stats``): per-tier utilization and EWMA estimates,
        deadline-hit rate, shed/degraded counts, queue peaks."""
        from repro.sharding.tier_mesh import mesh_desc as _mesh_desc
        served = [r for r in self._requests if r.done and not r.shed]
        lat = np.asarray([r.latency for r in served], np.float64)
        wait = np.asarray([r.queue_wait for r in served], np.float64)
        return {
            "request_latency": lat,
            "queue_wait": wait,
            "chunks_per_tier": list(self.chunks_per_tier),
            "chunk_occupancy": float(np.mean(self._fill)) if self._fill
            else 0.0,
            "n_chunks": int(sum(self.chunks_per_tier)),
            # per-tier queue waits, host counters and span records
            # (repro.core.telemetry), the same keys as the serial batcher
            **self.telemetry.publish(served),
            # scheduler extensions
            "tier_utilization": [e.utilization(total_s)
                                 for e in self.estimators],
            "service_ewma_s": [e.service.value for e in self.estimators],
            "queue_delay_ewma_s": [e.queue_delay.value
                                   for e in self.estimators],
            "deadline_hit_rate": (self.deadline_hits / self.deadline_total
                                  if self.deadline_total else None),
            "deadline_total": self.deadline_total,
            "shed": self.shed_count,
            "degraded": self.degraded_count,
            "queue_peak": list(self.queue_peak),
            # per-tier device pins (sharding.placement) — None entries
            # mean the tier shares the default device; with every tier
            # pinned to its own device the workers' chunk overlap is no
            # longer serialized on one device's queue
            "tier_devices": [None if s.device is None else
                             f"{s.device.platform}:{s.device.id}"
                             for s in self.pipeline.tiers],
            # per-tier mesh slices (sharding.tier_mesh) — the sharded
            # analogue of tier_devices: each worker dispatches to its
            # tier's device *set*
            "tier_meshes": [None if getattr(s, "mesh", None) is None
                            else _mesh_desc(s.mesh)
                            for s in self.pipeline.tiers],
            # speculative execution (None when the dial is off):
            # committed/cancelled row counts, the device-seconds burnt on
            # cancelled rows, and per-tier speculative busy time — the
            # overlap the cascade's wall clock gained
            "speculation": None if not self.slo.speculate else {
                "issued": self.spec_issued,
                "committed": self.spec_committed,
                "cancelled": self.spec_cancelled,
                "wasted_s": self.spec_wasted_s,
                "spec_busy_s": list(self.spec_busy_s),
                "spec_chunks": list(self.spec_chunks),
                "overlap_frac": [sb / total_s if total_s > 0 else 0.0
                                 for sb in self.spec_busy_s],
            },
            # resilience (None when no retry/breaker/faults are wired):
            # retry volume and its added latency, failover escalations,
            # degraded fallback answers, accounted sheds, and breaker
            # trip/recovery state per tier
            "resilience": None if not self._resilient else {
                "retries": self.retry_count,
                "backoff_s": self.retry_backoff_s,
                "failovers": self.failover_count,
                "fallback_answers": self.fallback_count,
                "shed": self.res_shed,
                "spec_aborted": self.spec_aborted,
                "trips": self._health.trips if self._health else 0,
                "recoveries": (self._health.recoveries
                               if self._health else 0),
                "breakers": (self._health.snapshot(total_s)
                             if self._health else None),
                "faults_injected": {
                    t.name: dict(t.injected) for t in self._tiers
                    if isinstance(t, FaultyTier)} or None,
            },
        }

    def result(self, total_s: float):
        """Fold the finished stream into a ``ServeResult`` bit-compatible
        with ``ServingPipeline.serve`` (see the equivalence guarantee in
        the module docstring); shed requests carry answer ``None``,
        ``stopped_at -2`` and zero cost."""
        return fold_stream_result(
            self.pipeline, self._requests, tier_counts=self.tier_counts,
            cache_hits=self.cache_hits, cache_misses=self.cache_misses,
            latency=self.latency, total_s=total_s,
            ingress=self.stats(total_s),
            strategy=(self._strategy.snapshot(len(self._tiers))
                      if self._strategy is not None else None))
