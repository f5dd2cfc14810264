"""LLM cascade (paper §3 Strategy 3): ordered API list + score thresholds.

There is exactly ONE cascade-execution implementation in this repo:
``execute_cascade``. It runs the tier-by-tier compaction loop — query
tier j with every still-pending query, score the answers, accept the
reliable ones, re-batch the rest to tier j+1 — and every answer, cost
and scorer call is chunked to ``batch_size`` so no tier ever sees an
unbounded batch. The per-tier chunk step itself is ``tier_step``
(invoke + score + accept on one chunk), which the continuous batcher
(``repro.serving.ingress``) reuses so the online admission loop and the
offline executor share one compaction implementation.

The executor is parameterized by backend through ``CascadeTier``:

  * offline replay — ``replay_tiers`` wraps a ``MarketData`` matrix so
    ``evaluate_offline`` (router optimizer, §Repro experiments) replays
    recorded marketplace responses through the same loop;
  * live models   — ``repro.serving`` wraps real tier models (neural
    marketplace APIs or ``GenerationEngine``-backed tiers) and the
    ``ServingPipeline`` adds the completion-cache and prompt-adaptation
    stages in front.

``run_online`` is kept as a thin compatibility wrapper for callable-API
call sites.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import numpy as np

from repro.core import telemetry
from repro.core.simulate import MarketData


@dataclasses.dataclass(frozen=True)
class Cascade:
    """A learned cascade: API indices L and per-position thresholds tau.

    The last position needs no threshold (it always answers), so
    ``thresholds`` has length len(apis) - 1.
    """

    apis: tuple            # indices into the marketplace (len m)
    thresholds: tuple      # len m-1, floats in [0,1]

    def describe(self, names: Sequence[str]) -> str:
        parts = []
        for j, a in enumerate(self.apis):
            if j < len(self.thresholds):
                parts.append(f"{names[a]} (accept if g>{self.thresholds[j]:.2f})")
            else:
                parts.append(f"{names[a]}")
        return " -> ".join(parts)


@dataclasses.dataclass
class CascadeTier:
    """One cascade stage: a single call returning (answers, costs).

    ``invoke(queries) -> (answers (b,), costs (b,))`` — one call per
    batch chunk, so backends that produce the answer and its cost
    together (a real API response) are never double-charged.
    """

    name: str
    invoke: Callable


def _accept_threshold(dtype, threshold: float):
    """Smallest ``dtype`` value t' with ``(x >= t') == (float64(x) >=
    threshold)`` for every finite x of ``dtype`` — lets the accept rule
    run natively on device scores (typically f32) while staying
    bit-identical to the host float64 comparison: round the threshold
    *up* to the next representable value whenever casting rounded it
    down."""
    t = np.asarray(threshold, dtype)
    if float(t) < float(threshold):
        t = np.nextafter(t, np.asarray(np.inf, dtype))
    return t


def _consume_prefilled(tier: CascadeTier, chunk, prefilled):
    """Merge a speculative pre-invoke into this chunk's (answers, costs).

    ``prefilled`` is ``(mask (b,) bool, answers (b,) object, costs (b,)
    float64)`` aligned row-for-row with ``chunk``: ``mask[i]`` means row
    i's ``tier.invoke`` already ran speculatively (while an earlier tier
    was still decoding) and its answer/cost are in ``answers[i]`` /
    ``costs[i]``. Only the cold rows are invoked now. Exact because tier
    backends are row-wise — the same contract the stream paths already
    rely on for chunk regrouping — so per-row answers and costs do not
    depend on which rows share an invoke."""
    mask, pa, pc = prefilled
    mask = np.asarray(mask, bool)
    if mask.shape != (len(chunk),):
        raise ValueError(f"prefilled mask shape {mask.shape} != "
                         f"({len(chunk)},)")

    def _densify(obj):
        # object array -> native dtype when rows are uniform scalars
        # (np.int32 elements infer int32, not int64); stays object
        # otherwise, and _merge_answers unboxes at fold time either way
        try:
            arr = np.array(obj.tolist())
        except Exception:
            return obj
        return arr if arr.ndim == 1 else obj

    pc = np.asarray(pc, np.float64)
    if mask.all():
        return _densify(pa), pc
    hot = np.flatnonzero(mask)
    cold = np.flatnonzero(~mask)
    ca, cc = tier.invoke(chunk[cold])
    ca = np.asarray(ca)
    a = np.empty(len(chunk), object)
    for i in hot:
        a[i] = pa[i]
    for k, i in enumerate(cold):
        a[i] = ca[k]
    c = np.empty(len(chunk), np.float64)
    c[hot] = pc[hot]
    c[cold] = np.asarray(cc, np.float64)
    return _densify(a), c


def tier_step(tier: CascadeTier, chunk, j: int, *, scorer: Callable,
              threshold: float | None, last: bool, scorer_lock=None,
              device_masks: list | None = None, prefilled=None):
    """One compaction step on ONE chunk: invoke tier j, score, accept.

    This is the single per-tier chunk implementation shared by the
    offline executor (``execute_cascade``), the continuous batcher
    (``repro.serving.ingress``) and the parallel tier scheduler
    (``repro.serving.sched``) — every path routes every tier call
    through here, so the accept rule can never drift between them.

    Returns ``(answers (b,), costs (b,) float64, scores (b,) float64,
    accept (b,) bool)``; ``scores`` are the accept-time reliability
    scores, NaN where the scorer was never consulted — the last tier
    accepts everything without scoring (``threshold`` is ignored).

    Concurrency contract (relied on by ``repro.serving.sched``):
    ``tier_step`` itself keeps no state, so it is safe to run on
    multiple threads provided the *caller* guarantees (a) each tier's
    ``invoke`` is entered by at most one thread at a time — the parallel
    scheduler gives every tier its own worker, so a tier backend
    (e.g. a ``GenerationEngine``) never sees concurrent calls — and
    (b) a ``scorer`` shared across tiers is either thread-safe or
    serialized by passing a ``scorer_lock`` (any context manager).

    ``device_masks`` (optional, a list): when the scorer returns a
    ``jax.Array``, the accept mask is computed *on device* — with the
    threshold rounded so the native-dtype comparison matches the host
    float64 rule exactly (``_accept_threshold``) — and the device mask
    is appended to the list. The on-device cascade executor feeds these
    masks straight into the compaction kernel, removing its last
    host->device round-trip (the host ``accept`` returned here is the
    transfer of that same mask, so bookkeeping cannot drift from it).

    ``prefilled`` (optional): speculative pre-invoke results from an
    idle-tier worker (``_consume_prefilled``) — rows already invoked
    skip the cold ``tier.invoke`` here; scoring, accept, and cost
    charging still run through the identical path below, so speculation
    can only move wall-clock, never answers or charged cost.

    Telemetry (``repro.core.telemetry``): the tier call runs under the
    ``cascade.invoke`` span and the scorer under ``cascade.score``; the
    host time after the tier call (casts, scoring, accept) is added to
    the chunk record open in this context as ``cascade_s``.
    """
    with telemetry.span(telemetry.INVOKE):
        if prefilled is not None:
            a, c = _consume_prefilled(tier, chunk, prefilled)
        else:
            a, c = tier.invoke(chunk)
    t_invoked = time.perf_counter()
    a = np.asarray(a)
    c = np.asarray(c, np.float64)
    if last:
        s = np.full(len(chunk), np.nan)
        accept = np.ones(len(chunk), bool)
    else:
        with telemetry.span(telemetry.SCORE):
            if scorer_lock is not None:
                with scorer_lock:
                    raw = scorer(chunk, a, j)
            else:
                raw = scorer(chunk, a, j)
        s = np.asarray(raw, np.float64)
        accept = None
        if device_masks is not None:
            import jax

            if (isinstance(raw, jax.Array)
                    and raw.dtype in (np.float16, np.float32, np.float64)):
                mask = raw >= _accept_threshold(raw.dtype, threshold)
                device_masks.append(mask)
                accept = np.asarray(mask)
        if accept is None:
            accept = s >= threshold
    rec = telemetry.current()
    if rec is not None:
        rec.cascade_s += time.perf_counter() - t_invoked
    return a, c, s, accept


#: pending-set compaction modes: "host" is the original numpy boolean
#: indexing; "device"/"pallas" run the gather + prefix-sum on device
#: (repro.kernels.cascade_compact — jnp argsort vs the Pallas kernel),
#: bit-identical to "host" by construction and by the equivalence suite
COMPACT_MODES = ("host", "device", "pallas")


def execute_cascade(tiers: Sequence[CascadeTier], thresholds: Sequence[float],
                    scorer: Callable, queries, *,
                    batch_size: int = 256, entry=None,
                    compact: str = "host", retry=None, breaker=None,
                    clock=None, sleep=None) -> dict:
    """THE cascade executor: tier-by-tier compaction over ``queries``.

    queries: (n, ...) array — rows are whatever the tier backend consumes
    (token matrices for live models, query indices for offline replay).
    scorer(queries_chunk, answers_chunk, tier_pos) -> scores in [0,1].

    ``entry`` (optional, (n,) ints in [0, m)) gives each query's cascade
    *entry position* (the contextual router, ``repro.serving.strategy``):
    query i joins the pending set at tier ``entry[i]`` instead of tier 0,
    never touching the tiers below it. ``entry=None`` keeps the classic
    everything-enters-at-0 cascade bit-identically.

    ``compact`` selects where the pending set lives between tiers:
    ``"host"`` (default) is the original numpy path; ``"device"`` keeps
    the pending indices on device and compacts them with a jitted
    gather + prefix-sum (``repro.kernels.cascade_compact``), so for
    numeric queries the next tier's batch is gathered on device too —
    and when the scorer is jax-native the accept mask is fused on device
    as well (``tier_step`` ``device_masks``), so compaction runs with no
    host round-trip at all; ``"pallas"`` uses the Pallas kernel variant
    of the same step. All three are bit-identical in every output
    (tests/test_placement.py).

    ``retry`` / ``breaker`` (optional, ``repro.serving.resilience``)
    opt the executor into fault tolerance: a ``RetryPolicy`` re-invokes
    chunks that raise ``TierFault`` (bounded attempts, deterministic
    backoff), a ``BreakerConfig`` — or a live ``TierHealth`` shared
    across calls — tracks per-tier availability and skips tiers whose
    circuit is open. Rows whose chunk still fails escalate
    forward with zero charged cost (failover); a row failing at the
    *last* tier resolves to its best-scoring earlier rejected answer
    (``stopped_at`` = that tier) or, with none, an accounted shed
    (``stopped_at = -2``). The result then gains a ``"resilience"``
    counters dict. ``clock``/``sleep`` are injectable for tests; both
    ``None`` (the default, with no retry/breaker) keeps every code path
    structurally identical to the pre-resilience executor.

    All tier and scorer calls are chunked to ``batch_size``. Returns
    dict(answers, cost, stopped_at (cascade position, -1 = unanswered),
    scores (accept-time reliability score, NaN where the scorer was
    never consulted — cache-confidence consumers use this), tier_counts
    (pending per tier), accepted_counts).
    """
    if compact not in COMPACT_MODES:
        raise ValueError(f"unknown compact mode {compact!r}; expected "
                         f"one of {COMPACT_MODES}")
    queries = np.asarray(queries)
    n = queries.shape[0]
    m = len(tiers)
    if len(thresholds) != m - 1:
        raise ValueError(f"need {m - 1} thresholds for {m} tiers, "
                         f"got {len(thresholds)}")
    if entry is not None:
        entry = np.asarray(entry, np.int64).ravel()
        if entry.shape != (n,):
            raise ValueError(f"entry must be ({n},), got {entry.shape}")
        if len(entry) and (entry.min() < 0 or entry.max() >= m):
            raise ValueError(f"entry positions must lie in [0, {m}); got "
                             f"range [{entry.min()}, {entry.max()}]")
    answers = np.empty(n, dtype=object)
    cost = np.zeros(n, np.float64)
    stopped_at = np.full(n, -1, np.int32)
    scores = np.full(n, np.nan)
    pending = (np.arange(n) if entry is None
               else np.flatnonzero(entry == 0))
    # fault tolerance is strictly opt-in: without a retry policy or a
    # breaker config every TierFault propagates (a fault-injected run is
    # *supposed* to crash when nobody asked for resilience) and none of
    # the machinery below is even imported
    resilient = retry is not None or breaker is not None
    health = rmeta = None
    if resilient:
        from repro.serving.resilience import (TierFault, TierHealth,
                                              invoke_with_retry)
        if clock is None:
            _t0 = time.perf_counter()
            clock = lambda: time.perf_counter() - _t0  # noqa: E731
        if sleep is None:
            sleep = time.sleep
        # breaker may be a BreakerConfig (fresh breakers for this call)
        # or a live TierHealth shared across calls — a repeatedly-invoked
        # executor then *starts* a pass with tiers already tripped open
        # and skips them outright
        health = None
        if breaker is not None:
            health = (breaker if isinstance(breaker, TierHealth)
                      else TierHealth(m, breaker))
            if len(health.breakers) != m:
                raise ValueError(f"TierHealth tracks "
                                 f"{len(health.breakers)} tiers, cascade "
                                 f"has {m}")
        # best-scoring rejected answer per row: the failover fallback
        # when the last tier fails the row
        best_ans = np.empty(n, object)
        best_score = np.full(n, -np.inf)
        best_tier = np.full(n, -1, np.int32)
        rmeta = {"retries": 0, "backoff_s": 0.0, "failovers": 0,
                 "fallback_answers": 0, "shed": 0}

        def _resolve_failed(g: int):
            if best_tier[g] >= 0:
                answers[g] = best_ans[g]
                scores[g] = best_score[g]
                stopped_at[g] = best_tier[g]
                rmeta["fallback_answers"] += 1
            else:
                stopped_at[g] = -2
                rmeta["shed"] += 1
    # on-device compaction: the pending indices (and, for numeric
    # queries, the query matrix) live on device between tiers; the host
    # mirror is refreshed from the device array so bookkeeping (cost
    # scatter, answer scatter) sees the exact same indices
    on_device = compact != "host"
    compact_op = None
    pending_dev = dev_queries = None
    if on_device:
        import jax.numpy as jnp

        from repro.kernels.cascade_compact.ops import compact as compact_op
        backend = "pallas" if compact == "pallas" else "jnp"
        pending_dev = jnp.asarray(pending, jnp.int32)
        if queries.dtype != object:
            dq = jnp.asarray(queries)
            # device-gather only when the round-trip is lossless: with
            # x64 disabled jax would silently downcast int64/float64
            # queries, changing what the tiers see
            dev_queries = dq if dq.dtype == queries.dtype else None
    tier_counts: list[int] = []
    accepted_counts: list[int] = []
    for j, tier in enumerate(tiers):
        if entry is not None and j > 0:
            # late entrants join the survivors, in ascending row order
            # (the same order a tier-0 entry would have seen them)
            pending = np.sort(np.concatenate(
                [pending, np.flatnonzero(entry == j)]))
            if on_device:
                pending_dev = jnp.asarray(pending, jnp.int32)
        tier_counts.append(len(pending))
        last = j == m - 1
        if len(pending) == 0:
            accepted_counts.append(0)
            continue
        if health is not None and not health.available(j, clock()):
            # circuit open: the whole pending set skips this tier
            # (forward-only escalation). At the last tier there is no
            # forward — every row resolves via its fallback or sheds.
            accepted_counts.append(0)
            rmeta["failovers"] += len(pending)
            if last:
                for g in pending:
                    _resolve_failed(g)
                pending = pending[:0]
            continue
        qs = (np.asarray(jnp.take(dev_queries, pending_dev, axis=0))
              if dev_queries is not None else queries[pending])
        b = len(pending)
        ans_chunks, cost_chunks, score_chunks, accept_chunks = [], [], [], []
        dev_masks: list = []
        eff_tier, failed = tier, None
        if resilient:
            failed = np.zeros(b, bool)
            if retry is not None:
                def _call(ch, _t=tier, _j=j):
                    fails = [0]

                    def _fail(_attempt, _exc):
                        fails[0] += 1

                    def _waited(w):
                        # credited per backoff, not from the returned
                        # total, so a terminally-failed chunk's wasted
                        # backoff seconds still land in the telemetry
                        rmeta["backoff_s"] += w

                    try:
                        a_, c_, attempts, _ = invoke_with_retry(
                            _t, ch, retry, clock=clock, sleep=sleep,
                            token=_j, on_attempt_fail=_fail,
                            on_backoff=_waited)
                    except TierFault:
                        rmeta["retries"] += max(0, fails[0] - 1)
                        raise
                    rmeta["retries"] += attempts - 1
                    return a_, c_

                eff_tier = CascadeTier(tier.name, _call)
        for i in range(0, b, batch_size):
            chunk = qs[i:i + batch_size]
            if resilient:
                try:
                    a, c, s, acc = tier_step(
                        eff_tier, chunk, j, scorer=scorer,
                        threshold=None if last else thresholds[j],
                        last=last,
                        device_masks=dev_masks if on_device else None)
                except TierFault:
                    # retries exhausted (or no retry policy): the chunk
                    # fails forward — zero charged cost, no score, no
                    # accept; the rows stay pending for the next tier
                    nl = len(chunk)
                    failed[i:i + nl] = True
                    a = np.empty(nl, object)
                    c = np.zeros(nl, np.float64)
                    s = np.full(nl, np.nan)
                    acc = np.zeros(nl, bool)
                    if health is not None:
                        health.record(j, False, clock())
                else:
                    if health is not None:
                        health.record(j, True, clock())
            else:
                a, c, s, acc = tier_step(
                    tier, chunk, j, scorer=scorer,
                    threshold=None if last else thresholds[j], last=last,
                    device_masks=dev_masks if on_device else None)
            ans_chunks.append(a)
            cost_chunks.append(c)
            score_chunks.append(s)
            accept_chunks.append(acc)
        ans = np.concatenate(ans_chunks)
        cost[pending] += np.concatenate(cost_chunks)
        accept = np.concatenate(accept_chunks)
        done = pending[accept]
        scores[done] = np.concatenate(score_chunks)[accept]
        if ans.dtype == object or ans.ndim != 1:
            for i_local, i_global in zip(np.flatnonzero(accept), done):
                answers[i_global] = ans[i_local]
        else:
            answers[done] = ans[accept]
        stopped_at[done] = j
        accepted_counts.append(int(accept.sum()))
        if resilient:
            n_failed = int(failed.sum())
            rmeta["failovers"] += n_failed
            if not last:
                # remember each rejected row's best-scoring answer — the
                # failover fallback if every remaining tier fails it too
                sc = np.concatenate(score_chunks)
                for i_local in np.flatnonzero(~accept & ~failed):
                    g = pending[i_local]
                    if sc[i_local] > best_score[g]:
                        best_score[g] = sc[i_local]
                        best_ans[g] = ans[i_local]
                        best_tier[g] = j
            elif n_failed:
                for g in pending[failed]:
                    _resolve_failed(g)
        if on_device:
            if len(dev_masks) == len(accept_chunks):
                # every chunk's accept mask was fused on device
                # (jax-native scorer): compaction consumes the device
                # masks directly — no host->device mask upload
                keep = (jnp.logical_not(dev_masks[0])
                        if len(dev_masks) == 1 else
                        jnp.logical_not(jnp.concatenate(dev_masks)))
            else:
                keep = jnp.asarray(~accept)
            padded, cnt = compact_op(pending_dev, keep, backend=backend)
            pending_dev = padded[:int(cnt)]   # cnt sync sizes the slice
            # host mirror: the cost/answer scatters above are numpy, so
            # the indices come back each tier — what stays on device is
            # the compaction itself and the next tier's query gather
            pending = np.asarray(pending_dev)
        else:
            pending = pending[~accept]
    try:                                     # densify when answers are scalar
        dense = np.array(answers.tolist())
        answers_arr = dense if dense.ndim == 1 else answers
    except ValueError:                       # heterogeneous answer objects
        answers_arr = answers
    out = {
        "answers": answers_arr,
        "cost": cost,
        "stopped_at": stopped_at,
        "scores": scores,
        "tier_counts": tier_counts,
        "accepted_counts": accepted_counts,
    }
    if resilient:
        if health is not None:
            rmeta["trips"] = health.trips
            rmeta["recoveries"] = health.recoveries
            rmeta["breakers"] = health.snapshot(clock())
        out["resilience"] = rmeta
    return out


def replay_tiers(data: MarketData, apis: Sequence[int]) -> list[CascadeTier]:
    """Offline backend: tiers that replay recorded MarketData responses.

    Queries are row indices into ``data``; tier k's "answer" is the
    recorded correctness bit (so accuracy = mean answer) and its cost is
    the recorded per-query cost.
    """
    correct = np.asarray(data.correct)
    cost = np.asarray(data.cost)

    def make(a: int) -> CascadeTier:
        return CascadeTier(
            data.names[a],
            lambda idx, a=a: (correct[idx, a], cost[idx, a]))

    return [make(a) for a in apis]


def evaluate_offline(cascade: Cascade, data: MarketData, scores) -> dict:
    """Replay a cascade over offline marketplace data (the paper's offline
    methodology). scores: (n, K) reliability scores g(q, a_k).

    Runs through ``execute_cascade`` on the replay backend.
    Returns dict(acc, avg_cost, stop_fracs, total_cost).
    """
    s = np.asarray(scores)
    tiers = replay_tiers(data, cascade.apis)

    def scorer(idx, _ans, j):
        return s[idx, cascade.apis[j]]

    res = execute_cascade(tiers, cascade.thresholds, scorer,
                          np.arange(data.n), batch_size=max(1, data.n))
    acc_per_query = np.asarray(res["answers"], np.float64)
    return {
        "acc": float(acc_per_query.mean()),
        "avg_cost": float(res["cost"].mean()),
        "total_cost": float(res["cost"].sum()),
        "stop_fracs": [c / data.n for c in res["accepted_counts"]],
    }


def run_online(cascade: Cascade, queries: list, apis: Sequence[Callable],
               scorer: Callable, names: Sequence[str] | None = None) -> dict:
    """Execute the cascade against live callable APIs (compat wrapper).

    apis[k](list_of_queries) -> (answers, per_query_cost)
    scorer(queries, answers, api_index) -> np.ndarray scores in [0,1]
    """
    try:
        qarr = np.asarray(queries)
    except ValueError:                   # ragged / heterogeneous queries
        qarr = np.empty(len(queries), dtype=object)
        qarr[:] = queries
    tiers = [CascadeTier(names[a] if names else str(a),
                         lambda qs, a=a: apis[a](list(qs)))
             for a in cascade.apis]

    def pos_scorer(qs, ans, j):
        return scorer(list(qs), ans, cascade.apis[j])

    res = execute_cascade(tiers, cascade.thresholds, pos_scorer, qarr,
                          batch_size=max(1, len(queries)))
    # map cascade positions back to marketplace API indices
    trace = np.full(len(queries), -1, np.int32)
    for j, a in enumerate(cascade.apis):
        trace[res["stopped_at"] == j] = a
    return {"answers": list(res["answers"]), "cost": res["cost"],
            "stopped_at": trace}
