"""The unified FrugalGPT serving pipeline: all three cost-reduction
strategies composed on ONE batched request path (paper §3, Fig. 2).

A token batch flows through three stages:

  1. completion cache (§3.2, LLM approximation) — queries are embedded
     with the scorer's encoder (no extra model) and answered from the
     nearest-neighbour cache when similarity clears the threshold;
  2. prompt adaptation (§3.1) — every cache miss is billed against the
     *adapted* per-tier few-shot prefix (``PromptSpec``) instead of the
     full prompt, with exact ``ApiCost`` token accounting;
  3. LLM cascade (§3.3) — misses run tier-by-tier with compaction
     through the repo's single cascade executor
     (``repro.core.cascade.execute_cascade``); answer, cost and scorer
     calls are all chunked to ``batch_size``.

Fresh answers are inserted back into the cache, and every request batch
returns a ``ServeResult`` telemetry record: per-tier counts, cache hit
rate, per-stage latency, and cost against the always-top-tier baseline.

Two request paths share these stages:

  * ``serve``        — batch-at-a-time: one closed token batch through
    all three stages;
  * ``serve_stream`` / ``aserve`` — continuous batching over an arrival
    trace (``repro.serving.ingress``): cache lookup runs per-admission,
    tier chunks are packed from whatever is waiting, and per-request
    latency telemetry lands in ``ServeResult.ingress``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax
import numpy as np

from repro.core.approx import CompletionCache
from repro.core.cascade import CascadeTier, execute_cascade
from repro.core.cost import ApiCost
from repro.core.prompt import PromptSpec


def _merge_answers(n: int, parts: Sequence[tuple]) -> np.ndarray:
    """Scatter ``(indices, values)`` parts into one (n,) answers array,
    preserving the values' dtype: int cache hits merged with int cascade
    answers densify to an integer array, string/object generation answers
    stay as they came from the executor instead of being forced through
    ``np.int32`` (which crashed on strings and silently truncated
    floats)."""
    if n == 0:
        return np.zeros(0, np.int32)
    out = np.empty(n, dtype=object)
    for idx, vals in parts:
        idx = np.asarray(idx).ravel()
        vals = np.asarray(vals)
        if vals.dtype == object or vals.ndim != 1:
            for i_local, i_global in enumerate(idx):
                out[i_global] = vals[i_local]
        else:
            out[idx] = vals
    try:                                     # densify when answers are scalar
        # unbox numpy scalars first so both fill branches above densify
        # to the same dtype (fancy assignment into an object array boxes
        # to Python scalars; per-element assignment keeps np scalars)
        dense = np.array([x.item() if isinstance(x, np.generic) else x
                          for x in out])
        if dense.ndim == 1 and dense.dtype != object:
            return dense
    except ValueError:                       # heterogeneous answer objects
        pass
    return out


@dataclasses.dataclass
class TierSpec:
    """One serving tier: a live model plus its economics.

    ``answer(tokens (b, L)) -> answers (b,)``; ``price`` is the exact
    3-term API cost model; ``prompt`` is the tier's adapted few-shot
    prefix (None = bill the full, unadapted prompt).
    """

    name: str
    answer: Callable
    price: ApiCost
    prompt: PromptSpec | None = None
    n_out: int = 1
    # the jax.Device this tier's model is pinned to (sharding.placement);
    # None = wherever the backend already lives (shared default device).
    # Placement happens where the tier's params are created/moved — this
    # field records the decision for telemetry and scheduling.
    device: object | None = None
    # ... or the mesh slice the tier's model is sharded over
    # (sharding.tier_mesh): params sharded per sharding.rules, batches
    # device_put onto the slice by the engine. Mutually exclusive with
    # ``device``; like it, this records the decision for telemetry.
    mesh: object | None = None


@dataclasses.dataclass
class ServeResult:
    """Telemetry for one served batch."""

    answers: np.ndarray          # (n,) final answers
    cost: np.ndarray             # (n,) accounted USD per query
    stopped_at: np.ndarray       # (n,) cascade position; -1 = cache hit
    tier_counts: list            # queries reaching each tier (compaction)
    tier_names: list
    cache_hits: int
    cache_misses: int
    prompt_tokens_saved: int     # adapted vs full prompt, summed over calls
    baseline_cost: float         # top tier + full prompt for every query
    latency: dict                # per-stage seconds
    # streaming telemetry (stream paths only): per-request latency,
    # queue-wait and summed tier-queue-wait arrays, chunks per tier,
    # chunk occupancy, per-tier host counters and chunk/visit span
    # records (repro.core.telemetry); the parallel scheduler adds
    # per-tier utilization/EWMA estimates, deadline-hit rate,
    # shed/degraded counts and queue peaks
    ingress: dict | None = None
    # contextual-strategy telemetry (pipelines with a ServingStrategy):
    # entry-tier histogram, realized spend rate, predicted-vs-realized
    # accept rate, governor state + threshold trace — cumulative over
    # the strategy's lifetime (it outlives individual batches/streams)
    strategy: dict | None = None

    @property
    def n(self) -> int:
        return len(self.answers)

    @property
    def cache_hit_rate(self) -> float:
        t = self.cache_hits + self.cache_misses
        return self.cache_hits / t if t else 0.0

    @property
    def savings_frac(self) -> float:
        if self.baseline_cost <= 0:
            return 0.0
        return 1.0 - float(self.cost.sum()) / self.baseline_cost

    def summary(self) -> str:
        lat = ", ".join(f"{k} {v * 1e3:.0f}ms" for k, v in
                        self.latency.items())
        tiers = ", ".join(f"{nm}: {c}" for nm, c in
                          zip(self.tier_names, self.tier_counts))
        extra = ""
        if self.ingress is not None and len(self.ingress["request_latency"]):
            rl = self.ingress["request_latency"]
            extra = (f" | per-request p50 {np.percentile(rl, 50) * 1e3:.0f}ms"
                     f" p95 {np.percentile(rl, 95) * 1e3:.0f}ms over "
                     f"{self.ingress['n_chunks']} chunks (occupancy "
                     f"{self.ingress['chunk_occupancy']:.2f})")
        if self.ingress is not None and len(self.ingress.get("tier_wait", ())):
            tw = np.percentile(self.ingress["tier_wait"], 95)
            extra += f" | tier queue wait p95 {tw * 1e3:.0f}ms"
            tc = self.ingress["tier_counters"]
            steps = sum(t["decode_steps"] for t in tc)
            if steps:
                host_us = 1e6 * sum(t["decode_dispatch_s"] for t in tc) / steps
                extra += f" | decode host {host_us:.0f}us/step"
        if self.ingress is not None and "tier_utilization" in self.ingress:
            util = ", ".join(f"{u:.2f}" for u in
                             self.ingress["tier_utilization"])
            extra += f" | tier util [{util}]"
            dhr = self.ingress.get("deadline_hit_rate")
            if dhr is not None:
                extra += f" | deadline hit rate {dhr:.2f}"
            if self.ingress.get("shed") or self.ingress.get("degraded"):
                extra += (f" | overload: {self.ingress['shed']} shed, "
                          f"{self.ingress['degraded']} degraded")
        spec = (self.ingress or {}).get("speculation")
        if spec is not None:
            extra += (f" | speculation: {spec['committed']}/{spec['issued']}"
                      f" committed, {spec['cancelled']} cancelled "
                      f"({spec['wasted_s'] * 1e3:.0f}ms wasted)")
        res = (self.ingress or {}).get("resilience")
        if res is not None:
            extra += (f" | resilience: {res['retries']} retries "
                      f"(+{res['backoff_s'] * 1e3:.0f}ms backoff), "
                      f"{res['failovers']} failovers, {res['trips']} trips/"
                      f"{res['recoveries']} recoveries, "
                      f"{res['fallback_answers']} degraded answers, "
                      f"{res['shed']} shed")
        if self.strategy is not None:
            extra += (f" | entry tiers {self.strategy['entry_hist']} "
                      f"(bar {self.strategy['entry_bar']:.2f}) | spend "
                      f"${self.strategy['spend_rate']:.6f}/q")
            gov = self.strategy.get("governor")
            if gov is not None:
                extra += (f" vs ${gov['budget_rate']:.6f} target "
                          f"(shift {gov['shift']:+.3f})")
            gtee = self.strategy.get("guarantee")
            if gtee is not None:
                extra += (
                    f" | guarantee: gap {gtee['gap_hat']:.3f} "
                    f"(ucb {gtee['gap_ucb']:.3f}) vs delta "
                    f"{gtee['delta']:.3f} at alpha {gtee['alpha']:.2f}, "
                    f"level {gtee['level']}/{gtee['levels'] - 1}, "
                    f"{gtee['n_shadow']} shadowed "
                    f"({gtee['n_invoked']} invoked, "
                    f"${gtee['shadow_cost']:.6f} shadow)")
            asg = self.strategy.get("assign")
            if asg is not None:
                extra += (
                    f" | assign: {asg['n_windows']} windows "
                    f"(fill {asg['window_fill']:.2f}), budget util "
                    f"{asg['budget_utilization']:.2f}, predicted "
                    f"{asg['predicted_utility_per_q']:.2f} vs realized "
                    f"{asg['realized_accept_rate']:.2f} accept, solver "
                    f"{asg['solver_iterations']} moves/"
                    f"{asg['solver_secs_per_window'] * 1e3:.1f}ms per window")
        return (
            f"served {self.n} queries | cache hit rate "
            f"{self.cache_hit_rate:.2f} ({self.cache_hits} hits) | "
            f"tier compaction [{tiers}] | prompt tokens saved "
            f"{self.prompt_tokens_saved} | cost ${self.cost.sum():.6f} vs "
            f"${self.baseline_cost:.6f} top-tier baseline "
            f"({100 * self.savings_frac:.0f}% saved) | {lat}{extra}")


@dataclasses.dataclass
class ServingPipeline:
    """Completion cache -> prompt adaptation -> LLM cascade, batched."""

    tiers: Sequence[TierSpec]
    thresholds: Sequence[float]          # len = len(tiers) - 1
    scorer: Callable                     # (tokens, answers) -> scores (n,)
    cache: CompletionCache | None = None
    embed: Callable | None = None        # tokens (n, L) -> embeddings (n, d)
    full_prompt_tokens: int = 0          # unadapted few-shot prefix length
    pad_token: int = 0
    batch_size: int = 256
    # economics of the marketplace's top tier, for the savings baseline —
    # the learned cascade may not end there (budget fallback), so this
    # must not default to whatever tier happens to be last in the cascade
    baseline_price: ApiCost | None = None
    baseline_n_out: int = 1
    # contextual routing + budget governance (repro.serving.strategy):
    # a ServingStrategy, or None for the classic fixed cascade — every
    # serving path is bit-identical to the fixed cascade when unset
    strategy: object | None = None
    # pending-set compaction mode for the batch cascade ("host" numpy |
    # "device" jitted gather+prefix-sum | "pallas" kernel) — opt-in,
    # bit-identical to "host" (repro.kernels.cascade_compact)
    compact: str = "host"
    # speculative cascade execution (repro.serving.sched): idle tier
    # workers pre-invoke predicted-reject rows still decoding upstream.
    # A *stream-scheduler* knob: serve()/the serial batcher have no idle
    # tier workers, so it is a no-op there by construction — which is
    # what keeps the {serve, serial, scheduler} equivalence matrix
    # closed. An explicit slo= passed to the stream entry points wins
    # (it carries its own speculation dials).
    speculate: bool = False
    # fault tolerance (repro.serving.resilience) — all three default
    # off, and off means structurally absent (no wrappers, no extra
    # branches), which is what keeps every serve path bit-identical:
    # per-tier fault injection (a FaultSpec, an index-aligned list of
    # FaultSpec/None, or None), ...
    faults: object | None = None
    # ... per-tier retry for TierFault invoke failures, ...
    retry: object | None = None
    # ... and per-tier circuit breakers (BreakerConfig) driving
    # failover escalation past unavailable tiers. An explicit slo=
    # passed to the stream entry points wins, as for speculate.
    breaker: object | None = None
    # the EnginePool backing generation tiers, when there is one — a
    # breaker trip cancels its in-flight speculative prefills
    # (EnginePool.cancel_all); None for marketplace/toy tiers
    engine_pool: object | None = None

    def __post_init__(self):
        from repro.core.cascade import COMPACT_MODES
        if self.compact not in COMPACT_MODES:
            raise ValueError(f"unknown compact mode {self.compact!r}; "
                             f"expected one of {COMPACT_MODES}")
        if self.cache is not None and self.embed is None:
            raise ValueError("a completion cache needs an embed function "
                             "(reuse the scorer encoder, see builder)")
        if (self.strategy is not None
                and getattr(self.strategy, "router", None) is not None
                and self.embed is None):
            raise ValueError("a contextual router routes on embeddings: "
                             "give the pipeline an embed function (reuse "
                             "the scorer encoder, see builder)")
        if (self.strategy is not None
                and getattr(self.strategy, "mode", "entry") == "assign"
                and self.embed is None):
            raise ValueError("window assignment scores on embeddings: "
                             "give the pipeline an embed function (reuse "
                             "the scorer encoder, see builder)")

    @staticmethod
    def _block(x):
        """Force pending async JAX work at a stage boundary — jax
        dispatch is asynchronous, so without a sync the *next* stage's
        timer pays for this stage's compute. No-op on numpy."""
        return jax.block_until_ready(x)

    # -- stage 2: exact per-tier cost with the adapted prompt --------------
    def _query_tokens(self, tokens: np.ndarray) -> np.ndarray:
        return np.asarray((tokens != self.pad_token).sum(-1), np.int64)

    def _tier_cost(self, spec: TierSpec, tokens: np.ndarray) -> np.ndarray:
        prefix = (spec.prompt.n_tokens if spec.prompt is not None
                  else self.full_prompt_tokens)
        n_q = self._query_tokens(tokens)
        n_out = np.full_like(n_q, spec.n_out)
        return np.asarray(spec.price.query_cost(n_q + prefix, n_out),
                          np.float64)

    def _tier_prices(self, tokens: np.ndarray) -> np.ndarray:
        """(n, m) exact per-(query, tier) $ with each tier's adapted
        prompt — the window meta-model's price input."""
        return np.stack([self._tier_cost(s, tokens) for s in self.tiers],
                        axis=1)

    def _baseline_cost(self, tokens: np.ndarray) -> float:
        """Everything to the marketplace top tier, full prompt, no cache."""
        if self.baseline_price is not None:
            price, n_out = self.baseline_price, self.baseline_n_out
        else:
            price, n_out = self.tiers[-1].price, self.tiers[-1].n_out
        n_q = self._query_tokens(tokens)
        return float(np.asarray(price.query_cost(
            n_q + self.full_prompt_tokens,
            np.full_like(n_q, n_out))).sum())

    # -- pieces shared with the continuous batcher (serving.ingress) -------
    def _cascade_tiers(self, clock=None, sleep=None) -> list[CascadeTier]:
        """The live tiers as cascade stages: one invoke = answer + the
        exact adapted-prompt cost for the same chunk. With ``faults``
        configured, the affected tiers come back wrapped in
        ``FaultyTier`` (the stream scheduler wires its clock into the
        wrappers at start; the batch path sees draw-based faults at
        t=0 unless a ``clock`` — e.g. a ``VirtualClock`` — is passed
        through ``serve``)."""
        tiers = [CascadeTier(
                     s.name,
                     lambda q, s=s: (s.answer(q), self._tier_cost(s, q)))
                 for s in self.tiers]
        if self.faults is not None:
            from repro.serving.resilience import wrap_tiers
            tiers = wrap_tiers(tiers, self.faults, clock=clock, sleep=sleep)
        return tiers

    def _pos_scorer(self, q, a, _j):
        return self.scorer(q, a)

    def _prompt_saved(self, tier_counts: Sequence[int]) -> int:
        saved = 0
        for spec, c in zip(self.tiers, tier_counts):
            if spec.prompt is not None:
                saved += c * (self.full_prompt_tokens - spec.prompt.n_tokens)
        return int(saved)

    def _cache_refresh(self):
        """Refresh the completion cache's *similarity threshold* from the
        budget governor when it owns one (``BudgetGovernor.
        base_threshold``) — overspend admits more near-duplicate hits
        (free answers), spare budget tightens back toward exactness.
        Called at every lookup site (``serve``, ``stage1_lookup``) so
        both serving paths read the same window's dial."""
        if self.cache is None:
            return
        strat = self.strategy
        gov = getattr(strat, "governor", None) if strat is not None else None
        if gov is not None:
            thr = gov.cache_threshold()
            if thr is not None:
                self.cache.threshold = thr

    def _cache_insert(self, emb_rows: np.ndarray, answers,
                      scores=None) -> bool:
        """Insert fresh answers — the cache is int-keyed, so non-integer
        (string/object generation) answers are skipped rather than
        crashed on or silently truncated. ``scores`` (accept-time
        reliability) feed the cache's ``min_score`` confidence floor.
        When the strategy's budget governor owns that floor
        (``BudgetGovernor.base_min_score``), the cache's floor is
        refreshed from it first, so spend overruns loosen what is
        cacheable and spare budget tightens it."""
        strat = self.strategy
        gov = getattr(strat, "governor", None) if strat is not None else None
        if gov is not None:
            ms = gov.min_score()
            if ms is not None:
                self.cache.min_score = ms
        a = np.asarray(answers)
        if a.dtype == object:
            try:
                a = np.array(a.tolist())
            except ValueError:
                return False
        if a.ndim != 1 or not np.issubdtype(a.dtype, np.integer):
            return False
        self.cache.insert(emb_rows, a, scores)
        return True

    # -- stage 3.5: accuracy-guarantee shadow audit ------------------------
    def _shadow_audit(self, tokens, miss, res_ans, stopped, emb, guar):
        """Shadow-sample this batch's served misses against the
        reference (top) tier (``repro.serving.guarantee``).

        Picks are drawn from the controller's seeded per-query coin (in
        row order, so a fixed seed reproduces the subset). A picked row
        that already stopped at the top tier IS the reference answer —
        a free zero-gap observation. The rest invoke the raw reference
        tier in ``batch_size`` chunks; shadow calls bypass fault
        injection (they are measurement, not service) and their cost is
        charged to the controller's separate shadow meter, never to the
        request or the governor's spend rate. Shadow agreement also
        labels the online router retrainer at the stopping position
        (skipping top-tier rows, whose agreement is trivial)."""
        top = len(self.tiers) - 1
        spec = self.tiers[top]
        picks = [i for i in range(len(miss)) if guar.should_sample()]
        if not picks:
            return
        need = [i for i in picks if stopped[i] != top]
        ref_ans: dict = {}
        ref_cost: dict = {}
        for s in range(0, len(need), self.batch_size):
            rows = need[s:s + self.batch_size]
            sub = tokens[miss[rows]]
            ans = np.asarray(spec.answer(sub))
            c = self._tier_cost(spec, sub)
            for k, i in enumerate(rows):
                ref_ans[i] = ans[k]
                ref_cost[i] = float(c[k])
        rt = getattr(guar, "retrainer", None)
        for i in picks:
            if stopped[i] == top:
                guar.observe(0.0, 0.0, invoked=False)
                continue
            agree = bool(np.all(res_ans[i] == ref_ans[i]))
            guar.observe(0.0 if agree else 1.0, ref_cost[i], invoked=True)
            if rt is not None and emb is not None:
                rt.observe(emb[miss[i]], int(stopped[i]), agree)

    def serve(self, tokens: np.ndarray, *, clock=None,
              sleep=None) -> ServeResult:
        """One closed token batch through all three stages. ``clock``/
        ``sleep`` (optional, e.g. a ``resilience.VirtualClock`` and its
        ``.sleep``) own time on the cascade's resilience path — fault
        windows, retry backoff and latency spikes then advance virtual
        time instead of wall-sleeping, with identical accounting."""
        t0 = time.perf_counter()
        n = tokens.shape[0]
        cost = np.zeros(n, np.float64)
        stopped_at = np.full(n, -1, np.int32)
        latency: dict = {}

        # stage 1: completion cache
        hits = 0
        emb = None
        hit_idx = np.zeros(0, np.int64)
        hit_ans = np.zeros(0, np.int32)
        miss = np.arange(n)
        if self.cache is not None:
            t = time.perf_counter()
            emb = np.asarray(self._block(self.embed(tokens)))
            latency["embed"] = time.perf_counter() - t
            t = time.perf_counter()
            self._cache_refresh()   # governor-owned similarity threshold
            hit_mask, cached = self.cache.lookup(emb)
            hit_idx = np.flatnonzero(hit_mask)
            hit_ans = cached[hit_idx]
            hits = len(hit_idx)
            miss = np.flatnonzero(~hit_mask)
            latency["cache"] = time.perf_counter() - t

        # stage 2.5: contextual entry routing (strategy layer) — the
        # router predicts each miss's cascade entry position from the
        # same embeddings the cache keys on; the governor supplies the
        # current (budget-adjusted) thresholds
        strat = self.strategy
        entries = probs = None
        thresholds = self.thresholds
        assign_mode = (strat is not None
                       and getattr(strat, "mode", "entry") == "assign")
        if strat is not None:
            thresholds = strat.thresholds(self.thresholds)
            if assign_mode and len(miss):
                # window assignment: chunk the misses into arrival
                # windows, score each as a batch, and solve entry tiers
                # under the window budget (repro.serving.assign)
                if emb is None:             # no cache stage ran: embed now
                    t = time.perf_counter()
                    emb = np.asarray(self._block(self.embed(tokens)))
                    latency["embed"] = time.perf_counter() - t
                t = time.perf_counter()
                asg = strat.assigner
                prices = self._tier_prices(tokens[miss])
                w = asg.cfg.window_size
                entries = np.concatenate([
                    asg.assign(emb[miss[i:i + w]], prices[i:i + w],
                               governor=strat.governor)["assignment"]
                    for i in range(0, len(miss), w)])
                latency["assign"] = time.perf_counter() - t
            elif getattr(strat, "router", None) is not None and len(miss):
                if emb is None:             # no cache stage ran: embed now
                    t = time.perf_counter()
                    emb = np.asarray(self._block(self.embed(tokens)))
                    latency["embed"] = time.perf_counter() - t
                t = time.perf_counter()
                entries, probs = strat.route(emb[miss])
                latency["route"] = time.perf_counter() - t

        # stages 2+3: adapted prompts + cascade over the misses
        t = time.perf_counter()
        tier_counts = [0] * len(self.tiers)
        res_ans = np.zeros(0, np.int32)
        ingress = None
        if len(miss):
            res = execute_cascade(self._cascade_tiers(clock, sleep),
                                  thresholds,
                                  self._pos_scorer, tokens[miss],
                                  batch_size=self.batch_size, entry=entries,
                                  compact=self.compact, retry=self.retry,
                                  breaker=self.breaker, clock=clock,
                                  sleep=sleep)
            res_ans = np.asarray(res["answers"])
            cost[miss] = res["cost"]
            stopped_at[miss] = res["stopped_at"]
            tier_counts = res["tier_counts"]
            if "resilience" in res:
                # surface the executor's retry/failover counters (incl.
                # backoff credited on terminally-failed chunks) the same
                # way the stream paths do; trips/recoveries only exist
                # with a breaker, but summary() reads them regardless
                ingress = {"request_latency": np.zeros(0),
                           "resilience": {"trips": 0, "recoveries": 0,
                                          **res["resilience"]}}
        latency["cascade"] = time.perf_counter() - t
        answers = _merge_answers(n, [(hit_idx, hit_ans), (miss, res_ans)])

        # write fresh answers back into the cache (int-keyed; skip others)
        if self.cache is not None and len(miss):
            t = time.perf_counter()
            self._cache_insert(emb[miss], res_ans, res["scores"])
            latency["insert"] = time.perf_counter() - t

        # stage 3.5: accuracy-guarantee shadow audit (separate meter)
        guar = getattr(strat, "guarantee", None) if strat is not None else None
        if guar is not None and len(miss):
            t = time.perf_counter()
            self._shadow_audit(tokens, miss, res_ans, stopped_at[miss],
                               emb, guar)
            latency["shadow"] = time.perf_counter() - t

        # feed the strategy: cache hits are zero-cost served queries,
        # misses carry entry/accept telemetry when the router routed them
        strategy_snap = None
        if strat is not None:
            strat.observe_batch(cost[hit_idx])
            if len(miss):
                strat.observe_batch(cost[miss], entries,
                                    stopped_at[miss], probs)
                if assign_mode:
                    # realized counterparts of the solver's predictions:
                    # per-query $ and acceptance at the assigned entry
                    strat.assigner.observe(
                        cost[miss], stopped_at[miss] == entries)
            rt = getattr(guar, "retrainer", None) if guar is not None else None
            if rt is not None and len(miss):
                if entries is not None and emb is not None:
                    # realized accepts at the routed entry — the
                    # predicted-vs-realized telemetry, consumed as
                    # labels (final position is supervised by shadow
                    # agreement only: its offline label was correctness,
                    # and entering there accepts unconditionally)
                    top = len(self.tiers) - 1
                    sub_stop = stopped_at[miss]
                    for i in range(len(miss)):
                        if int(entries[i]) != top:
                            rt.observe(emb[miss[i]], int(entries[i]),
                                       bool(sub_stop[i] == entries[i]))
                rt.maybe_step()
            strategy_snap = strat.snapshot(len(self.tiers))

        latency["total"] = time.perf_counter() - t0
        return ServeResult(
            answers=answers, cost=cost, stopped_at=stopped_at,
            tier_counts=list(tier_counts),
            tier_names=[s.name for s in self.tiers],
            cache_hits=hits, cache_misses=len(miss),
            prompt_tokens_saved=self._prompt_saved(tier_counts),
            baseline_cost=self._baseline_cost(tokens),
            latency=latency, ingress=ingress, strategy=strategy_snap)

    # -- continuous-batching entry points (ingress + sched subsystems) -----
    def _stream_backend(self, max_chunk, holdback, parallel, slo):
        """The stream path's executor: the parallel SLO-aware tier
        scheduler (default) or the serial continuous batcher
        (``parallel=False`` — the reference implementation the
        scheduler is benchmarked against). ``holdback`` and ``slo`` are
        mutually exclusive: an ``SLOConfig`` carries its own
        ``max_holdback_s``, so a separately-passed window would be
        silently dropped."""
        if holdback is not None and slo is not None:
            raise ValueError("pass either holdback= or slo= (SLOConfig "
                             "carries its own max_holdback_s), not both")
        if parallel:
            from repro.serving.sched import SLOConfig, TierScheduler
            if slo is None:
                slo = SLOConfig(max_holdback_s=0.02 if holdback is None
                                else holdback, speculate=self.speculate,
                                retry=self.retry, breaker=self.breaker)
            return TierScheduler(self, max_chunk=max_chunk, slo=slo)
        from repro.serving.ingress import ContinuousBatcher
        if slo is not None:
            raise ValueError("SLO config needs the parallel scheduler "
                             "(parallel=True)")
        if self.strategy is not None:
            raise ValueError("a contextual strategy runs on the parallel "
                             "scheduler (parallel=True); the serial "
                             "batcher is the fixed-cascade reference")
        return ContinuousBatcher(self, max_chunk=max_chunk,
                                 holdback=0.02 if holdback is None
                                 else holdback)

    def serve_stream(self, tokens: np.ndarray, arrivals=None, *,
                     max_chunk: int | None = None,
                     holdback: float | None = None,
                     parallel: bool = True, slo=None) -> ServeResult:
        """Replay an arrival trace through the streaming path: row i of
        ``tokens`` becomes visible at offset ``arrivals[i]`` seconds
        (all at t=0 when None). Cache lookup and prompt accounting run
        per-admission; answers come back in submission order. By default
        tiers decode concurrently under the SLO-aware scheduler
        (``repro.serving.sched``; pass ``slo=SLOConfig(...)`` for
        deadlines/backpressure); ``parallel=False`` selects the serial
        ``ContinuousBatcher``. For a fixed request set under greedy
        decoding both paths are bit-identical to ``serve``
        (tests/test_ingress.py, tests/test_sched.py)."""
        return self._stream_backend(max_chunk, holdback, parallel,
                                    slo).run_trace(tokens, arrivals)

    async def aserve(self, tokens: np.ndarray, arrivals=None, *,
                     max_chunk: int | None = None,
                     holdback: float | None = None,
                     parallel: bool = True, slo=None) -> ServeResult:
        """Async flavour of ``serve_stream`` — cooperates with other
        coroutines while idle. For live producer/consumer streams build
        an ``IngressQueue`` and drive ``TierScheduler.serve_async`` (or
        ``ContinuousBatcher.serve_async``) directly — per-request
        futures resolve as answers land."""
        from repro.serving.ingress import IngressQueue
        backend = self._stream_backend(max_chunk, holdback, parallel, slo)
        queue = IngressQueue()
        queue.submit_burst(tokens, arrivals)
        queue.close()
        return await backend.serve_async(queue)
