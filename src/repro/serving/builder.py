"""Shared pipeline builder: everything between "a task name" and "a ready
``ServingPipeline``", used by both ``repro.launch.serve`` and
``examples/cascade_serving.py`` (which are now thin CLI wrappers).

Build steps:
  1. train the tier models (neural marketplace) on the synthetic task;
  2. collect offline marketplace data and train the scoring function
     g(q, a) on it;
  3. greedy prompt selection per tier (§3.1): pick the few-shot examples
     worth their tokens under each tier's measured accuracy profile;
  4. reprice the offline data with the adapted per-tier prompts and
     learn (L, tau) with the router optimizer under the budget;
  5. assemble the ``ServingPipeline``: completion cache keyed by
     scorer-encoder embeddings, adapted prompts, learned cascade.

The prompt-selection accuracy model is the calibrated diminishing-
returns curve (per-example gains anchored at the tier's measured
validation accuracy, as in ``examples/prompt_adaptation.py``); the token
accounting is exact.
"""
from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np

from repro.core import neural_market as NM
from repro.core import scorer as SC
from repro.core.approx import CompletionCache, embed_queries
from repro.core.joint import joint_prompt_cascade
from repro.core.prompt import PromptSpec, select_prompt
from repro.core.router import RouterConfig, learn_cascade
from repro.core.simulate import MarketData
from repro.data import synthetic
from repro.serving.pipeline import ServingPipeline, TierSpec
from repro.serving.strategy import (BudgetGovernor, ContextualRouter,
                                    ServingStrategy, accept_labels,
                                    train_entry_router)

#: synthetic task -> the paper dataset whose prompt shape ``core.joint``
#: models (prompt sizes, per-example token counts, Table-1 pricing)
_JOINT_DATASET = {"headlines": "HEADLINES", "overruling": "OVERRULING",
                  "qa": "COQA"}


@dataclasses.dataclass
class BuildConfig:
    task: str = "headlines"
    tiers: tuple = ("GPT-J", "ChatGPT", "GPT-4")
    train_queries: int = 400
    train_steps_cap: int = 200
    scorer_steps: int = 250
    budget_frac: float = 0.3        # budget as fraction of top-tier cost
    seed: int = 0
    router: RouterConfig | None = None
    # strategy toggles
    enable_cache: bool = True
    enable_prompt_adaptation: bool = True
    cache_capacity: int = 1024
    cache_threshold: float = 0.995
    cache_policy: str = "fifo"          # "fifo" ring | "lru" | "lfu"
    cache_min_score: float | None = None  # score-confidence insert floor
    cache_ttl: float | None = None      # entry time-to-live (seconds)
    # per-tier device placement (sharding.placement): pin each cascade
    # tier's model to its own local jax.Device, sized by the offline
    # replay's per-tier traffic share — on a multi-device host the tier
    # workers then decode on disjoint devices. Results are bit-identical
    # to the shared-device pipeline (tests/test_placement.py).
    place_tiers: bool = False
    # ... or per-tier mesh slices (sharding.tier_mesh): each tier's
    # model is sharded over a contiguous sub-mesh sized by the same
    # traffic signal — the multi-host rung of place_tiers (which it
    # supersedes; setting both is an error). mesh_shape=(R, C) lays the
    # local devices out as R rows ("data"/FSDP axis units) x C columns
    # ("model" tensor axis); None = (n_devices, 1), data-parallel only,
    # which keeps results bit-identical to the unsharded pipeline on CPU
    # devices; on a TPU a greedy token may flip at a near-tie.
    shard_tiers: bool = False
    mesh_shape: tuple | None = None
    # pending-set compaction mode for the batch cascade path:
    # "host" numpy | "device" jitted gather+prefix-sum | "pallas" kernel
    compact: str = "host"
    # speculative cascade execution (repro.serving.sched): idle tier
    # workers pre-invoke predicted-reject rows on the stream scheduler.
    # Opt-in; bit-identical answers/costs by construction — only moves
    # wall-clock. Dials (depth, probability bar, idle budget) live on
    # the SLOConfig passed to the stream entry points.
    speculate: bool = False
    # fault tolerance (repro.serving.resilience). ``faults`` injects a
    # deterministic seeded fault schedule into the assembled tiers (one
    # FaultSpec broadcast to every tier, or a list indexed by the
    # *marketplace* tier order — the learned cascade keeps a subsequence
    # of the marketplace, so the builder maps the list onto whichever
    # tiers were selected and drops the rest; None = the tiers are not
    # even wrapped). ``retry``/``breaker`` opt the serving paths into
    # retry + circuit-breaker failover; all three default off and off
    # is bit-identical to not having the subsystem at all.
    faults: object | None = None        # FaultSpec | list | None
    retry: object | None = None         # RetryPolicy | None
    breaker: object | None = None       # BreakerConfig | None
    # joint prompt x cascade search (core.joint) instead of greedy
    # per-tier prompt selection: one shared prompt size chosen jointly
    # with the cascade under the budget
    joint_search: bool = False
    joint_prompt_sizes: tuple | None = None   # None = 0..n_shot
    # contextual entry routing + online budget governance
    # (repro.serving.strategy): train a per-query entry-tier router on
    # the offline artifacts; optionally govern spend to budget_rate
    contextual: bool = False
    entry_bar: float = 0.5          # predicted-accept bar to enter a tier
    degrade_relief: float = 0.5     # bar relief factor under overload
    router_hidden: int = 64
    router_steps: int = 300
    budget_rate: float | None = None  # target USD/query (None = no governor)
    governor_window: int = 64         # queries per governor update
    # window-assignment routing (repro.serving.assign): an AssignConfig
    # trains the two-head window meta-model on the same offline
    # artifacts and wires a WindowAssigner into the strategy as
    # mode="assign" — the third routing mode, beside fixed thresholds
    # and greedy contextual entry. None = structurally absent.
    assign: object | None = None        # assign.AssignConfig | None
    # accuracy-guaranteed frugality (repro.serving.guarantee): a
    # GuaranteeConfig(delta=, alpha=, sample_frac=) shadow-samples live
    # traffic against the reference (top) tier, holds anytime-valid
    # sequential confidence intervals on the gap-to-reference, and caps
    # the governor's threshold shift so P(gap > delta) <= alpha — the
    # spend controller's second dual constraint. Shadow invocations are
    # charged to a separate meter. None = structurally absent
    # (bit-identical serving).
    guarantee: object | None = None     # guarantee.GuaranteeConfig | None
    # unadapted few-shot prompt shape (paper's 8-shot HEADLINES scale)
    n_shot: int = 8
    tokens_per_example: int = 110
    base_tokens: int = 140
    verbose: bool = True


def _select_tier_prompt(cfg: BuildConfig, tier_idx: int,
                        val_acc: float) -> tuple[PromptSpec, list]:
    """Greedy prompt selection for one tier (Fig. 2a).

    Accuracy model: measured validation accuracy at the full prompt,
    diminishing per-example gains (seeded per tier) — the greedy selector
    finds the knee where examples stop paying for their tokens.
    """
    rng = np.random.default_rng(cfg.seed + 101 * tier_idx)
    gains = np.sort(rng.uniform(0.004, 0.02, size=cfg.n_shot))[::-1]
    base = val_acc - float(gains.sum())

    def evaluate(ids):
        return base + sum(float(gains[i]) for i in ids)

    return select_prompt(list(range(cfg.n_shot)), evaluate,
                         tokens_per_example=cfg.tokens_per_example,
                         base_tokens=cfg.base_tokens, min_gain=0.008)


def _reprice(data: MarketData, apis, prompts, full_tokens: int) -> MarketData:
    """Offline costs as the pipeline will actually bill them: query
    tokens + the (adapted or full) per-tier prompt prefix."""
    cost = np.zeros(np.asarray(data.cost).shape, np.float32)
    n_in = np.asarray(data.n_in)
    for k, api in enumerate(apis):
        prefix = prompts[k].n_tokens if prompts[k] is not None else full_tokens
        cost[:, k] = np.asarray(api.price.query_cost(n_in + prefix,
                                                     data.n_out))
    return MarketData(data.names, data.correct, jnp.asarray(cost),
                      data.n_in, data.n_out, data.difficulty)


def _select_tier_faults(faults, n_market: int, selected):
    """Map a marketplace-indexed per-tier fault list onto the tiers the
    learned cascade actually kept (``selected`` = marketplace indices,
    in cascade order). Broadcast specs and ``None`` pass through."""
    if not isinstance(faults, (list, tuple)):
        return faults
    if len(faults) != n_market:
        raise ValueError(
            f"{len(faults)} fault specs for a {n_market}-tier "
            "marketplace (per-tier fault lists are indexed by the "
            "marketplace order, not the learned cascade)")
    return [faults[i] for i in selected]


def build_pipeline(cfg: BuildConfig) -> tuple[ServingPipeline, dict]:
    """Returns (pipeline, report). ``report`` carries the build artifacts
    (apis, market data, scorer params, cascade, metrics) for drivers that
    want to print or evaluate them."""
    say = print if cfg.verbose else (lambda *a, **k: None)

    # 1. tier models
    say("== training tier models ==")
    tier_specs = NM.tier_subset(cfg.tiers, steps_cap=cfg.train_steps_cap)
    apis = NM.train_marketplace(cfg.task, seed=cfg.seed, verbose=cfg.verbose,
                                tiers=tier_specs)

    # 2. offline data + scorer
    say("== collecting offline marketplace data ==")
    train = synthetic.sample(cfg.task, cfg.train_queries, seed=cfg.seed + 11)
    data, answers = NM.collect_market_data(apis, train.tokens, train.labels)
    accs = np.asarray(data.accuracy())
    say("tier accuracy:", {n: round(float(a), 3)
                           for n, a in zip(data.names, accs)})

    say("== training the scoring function g(q, a) ==")
    k = len(apis)
    q = np.repeat(train.tokens, k, axis=0)
    y = np.asarray(data.correct).reshape(-1)
    sp = SC.train_scorer(q, answers.reshape(-1), y, steps=cfg.scorer_steps,
                         seed=cfg.seed)
    s_train = np.stack([SC.score(sp, train.tokens, answers[:, j])
                        for j in range(k)], axis=1)
    say(f"scorer AUC: {SC.auc(s_train.reshape(-1), y):.3f}")

    # 3. prompt adaptation: greedy per-tier selection, or the joint
    #    prompt x cascade search (one shared prompt size chosen jointly
    #    with the cascade, core.joint) behind cfg.joint_search
    full_tokens = cfg.base_tokens + cfg.n_shot * cfg.tokens_per_example
    prompts: list[PromptSpec | None] = [None] * k
    router = cfg.router or RouterConfig(top_lists=10, sample=256)
    joint_report = None
    if cfg.joint_search:
        say("== joint prompt x cascade search ==")
        full_priced = _reprice(data, apis, prompts, full_tokens)
        joint_budget = float(full_priced.cost[:, -1].mean()) * cfg.budget_frac
        sizes = (cfg.joint_prompt_sizes if cfg.joint_prompt_sizes is not None
                 else range(cfg.n_shot + 1))
        best, rows = joint_prompt_cascade(
            full_priced, jnp.asarray(s_train), _JOINT_DATASET[cfg.task],
            joint_budget, cfg=router, prompt_sizes=sizes, seed=cfg.seed)
        n_ex = int(best["n_examples"])
        prompts = [PromptSpec(tuple(range(n_ex)), cfg.tokens_per_example,
                              cfg.base_tokens) for _ in range(k)]
        joint_report = {"n_examples": n_ex, "rows": rows,
                        "budget": joint_budget}
        say(f"  joint winner: {n_ex}/{cfg.n_shot} examples "
            f"(acc {best['acc']:.3f} at ${best['avg_cost']:.6f}/query)")
    elif cfg.enable_prompt_adaptation:
        say("== greedy prompt selection per tier ==")
        for j in range(k):
            spec, _ = _select_tier_prompt(cfg, j, float(accs[j]))
            prompts[j] = spec
            say(f"  {data.names[j]}: kept {len(spec.example_ids)}/"
                f"{cfg.n_shot} examples ({spec.n_tokens} vs {full_tokens} "
                f"prompt tokens)")

    # 4. learn the cascade on the repriced (served-as-billed) costs
    say("== learning the cascade ==")
    priced = _reprice(data, apis, prompts, full_tokens)
    budget = float(priced.cost[:, -1].mean()) * cfg.budget_frac
    cas, metrics = learn_cascade(priced, jnp.asarray(s_train), budget, router)
    say(f"cascade: {cas.describe(data.names)} "
        f"(train acc {metrics['acc']:.3f}, ${metrics['avg_cost']:.6f}/query)")

    # 5. contextual strategy: entry-tier router trained on the same
    #    offline artifacts the cascade was learned from, plus an online
    #    budget governor when a target spend rate is set
    strategy = None
    entry_router = governor = assigner = None
    ent = None
    emb_train = None
    if cfg.contextual or cfg.assign is not None:
        emb_train = embed_queries(sp, train.tokens, cfg=SC.SCORER_CFG)
    if cfg.contextual:
        say("== training the contextual entry router ==")
        y = accept_labels(s_train, np.asarray(data.correct),
                          cas.apis, cas.thresholds)
        rp = train_entry_router(emb_train, y, hidden=cfg.router_hidden,
                                steps=cfg.router_steps, seed=cfg.seed)
        entry_router = ContextualRouter(rp, len(cas.apis))
        ent = entry_router.entry_tiers(emb_train, cfg.entry_bar)
        say(f"  entry-tier distribution (train): "
            f"{np.bincount(ent, minlength=len(cas.apis)).tolist()}")
    if cfg.assign is not None:
        from repro.serving.assign import (WindowAssigner,
                                          correctness_labels,
                                          train_window_meta)
        say("== training the window meta-model ==")
        acc_y = accept_labels(s_train, np.asarray(data.correct),
                              cas.apis, cas.thresholds)
        cor_y = correctness_labels(data.correct, cas.apis)
        meta = train_window_meta(
            emb_train, acc_y, cor_y, hidden=cfg.assign.hidden,
            steps=cfg.assign.steps, batch=cfg.assign.batch,
            lr=cfg.assign.lr, seed=cfg.assign.seed + cfg.seed)
        assigner = WindowAssigner(meta=meta, cfg=cfg.assign)
        say(f"  window meta: {len(cas.apis)} tiers, "
            f"window_size={cfg.assign.window_size}")
    guarantee_ctrl = None
    if cfg.guarantee is not None:
        from repro.serving.guarantee import (GuaranteeController,
                                             RouterRetrainer)
        retrainer = None
        if cfg.guarantee.retrain and entry_router is not None:
            retrainer = RouterRetrainer(entry_router)
        guarantee_ctrl = GuaranteeController(cfg.guarantee,
                                             retrainer=retrainer)
        say(f"== accuracy guarantee: gap <= {cfg.guarantee.delta} at "
            f"alpha {cfg.guarantee.alpha} "
            f"({cfg.guarantee.sample_frac:.0%} shadow"
            f"{', online router retraining' if retrainer else ''}) ==")
    if cfg.budget_rate is not None:
        governor = BudgetGovernor(cfg.budget_rate, cas.thresholds,
                                  base_bar=cfg.entry_bar,
                                  base_min_score=cfg.cache_min_score
                                  if cfg.enable_cache else None,
                                  base_threshold=cfg.cache_threshold
                                  if cfg.enable_cache else None,
                                  window=cfg.governor_window,
                                  guarantee=guarantee_ctrl)
    if (entry_router is not None or governor is not None
            or assigner is not None or guarantee_ctrl is not None):
        strategy = ServingStrategy(router=entry_router, governor=governor,
                                   entry_bar=cfg.entry_bar,
                                   degrade_relief=cfg.degrade_relief,
                                   mode=("assign" if assigner is not None
                                         else "entry"),
                                   assigner=assigner,
                                   guarantee=guarantee_ctrl)

    # 6. per-tier device placement: the offline replay's per-tier
    #    pending counts are the traffic-share signal (the online
    #    analogue is ServeResult.tier_counts); each tier's params move
    #    to their assigned device, so its chunks decode there. With a
    #    contextual router the replay honours the learned entry tiers —
    #    all-enter-at-0 pending fractions would size the wrong tiers.
    placement = mesh_plan = None
    if cfg.place_tiers and cfg.shard_tiers:
        raise ValueError("place_tiers pins tiers to single devices, "
                         "shard_tiers slices a mesh over them — pick one")
    if cfg.place_tiers or cfg.shard_tiers:
        from repro.core.cascade import execute_cascade, replay_tiers
        if ent is not None:
            replay = execute_cascade(
                replay_tiers(priced, cas.apis), cas.thresholds,
                lambda idx, _a, j: s_train[idx, cas.apis[j]],
                np.arange(data.n), batch_size=max(1, data.n), entry=ent)
            reach = [float(c) for c in replay["tier_counts"]]
        else:
            stop = list(metrics["stop_fracs"])
            reach = [1.0 - sum(stop[:j]) for j in range(len(cas.apis))]
    if cfg.place_tiers:
        from repro.sharding.placement import place_params, plan_placement
        placement = plan_placement(len(cas.apis), tier_counts=reach)
        for j, i in enumerate(cas.apis):
            apis[i].params = place_params(apis[i].params,
                                          placement.for_tier(j))
        say(f"tier placement: "
            f"{placement.describe([data.names[i] for i in cas.apis])}")
    elif cfg.shard_tiers:
        from repro.sharding.tier_mesh import plan_tier_meshes, shard_params
        mesh_plan = plan_tier_meshes(len(cas.apis),
                                     mesh_shape=cfg.mesh_shape,
                                     tier_counts=reach)
        for j, i in enumerate(cas.apis):
            apis[i].params = shard_params(apis[i].params,
                                          mesh_plan.for_tier(j))
        say(f"tier mesh slices: "
            f"{mesh_plan.describe([data.names[i] for i in cas.apis])}")

    # 7. assemble the pipeline
    cache = embed = None
    if cfg.enable_cache:
        cache = CompletionCache(capacity=cfg.cache_capacity,
                                threshold=cfg.cache_threshold,
                                policy=cfg.cache_policy,
                                min_score=cfg.cache_min_score,
                                ttl=cfg.cache_ttl)
    if (cfg.enable_cache or entry_router is not None
            or assigner is not None):
        embed = functools.partial(embed_queries, sp, cfg=SC.SCORER_CFG)
    tiers = [TierSpec(apis[i].name, apis[i].answer, apis[i].price,
                      prompt=prompts[i],
                      device=placement.for_tier(j) if placement else None,
                      mesh=mesh_plan.for_tier(j) if mesh_plan else None)
             for j, i in enumerate(cas.apis)]
    # savings baseline = the marketplace's most expensive tier, NOT the
    # cascade's last tier (a tight budget can drop the top tier entirely)
    top = int(np.argmax(np.asarray(priced.cost).mean(0)))
    faults = _select_tier_faults(cfg.faults, len(apis), cas.apis)
    pipeline = ServingPipeline(
        tiers=tiers, thresholds=cas.thresholds,
        scorer=lambda toks, ans: SC.score(sp, toks, ans),
        cache=cache, embed=embed, full_prompt_tokens=full_tokens,
        pad_token=synthetic.PAD, baseline_price=apis[top].price,
        strategy=strategy, compact=cfg.compact, speculate=cfg.speculate,
        faults=faults, retry=cfg.retry, breaker=cfg.breaker)
    report = {"apis": apis, "data": data, "priced": priced,
              "answers": answers, "scorer": sp, "scores": s_train,
              "cascade": cas, "metrics": metrics, "budget": budget,
              "prompts": prompts, "full_prompt_tokens": full_tokens,
              "strategy": strategy, "joint": joint_report,
              "guarantee": guarantee_ctrl,
              "placement": placement, "mesh_plan": mesh_plan}
    return pipeline, report
