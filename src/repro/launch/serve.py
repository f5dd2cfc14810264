"""Serving launcher: the unified FrugalGPT pipeline (cache + prompt
adaptation + cascade) over a batched request stream.

Demo (CPU):
  PYTHONPATH=src python -m repro.launch.serve --requests 200
  PYTHONPATH=src python -m repro.launch.serve --requests 200 \\
      --stream --rate 500        # parallel tier scheduler, Poisson trace
  PYTHONPATH=src python -m repro.launch.serve --requests 200 --stream \\
      --deadline-ms 100 --queue-cap 64 --overload degrade   # SLO mode
  PYTHONPATH=src python -m repro.launch.serve --requests 200 \\
      --contextual --budget-rate 3e-5     # entry routing + spend governor
  PYTHONPATH=src python -m repro.launch.serve --requests 200 \\
      --assign --window-budget 1e-3       # budgeted window assignment
  PYTHONPATH=src python -m repro.launch.serve --requests 400 \\
      --contextual --budget-rate 3e-5 --guarantee --acc-gap 0.05 \\
      --shadow-frac 0.1    # accuracy floor: P(gap > delta) <= alpha
  PYTHONPATH=src python -m repro.launch.serve --requests 200 --stream \\
      --devices 4 --on-device-compact     # per-tier device placement
  PYTHONPATH=src python -m repro.launch.serve --requests 200 --stream \\
      --mesh 8,1                          # per-tier mesh slices (sharded)
  PYTHONPATH=src python -m repro.launch.serve --requests 200 --stream \\
      --profile /tmp/serve-trace          # profiler trace of the served run

Thin CLI over ``repro.serving.build_pipeline`` — this is the entry point
a real deployment would point at the production mesh (tiers sharded with
pjit per DESIGN.md §5). On an accelerator ``--devices``/``--mesh`` use
the chips that are there and exit with an error when too few are;
under ``JAX_PLATFORMS=cpu`` they force that many host devices. Compiled
programs persist in JAX's compilation cache
(``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys

# On the CPU (JAX_PLATFORMS=cpu), --devices N / --mesh R,C force an N-
# (R*C-) device host platform: a CPU box has one device, and tier
# placement/sharding needs several. XLA locks the device count at first
# use, so the flag must land in the environment BEFORE anything imports
# jax — pre-parse it here, ahead of the repro imports below. Both
# `--flag V` and `--flag=V` spellings count; a user's own XLA_FLAGS is
# left alone, and main() exits with an error when the devices that
# result fall short of the request.


def _preparse(argv, flag: str) -> str | None:
    for i, a in enumerate(argv):
        if a == flag and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
    return None


def _parse_mesh(spec: str | None) -> tuple[int, int] | None:
    if spec is None:
        return None
    parts = spec.split(",")
    if len(parts) != 2 or not all(p.strip().isdigit() for p in parts):
        return None
    return int(parts[0]), int(parts[1])


def _parse_faults(spec: str, n_tiers: int):
    """--faults grammar: one FaultSpec broadcast to every tier, or
    pipe-separated ``J:SPEC`` entries targeting tier J (mixing the two
    forms is an error). The per-entry grammar is ``FaultSpec.parse``'s.
    A window value like ``outage=0.1:0.5`` also contains a colon, so a
    tier prefix only counts when the head is a bare integer."""
    from repro.serving.resilience import FaultSpec
    entries = [e.strip() for e in spec.split("|") if e.strip()]
    per_tier: list = [None] * n_tiers
    broadcast = None
    for e in entries:
        head, sep, rest = e.partition(":")
        if sep and "=" not in head and head.strip().isdigit():
            j = int(head)
            if not 0 <= j < n_tiers:
                raise ValueError(f"tier {j} out of range for "
                                 f"{n_tiers} tiers")
            per_tier[j] = FaultSpec.parse(rest)
        else:
            if broadcast is not None:
                raise ValueError("multiple broadcast entries; use "
                                 "'J:SPEC' to target tiers")
            broadcast = FaultSpec.parse(e)
    if broadcast is not None and any(s is not None for s in per_tier):
        raise ValueError("mix of broadcast and per-tier 'J:SPEC' "
                         "entries; pick one form")
    return broadcast if broadcast is not None else per_tier


def _force_host_devices(argv, environ) -> None:
    """Under ``JAX_PLATFORMS=cpu``, write the host device count that
    ``--devices``/``--mesh`` ask for into ``environ["XLA_FLAGS"]``. Any
    other platform keeps the devices it has."""
    if environ.get("JAX_PLATFORMS") != "cpu" or "XLA_FLAGS" in environ:
        return
    n = _preparse(argv, "--devices")
    mesh = _parse_mesh(_preparse(argv, "--mesh"))
    if mesh is not None and (n is None or not n.isdigit()
                             or int(n) < mesh[0] * mesh[1]):
        n = str(mesh[0] * mesh[1])
    if n is not None and n.isdigit() and int(n) > 1:
        environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"


_force_host_devices(sys.argv, os.environ)

from repro.core.router import RouterConfig            # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.data import synthetic                      # noqa: E402
from repro.serving import BuildConfig, build_pipeline  # noqa: E402
from repro.serving.ingress import poisson_arrivals    # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="headlines",
                    choices=list(synthetic.N_CLASSES))
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--budget-frac", type=float, default=0.3,
                    help="budget as a fraction of top-tier cost")
    ap.add_argument("--tiers", default="GPT-J,ChatGPT,GPT-4")
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--no-prompt-adaptation", action="store_true")
    ap.add_argument("--stream", action="store_true",
                    help="replay a Poisson arrival trace through the "
                         "streaming path instead of one closed batch")
    ap.add_argument("--rate", type=float, default=500.0,
                    help="stream mode: mean arrival rate (requests/s)")
    ap.add_argument("--max-chunk", type=int, default=32,
                    help="stream mode: max requests per tier chunk")
    ap.add_argument("--serial", action="store_true",
                    help="stream mode: serial continuous batcher instead "
                         "of the parallel SLO-aware tier scheduler")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="stream mode: per-request latency SLO; partial "
                         "chunks ship when the head-of-line request "
                         "would miss it")
    ap.add_argument("--holdback-ms", type=float, default=20.0,
                    help="stream mode: max wait for chunk fill")
    ap.add_argument("--queue-cap", type=int, default=None,
                    help="stream mode: bounded per-tier wait queue "
                         "(enables backpressure/shedding)")
    ap.add_argument("--overload", default="reject",
                    choices=["reject", "degrade"],
                    help="stream mode: policy once the queue cap is hit — "
                         "shed arrivals, or answer them from the cheapest "
                         "tier whose predicted score clears a reduced bar "
                         "(tier 0 without --contextual)")
    ap.add_argument("--contextual", action="store_true",
                    help="train a contextual entry-tier router: each "
                         "query enters the cascade at the cheapest tier "
                         "whose predicted accept probability clears the "
                         "entry bar")
    ap.add_argument("--entry-bar", type=float, default=0.5,
                    help="contextual mode: predicted-accept probability "
                         "needed to enter a tier")
    ap.add_argument("--assign", action="store_true",
                    help="window-assignment routing (third mode, beside "
                         "the fixed cascade and --contextual): score "
                         "each arrival window's (query, tier) grid with "
                         "a trained meta-model and solve every entry "
                         "tier jointly, on device, under a per-window "
                         "$ budget and per-tier capacity caps")
    ap.add_argument("--window-size", type=int, default=32,
                    help="assign mode: queries assigned together per "
                         "window")
    ap.add_argument("--window-budget", type=float, default=None,
                    help="assign mode: $ per full window (pro-rated to "
                         "actual fill); default derives the budget from "
                         "--budget-rate's governor, or unbounded with "
                         "neither")
    ap.add_argument("--capacity-frac", type=float, default=None,
                    help="assign mode: cap each tier at this fraction "
                         "of a window (derated by live tier utilization "
                         "on the stream scheduler)")
    ap.add_argument("--budget-rate", type=float, default=None,
                    help="target spend rate (USD/query): an online "
                         "governor shifts the cascade thresholds and "
                         "entry bar to hold it")
    ap.add_argument("--governor-window", type=int, default=64,
                    help="queries per governor controller update")
    ap.add_argument("--guarantee", action="store_true",
                    help="accuracy-guaranteed frugality (online SMART "
                         "calibration): shadow-sample served queries "
                         "against the reference (top) tier, hold "
                         "anytime-valid sequential confidence intervals "
                         "on the gap-to-reference, and tighten the "
                         "cascade thresholds so P(gap > delta) <= alpha "
                         "— the guarantee side can veto the budget "
                         "governor's cost-driven loosening. Shadow "
                         "invocations are charged to a separate meter")
    ap.add_argument("--acc-gap", type=float, default=0.05,
                    help="guarantee: tolerable accuracy gap delta vs "
                         "the reference tier (disagreement rate)")
    ap.add_argument("--acc-alpha", type=float, default=0.05,
                    help="guarantee: failure probability alpha of the "
                         "sequential guarantee")
    ap.add_argument("--shadow-frac", type=float, default=0.1,
                    help="guarantee: fraction of served queries "
                         "shadow-routed to the reference tier")
    ap.add_argument("--devices", type=int, default=None,
                    help="pin each cascade tier's model to its own "
                         "device, sized by offline traffic share "
                         "(under JAX_PLATFORMS=cpu, forces an N-device "
                         "host; elsewhere, exits when fewer exist; "
                         "results are bit-identical to the shared "
                         "device)")
    ap.add_argument("--mesh", default=None,
                    help="R,C: shard each cascade tier over its own "
                         "contiguous slice of an RxC device grid (rows "
                         "= data/FSDP axis, cols = tensor axis), sized "
                         "by offline traffic share; under JAX_PLATFORMS="
                         "cpu forces an R*C-device host, elsewhere exits "
                         "when fewer exist. C=1 slices are bit-identical "
                         "to the unsharded pipeline on CPU devices; on a "
                         "TPU a greedy token may flip at a near-tie. "
                         "Mutually exclusive with --devices")
    ap.add_argument("--speculate", action="store_true",
                    help="stream mode: speculative cascade execution — "
                         "idle tier workers pre-invoke predicted-reject "
                         "rows still decoding upstream; answers and "
                         "charged cost are bit-identical, only wall-"
                         "clock moves (best with --contextual for the "
                         "router's probabilities and --devices/--mesh "
                         "so tiers overlap on real hardware)")
    ap.add_argument("--spec-depth", type=int, default=1,
                    help="speculation: how many tiers ahead of a row's "
                         "current position may pre-invoke it")
    ap.add_argument("--spec-bar", type=float, default=0.5,
                    help="speculation: router accept-probability floor — "
                         "every intermediate tier must be predicted to "
                         "reject (prob below this) for a row to qualify")
    ap.add_argument("--spec-idle-frac", type=float, default=0.5,
                    help="speculation: cap on wasted device-seconds as a "
                         "fraction of elapsed stream time")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="inject a deterministic seeded fault schedule "
                         "into the tiers. SPEC is comma-separated "
                         "key=value pairs (error=RATE, timeout=RATE, "
                         "spike=RATE@SECS, rlim=START:END, "
                         "outage=START:END, max=N, seed=N) broadcast to "
                         "every tier, or pipe-separated 'J:SPEC' entries "
                         "targeting tier J in --tiers order (the learned "
                         "cascade may keep a subset; specs for dropped "
                         "tiers are dropped with it), e.g. "
                         "'1:error=0.2|2:outage=0.1:0.5'. Without "
                         "--retry/--breaker an injected fault is fatal "
                         "(the no-resilience baseline)")
    ap.add_argument("--retry", type=int, default=None, metavar="N",
                    help="retry TierFault invokes up to N attempts per "
                         "tier call (exponential backoff, deterministic "
                         "jitter, deadline-aware)")
    ap.add_argument("--retry-backoff-ms", type=float, default=20.0,
                    help="base backoff before the first retry")
    ap.add_argument("--breaker", action="store_true",
                    help="per-tier circuit breakers: a tier whose "
                         "recent invokes keep failing trips open and "
                         "pending rows fail over past it until a "
                         "half-open probe succeeds")
    ap.add_argument("--breaker-cooldown-ms", type=float, default=500.0,
                    help="seconds(ms) an open breaker waits before its "
                         "half-open probe")
    ap.add_argument("--virtual-clock", action="store_true",
                    help="batch mode: run the offline executor's "
                         "resilience path on a virtual clock — fault "
                         "windows, retry backoff and latency spikes "
                         "advance virtual time instead of wall-"
                         "sleeping, with identical accounting")
    ap.add_argument("--on-device-compact", nargs="?", const="device",
                    choices=["device", "pallas"], default=None,
                    help="keep the cascade's pending-set compaction on "
                         "device (jitted gather+prefix-sum, or the "
                         "Pallas kernel variant); bit-identical to the "
                         "host path")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the served run "
                         "into DIR (TensorBoard/XProf or Perfetto): device "
                         "activity under the serving spans serve.stream, "
                         "sched.chunk, cascade.*, engine.prefill and "
                         "engine.decode[.fetch|.dispatch]")
    args = ap.parse_args()
    if args.devices is not None and args.devices < 1:
        ap.error("--devices must be >= 1")
    mesh_shape = None
    if args.mesh is not None:
        mesh_shape = _parse_mesh(args.mesh)
        if mesh_shape is None or min(mesh_shape) < 1:
            ap.error("--mesh expects R,C with positive integers")
        if args.devices is not None:
            ap.error("--devices pins tiers to single devices, --mesh "
                     "shards them over slices; pick one")
    need = (args.devices if args.devices is not None
            else mesh_shape[0] * mesh_shape[1] if mesh_shape else None)
    if need is not None and need > 1:
        import jax
        avail = len(jax.local_devices())
        if avail < need:
            ap.error(f"{need} devices requested (--devices/--mesh) but "
                     f"{jax.default_backend()} has {avail}")
    if args.serial and (args.deadline_ms is not None
                        or args.queue_cap is not None
                        or args.overload != "reject"):
        ap.error("--deadline-ms/--queue-cap/--overload need the "
                 "parallel scheduler; drop --serial")
    if args.serial and (args.contextual or args.budget_rate is not None):
        ap.error("--contextual/--budget-rate run on the parallel "
                 "scheduler; drop --serial")
    if args.assign and args.serial:
        ap.error("--assign runs on the batch path or the parallel "
                 "scheduler; drop --serial")
    if args.assign and args.contextual:
        ap.error("--assign and --contextual are different routing "
                 "modes; pick one")
    if not args.assign and (args.window_budget is not None
                            or args.capacity_frac is not None):
        ap.error("--window-budget/--capacity-frac are assign-mode "
                 "dials; add --assign")
    if args.assign and args.window_size < 1:
        ap.error("--window-size must be >= 1")
    if args.guarantee and args.serial:
        ap.error("--guarantee runs on the batch path or the parallel "
                 "scheduler; drop --serial")
    if not args.guarantee and (args.acc_gap != 0.05
                               or args.acc_alpha != 0.05
                               or args.shadow_frac != 0.1):
        ap.error("--acc-gap/--acc-alpha/--shadow-frac are guarantee "
                 "dials; add --guarantee")
    if args.virtual_clock and args.stream:
        ap.error("--virtual-clock drives the offline batch executor; "
                 "drop --stream (the stream scheduler owns its clock)")
    if args.overload != "reject" and args.queue_cap is None:
        ap.error("--overload degrade only acts on a bounded queue; "
                 "set --queue-cap")
    if args.speculate and (not args.stream or args.serial):
        ap.error("--speculate needs the parallel stream scheduler's idle "
                 "tier workers; add --stream and drop --serial")
    if args.serial and (args.retry is not None or args.breaker
                        or args.faults is not None):
        ap.error("--faults/--retry/--breaker run on the batch executor "
                 "or the parallel stream scheduler; drop --serial")
    if args.retry is not None and args.retry < 1:
        ap.error("--retry must be >= 1 (total attempts)")
    n_tiers = len(args.tiers.split(","))
    faults = retry_pol = breaker_cfg = None
    if args.faults is not None:
        try:
            faults = _parse_faults(args.faults, n_tiers)
        except ValueError as e:
            ap.error(f"--faults: {e}")
    if args.retry is not None:
        from repro.serving.resilience import RetryPolicy
        retry_pol = RetryPolicy(max_attempts=args.retry,
                                backoff_s=args.retry_backoff_ms / 1e3)
    if args.breaker:
        from repro.serving.resilience import BreakerConfig
        breaker_cfg = BreakerConfig(
            cooldown_s=args.breaker_cooldown_ms / 1e3)
    assign_cfg = None
    if args.assign:
        from repro.serving.assign import AssignConfig
        assign_cfg = AssignConfig(window_size=args.window_size,
                                  window_budget=args.window_budget,
                                  capacity_frac=args.capacity_frac)
    guarantee_cfg = None
    if args.guarantee:
        from repro.serving.guarantee import GuaranteeConfig
        try:
            guarantee_cfg = GuaranteeConfig(delta=args.acc_gap,
                                            alpha=args.acc_alpha,
                                            sample_frac=args.shadow_frac)
        except ValueError as e:
            ap.error(f"--guarantee: {e}")

    enable_compile_cache()
    pipe, _ = build_pipeline(BuildConfig(
        task=args.task, tiers=tuple(args.tiers.split(",")),
        train_steps_cap=args.train_steps, budget_frac=args.budget_frac,
        enable_cache=not args.no_cache,
        enable_prompt_adaptation=not args.no_prompt_adaptation,
        contextual=args.contextual, entry_bar=args.entry_bar,
        budget_rate=args.budget_rate, assign=assign_cfg,
        guarantee=guarantee_cfg,
        governor_window=args.governor_window,
        place_tiers=args.devices is not None,
        shard_tiers=mesh_shape is not None, mesh_shape=mesh_shape,
        compact=args.on_device_compact or "host",
        speculate=args.speculate,
        faults=faults, retry=retry_pol, breaker=breaker_cfg,
        router=RouterConfig(top_lists=10, sample=256)))

    test = synthetic.sample(args.task, args.requests, seed=77)
    profile = contextlib.nullcontext()
    if args.profile is not None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # Python call tracing slows the host
        profile = jax.profiler.trace(args.profile, profiler_options=opts)
    with profile:
        res = _serve(pipe, test, args, retry_pol, breaker_cfg)
    served = res.stopped_at != -2
    n_served = int(served.sum())
    acc = (float((res.answers[served] == test.labels[served]).mean())
           if n_served else float("nan"))
    avg_cost = float(res.cost[served].mean()) if n_served else 0.0
    print(res.summary())
    print(f"accuracy {acc:.3f} over {n_served} served; "
          f"avg cost ${avg_cost:.6f}/served query "
          f"({100 * res.savings_frac:.0f}% below top-tier-only)")


def _serve(pipe, test, args, retry_pol, breaker_cfg):
    """One served run of the test set: a stream replay or the batch path."""
    if args.stream:
        arrivals = poisson_arrivals(args.requests, args.rate, seed=77)
        mode = ("serial continuous batcher" if args.serial
                else "parallel SLO scheduler")
        print(f"== streaming {args.requests} requests over "
              f"{arrivals[-1]:.2f}s (Poisson, {args.rate:.0f}/s; "
              f"{mode}) ==")
        if args.serial:
            res = pipe.serve_stream(test.tokens, arrivals,
                                    max_chunk=args.max_chunk,
                                    holdback=args.holdback_ms / 1e3,
                                    parallel=False)
        else:
            from repro.serving.sched import SLOConfig
            slo = SLOConfig(
                deadline_s=(None if args.deadline_ms is None
                            else args.deadline_ms / 1e3),
                max_holdback_s=args.holdback_ms / 1e3,
                queue_cap=args.queue_cap, overload=args.overload,
                speculate=args.speculate, spec_depth=args.spec_depth,
                spec_bar=args.spec_bar,
                spec_idle_frac=args.spec_idle_frac,
                retry=retry_pol, breaker=breaker_cfg)
            res = pipe.serve_stream(test.tokens, arrivals,
                                    max_chunk=args.max_chunk, slo=slo)
    elif args.virtual_clock:
        from repro.serving.resilience import VirtualClock
        vc = VirtualClock()
        res = pipe.serve(test.tokens, clock=vc, sleep=vc.sleep)
    else:
        res = pipe.serve(test.tokens)
    return res


if __name__ == "__main__":
    main()
