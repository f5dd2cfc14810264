#!/usr/bin/env python3
"""Bring-up smoke run of the cascade server on a TPU.

Drives the served path once, through the entry points a user calls, and
checks what comes out. One process holds the chip for the whole run and
starts no other. Phases, one printed line each:

  1. device    the first device must be a TPU; there is no CPU fallback.
  2. launcher  what ``python -m repro.launch.serve --tiers GPT-J,GPT-4
               --train-steps 40 --requests 60`` does, in process:
               ``build_pipeline``, ``serve``, then ``serve_stream`` on a
               Poisson trace. Both must agree exactly.
  3. cascade   two ``GenerationEngine`` tiers of gemma3-1b at its
               published widths (weights from seeds 0 and 1) behind a
               ``ServingPipeline``: 64 queries through ``serve`` with every
               compaction mode and through ``serve_stream``, all identical;
               tier 0's greedy tokens checked against an uncached float32
               forward pass.
  4. kernels   the tier-0 weights with ``enable_kernels(True)``: the
               compiled prefill and decode hold Mosaic kernels
               (``tpu_custom_call``) and their logits match the served
               path's, whose decode is jnp. On a TPU the served prefill
               takes the flash kernel without the switch, so phase 3
               already checks it against the reference.

With ``--chips 4`` only phase 5 runs: the phase-3 cascade with its tiers
pinned to chips of their own (``plan_placement``) and sliced over a 4x1
mesh (``plan_tier_meshes``), each compared with the one-device run:
placed tiers exactly, sharded tiers exactly in cost and routing and, for
tokens that differ, against the uncached reference.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Wall times it prints are set-up and smoke timings, not benchmark numbers.

    python chip_smoke.py              # one chip: phases 1-4
    python chip_smoke.py --chips 4    # four chips: phase 5
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import zlib
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

ARCH = "gemma3-1b"
N_QUERIES = 64          # queries per cascade run
N_NEW = 16              # greedy tokens generated per query
PROMPT_LEN = (16, 200)  # true prompt lengths; rows are right-padded
N_CHECK = 4             # prompts checked against the uncached reference
PAD = 0


# -- shared pieces -----------------------------------------------------------


def _say(phase: str, t0: float, text: str):
    print(f"phase {phase}: {text} | wall {time.perf_counter() - t0:.1f} s "
          f"(set-up and smoke timing, not a benchmark)", flush=True)


def _peak_memory() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return ("not reported" if peak is None
            else f"{peak / 2 ** 30:.2f} GiB")


def _assert_same(ref, res, tag: str, answers: bool = True):
    """Answers, charged cost, stopping tier and per-tier counts agree
    exactly: the cascade's outputs that no serving path may move.
    ``answers=False`` leaves the answers to a check of their own."""
    if answers:
        assert np.array_equal(ref.answers, res.answers), (
            f"{tag}: answers differ in "
            f"{int((ref.answers != res.answers).sum())}/{len(ref.answers)} "
            f"rows")
    assert np.array_equal(ref.cost, res.cost), f"{tag}: cost"
    assert np.array_equal(ref.stopped_at, res.stopped_at), \
        f"{tag}: stopped_at"
    assert list(ref.tier_counts) == list(res.tier_counts), \
        f"{tag}: tier_counts {ref.tier_counts} != {res.tier_counts}"


def cascade_prompts(cfg, n: int = N_QUERIES, seed: int = 0) -> np.ndarray:
    """(n, PROMPT_LEN[1]) tokens with true lengths drawn from PROMPT_LEN,
    right-padded with PAD. The first token of exactly a quarter of the
    rows is 0 mod 4: those are the rows the scorer escalates."""
    rng = np.random.default_rng(seed)
    lo, width = PROMPT_LEN
    toks = rng.integers(1, cfg.vocab, size=(n, width)).astype(np.int32)
    length = rng.integers(lo, width + 1, size=n)
    toks[np.arange(width)[None, :] >= length[:, None]] = PAD
    escalate = rng.permutation(n) < n // 4
    toks[:, 0] = 4 * rng.integers(1, cfg.vocab // 4, size=n) + ~escalate
    return toks


def _scorer(tokens, answers):
    """Deterministic accept scores: rows whose first token is 0 mod 4
    score below the 0.5 threshold and escalate to tier 1."""
    return np.where(tokens[:, 0] % 4 == 0, 0.1, 0.9)


def _answer_ids(generated: np.ndarray) -> np.ndarray:
    """One int per row standing for all of its generated tokens."""
    return np.array([zlib.crc32(r.astype(np.int32).tobytes())
                     for r in generated], np.int64)


def cascade_pipeline(engines, compact: str = "host"):
    """A ServingPipeline over generation tiers. Returns the pipeline and
    a per-tier log of (prompts, generated) token arrays, one entry per
    tier call."""
    from repro.core.cost import ApiCost
    from repro.serving.pipeline import ServingPipeline, TierSpec

    log = [[] for _ in engines]

    def tier(j, eng):
        def answer(tokens):
            gen = eng.generate(tokens, N_NEW)
            log[j].append((tokens, gen))
            return _answer_ids(gen)

        price = ApiCost(2.0 * 10 ** j, 10.0 * 10 ** j)
        return TierSpec(f"{ARCH}/seed{j}", answer, price, n_out=N_NEW,
                        device=eng.device, mesh=eng.mesh)

    pipe = ServingPipeline(
        tiers=[tier(j, e) for j, e in enumerate(engines)],
        thresholds=[0.5] * (len(engines) - 1), scorer=_scorer,
        pad_token=PAD, batch_size=N_QUERIES, compact=compact)
    return pipe, log


def init_tiers(cfg, seeds=(0, 1)):
    """Seeded float32 master weights, one pytree per tier (op by op: a
    jitted init of the whole model compiles for most of a minute)."""
    import jax

    from repro.models import transformer as T

    return [T.init_params(jax.random.PRNGKey(s), cfg) for s in seeds]


def make_engines(cfg, params, **where):
    """GenerationEngines over ``params``. Batches bucket to N_QUERIES
    rows, so both tiers run the same compiled programs."""
    from repro.serving.engine import GenerationEngine

    return [GenerationEngine(cfg, p, max_new_tokens=N_NEW,
                             batch_floor=N_QUERIES,
                             **{k: v[j] for k, v in where.items()})
            for j, p in enumerate(params)]


# -- phase 1 -----------------------------------------------------------------


def phase_device(chips: int) -> dict:
    """The devices JAX sees. Raises unless the first is a TPU and there
    are at least ``chips`` of them."""
    import jax

    t0 = time.perf_counter()
    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: the first JAX device is "
                         f"{d0.platform!r} ({d0.device_kind}), not a TPU; "
                         f"this script never falls back to the CPU")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX sees {len(devs)}")
    _say("1 device", t0, f"{devs} platform {info['platform']} kind "
         f"{info['kind']} count {info['count']}")
    return info


# -- phase 2 -----------------------------------------------------------------


def phase_launcher(requests: int = 60, train_steps: int = 40, **build):
    """The launcher's path in process: build the toy-marketplace pipeline,
    serve one closed batch, then replay a Poisson trace through the
    tier scheduler. ``build`` overrides BuildConfig fields."""
    from repro.core.router import RouterConfig
    from repro.data import synthetic
    from repro.serving import BuildConfig, build_pipeline
    from repro.serving.ingress import poisson_arrivals

    t0 = time.perf_counter()
    build.setdefault("router", RouterConfig(top_lists=10, sample=256))
    pipe, _ = build_pipeline(BuildConfig(
        task="headlines", tiers=("GPT-J", "GPT-4"),
        train_steps_cap=train_steps, verbose=False, **build))
    test = synthetic.sample("headlines", requests, seed=77)
    served = pipe.serve(test.tokens)
    # a streamed chunk may hit what an earlier chunk cached, so which
    # rows hit depends on arrival times: compare with the cache off
    pipe.cache = None
    batch = pipe.serve(test.tokens)
    arrivals = poisson_arrivals(requests, 500.0, seed=77)
    stream = pipe.serve_stream(test.tokens, arrivals, max_chunk=32)
    _assert_same(batch, stream, "launcher serve vs serve_stream")
    acc = float((served.answers == test.labels).mean())
    _say("2 launcher", t0, f"{requests} requests, tiers "
         f"{served.tier_names} tier_counts {served.tier_counts}, cache "
         f"hits {served.cache_hits}, cost ${served.cost.sum():.6f}, "
         f"accuracy {acc:.3f} | cache off: serve == serve_stream "
         f"(Poisson, {stream.ingress['n_chunks']} chunks)")


# -- phase 3 -----------------------------------------------------------------


def reference_check(cfg, params, prompts, generated) -> dict:
    """A tier's greedy tokens against the argmax of an uncached float32
    forward over prompt + generated tokens (teacher-forced).

    The served tier computes in ``cfg.dtype``; where the served token's
    reference logit is within the rounding that dtype introduces of the
    top one, the argmax may flip, so such a position is excused. The
    excusing tolerance is measured, not assumed: an uncached forward in
    ``cfg.dtype`` on the same tokens shows how far that rounding moves
    the reference's own top-2 margin, doubled because the served path
    (padded, cached, perhaps sharded) rounds in a different order again.
    A floor of 1e-4 of the logit scale covers float32 reordering when
    ``cfg.dtype`` is float32 itself.
    """
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    seq = jnp.asarray(np.concatenate([prompts, generated[:, :-1]], axis=1))
    start = prompts.shape[1] - 1        # logits that produced generated[0]

    def logits(dtype):
        c = dataclasses.replace(cfg, dtype=dtype)
        fn = jax.jit(lambda p, t: T.forward_logits(p, {"tokens": t},
                                                   c)[:, start:])
        return fn(params, seq)

    with jax.default_matmul_precision("highest"):
        ref = logits("float32")                      # (b, N_NEW, V)
    low = logits(cfg.dtype)
    top, idx = jax.lax.top_k(ref, 2)
    low_top = jnp.take_along_axis(low, idx, axis=-1)
    margin = np.asarray(top[..., 0] - top[..., 1])
    got = jnp.take_along_axis(ref, jnp.asarray(generated)[..., None], -1)
    behind = np.asarray(top[..., 0] - got[..., 0])  # 0 where it is the top
    shift = float(jnp.max(jnp.abs((low_top[..., 0] - low_top[..., 1])
                                  - (top[..., 0] - top[..., 1]))))
    dev = float(jnp.max(jnp.abs(low - ref)))
    scale = float(jnp.max(jnp.abs(top[..., 0])))
    tol = max(2.0 * shift, 1e-4 * scale)
    want = np.asarray(idx[..., 0])
    agree = want == generated
    excused = ~agree & (behind < tol)
    wrong = ~agree & ~excused
    assert not wrong.any(), (
        f"greedy tokens differ from the uncached reference at "
        f"{np.argwhere(wrong).tolist()}, {behind[wrong].tolist()} below "
        f"the top logit (top-2 margins {margin[wrong].tolist()}), at "
        f"tolerance {tol}")
    assert excused.mean() <= 0.5, (
        f"{int(excused.sum())}/{agree.size} positions excused as near-ties "
        f"at tolerance {tol}: the check would show nothing")
    return {"positions": int(agree.size), "match": int(agree.sum()),
            "excused": int(excused.sum()), "tol": tol, "shift": shift,
            "dev": dev, "scale": scale, "ref_head": ref[:, :2]}


def phase_cascade(cfg) -> dict:
    """Two full-width tiers behind the pipeline; every batch compaction
    mode and the stream scheduler give identical outputs, and tier 0's
    tokens match the uncached reference."""
    from repro.core.cascade import COMPACT_MODES

    t0 = time.perf_counter()
    params = init_tiers(cfg)
    engines = make_engines(cfg, params)
    toks = cascade_prompts(cfg)
    runs, gen0 = {}, None
    for compact in COMPACT_MODES:
        pipe, log = cascade_pipeline(engines, compact)
        runs[f"serve/{compact}"] = pipe.serve(toks)
        gen0 = log[0][0][1] if gen0 is None else gen0
    pipe, _ = cascade_pipeline(engines)
    runs["serve_stream"] = stream = pipe.serve_stream(toks,
                                                      max_chunk=N_QUERIES)
    ref = runs["serve/host"]
    for tag, res in runs.items():
        _assert_same(ref, res, tag)
    assert ref.tier_counts == [N_QUERIES, N_QUERIES // 4], ref.tier_counts
    chk = reference_check(cfg, params[0], toks[:N_CHECK], gen0[:N_CHECK])
    compiles = [(e.compile_stats["prefill_compiles"],
                 e._decode._cache_size()) for e in engines]
    _say("3 cascade", t0, (
        f"{cfg.name} {cfg.n_layers} layers d_model {cfg.d_model} vocab "
        f"{cfg.vocab} dtype {cfg.dtype}, {N_QUERIES} queries with prompts "
        f"{PROMPT_LEN[0]}-{PROMPT_LEN[1]} tokens, n_new {N_NEW} | "
        f"tier_counts {ref.tier_counts}, cost ${ref.cost.sum():.6f} | "
        f"{' == '.join(runs)} (stream chunks "
        f"{stream.ingress['chunks_per_tier']}) | tier-0 greedy tokens vs "
        f"uncached float32 forward: {chk['match']}/{chk['positions']} "
        f"match, {chk['excused']} excused near-ties within "
        f"{chk['tol']:.4g} of the top logit (2 x the largest top-2 margin "
        f"shift a "
        f"{cfg.dtype} uncached forward shows, {chk['shift']:.4g}; max "
        f"|{cfg.dtype} - float32| logit {chk['dev']:.4g} at logit scale "
        f"{chk['scale']:.4g}) | (prefill, decode) compiles per tier "
        f"{compiles} | peak device memory {_peak_memory()}"))
    return {"params": params, "engines": engines, "prompts": toks,
            "generated": gen0, "check": chk}


# -- phase 4 -----------------------------------------------------------------


def phase_kernels(cfg, params, engine, prompts, ref_head) -> dict:
    """The tier-0 weights served with ``enable_kernels(True)``: flash
    prefill on every layer, the decode kernel on the global layers.

    Runs phase 3's prompts (padded to a 256-token bucket, which admits
    the flash kernel) through the engine's prefill and one decode step,
    with the kernels and on ``engine``'s served path (jnp decode; on a
    TPU its prefill takes the flash kernel without the switch). ``ref_head`` holds the
    uncached float32 logits of the first prompts at the same two
    positions (phase 3's reference). Both paths compute in ``cfg.dtype``
    and each lands some distance from float32; two paths as accurate as
    the jnp path differ by at most twice its distance, so that is the
    tolerance on |kernel - jnp|.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import enable_kernels, interpret_mode
    from repro.models import transformer as T
    from repro.serving.engine import GenerationEngine

    t0 = time.perf_counter()
    n, width = prompts.shape
    key = engine.bucket_key(n, width, N_NEW)
    # the flash kernel takes 128-row blocks of the bucketed prompt
    assert key[1] % 128 == 0 and key[0] == n, key
    toks = np.full(key[:2], PAD, np.int32)
    toks[:, :width] = prompts
    last, pos = jnp.int32(width - 1), jnp.int32(width)

    def step():
        # a fresh function per trace: the kernel switch is read while
        # tracing, and jax reuses the trace of a function it has seen
        return lambda p, c, t, s: T.decode_step(p, c, t, s, cfg)

    def compiled(fn, *args):
        c = jax.jit(fn).lower(*args).compile()
        return c, "tpu_custom_call" in c.as_text()

    pre_j, cache = engine._prefill_fn(key)(params, toks, last)
    tok = jnp.argmax(pre_j[:, -1], -1)[:, None].astype(jnp.int32)
    enable_kernels(True)
    try:
        eng_k = GenerationEngine(cfg, params, max_new_tokens=N_NEW,
                                 batch_floor=engine.batch_floor)
        run_pre_k, pre_kernel = compiled(eng_k._prefill_fn(key), params,
                                         toks, last)
        run_dec_k, dec_kernel = compiled(step(), params, cache, tok, pos)
    finally:
        enable_kernels(False)
    run_dec_j, dec_plain = compiled(step(), params, cache, tok, pos)
    if not interpret_mode():
        assert pre_kernel and dec_kernel, (
            f"Mosaic kernel missing: prefill {pre_kernel}, decode "
            f"{dec_kernel}")
        assert not dec_plain, "the jnp decode path holds a kernel"
    pre_k = run_pre_k(params, toks, last)[0]
    dec_k = run_dec_k(params, cache, tok, pos)[0]
    dec_j = run_dec_j(params, cache, tok, pos)[0]
    # (rows, 2 positions, V): the prefill's last position, then the step
    kern = jnp.concatenate([pre_k, dec_k], axis=1)
    plain = jnp.concatenate([pre_j, dec_j], axis=1)
    m = ref_head.shape[0]
    err_j = float(jnp.max(jnp.abs(plain[:m] - ref_head)))
    err_k = float(jnp.max(jnp.abs(kern[:m] - ref_head)))
    gap = float(jnp.max(jnp.abs(kern[:m] - plain[:m])))
    gap_all = float(jnp.max(jnp.abs(kern - plain)))
    tol = 2.0 * err_j
    assert gap <= tol, (gap, tol)
    _say("4 kernels", t0, (
        f"enable_kernels(True), {n} prompts bucketed to {key[1]} tokens, "
        f"cache {key[2]} | tpu_custom_call in compiled prefill "
        f"{pre_kernel}, decode {dec_kernel} (jnp decode {dec_plain}) | "
        f"max |kernel - jnp| logit over the {m} reference prompts "
        f"{gap:.4g} <= {tol:.4g} (2 x the jnp path's own max |{cfg.dtype} "
        f"- float32| logit error {err_j:.4g}; kernel path's error "
        f"{err_k:.4g}); over all {n} prompts {gap_all:.4g} | peak device "
        f"memory {_peak_memory()}"))
    return {"gap": gap, "gap_all": gap_all, "tol": tol, "err_jnp": err_j,
            "err_kernel": err_k, "kernel_in_prefill": pre_kernel,
            "kernel_in_decode": dec_kernel}


# -- phase 5 -----------------------------------------------------------------


def _param_devices(engine) -> list[int]:
    import jax

    return sorted({d.id for leaf in jax.tree.leaves(engine.params)
                   for d in leaf.devices()})


def held_to_reference(cfg, params, base_call, call) -> str:
    """One tier call of a sharded run against the same call on one
    device. Rows whose greedy tokens differ are checked against the
    uncached reference as in phase 3; returns a summary."""
    (prompts, want), (prompts_s, got) = base_call, call
    assert np.array_equal(prompts, prompts_s), "tier inputs differ"
    rows = np.flatnonzero((want != got).any(axis=1))
    if not len(rows):
        return f"0/{len(got)} rows differ"
    chk = reference_check(cfg, params, prompts[rows], got[rows])
    return (f"{len(rows)}/{len(got)} rows differ; their tokens vs the "
            f"uncached reference {chk['match']}/{chk['positions']} match, "
            f"{chk['excused']} excused within {chk['tol']:.4g} of the top "
            f"logit")


def phase_four_chips(cfg, n_chips: int = 4) -> dict:
    """The phase-3 cascade on one device, then with each tier pinned to
    a chip of its own (``plan_placement``), then with each tier sharded
    over a slice of a 4x1 mesh (``plan_tier_meshes``).

    Placed tiers run the one-device programs on other chips: batch and
    stream must equal the one-device run exactly. Sharded tiers run
    other programs, which round differently: the partitioner may split
    a contraction over the slice and all-reduce partial sums, and each
    chip multiplies its share of the rows, which the TPU compiler tiles
    along the contraction by row count. So a greedy token can flip where
    two logits nearly tie. Their cost,
    stopped_at and tier_counts must still equal the one-device run, their
    stream must equal their batch exactly, and every row whose tokens
    differ from the one-device run is held to the uncached reference.
    """
    import jax

    from repro.sharding.placement import plan_placement
    from repro.sharding.tier_mesh import mesh_desc, plan_tier_meshes

    t0 = time.perf_counter()
    devs = jax.devices()[:n_chips]
    assert len(devs) == n_chips, devs
    params = init_tiers(cfg)
    toks = cascade_prompts(cfg)
    base_pipe, base_log = cascade_pipeline(make_engines(cfg, params))
    base = base_pipe.serve(toks)
    placement = plan_placement(len(params), devices=devs,
                               tier_counts=base.tier_counts)
    plan = plan_tier_meshes(len(params), mesh_shape=(n_chips, 1),
                            devices=devs)
    assert placement.n_distinct == len(params), placement.describe()
    assert plan.n_distinct == len(params), plan.describe()
    legs = {"placed": make_engines(cfg, params,
                                   device=placement.devices),
            "sharded": make_engines(cfg, params, mesh=plan.slices)}
    where, sharded = [], []
    for name, engines in legs.items():
        print(f"phase 5: {name} leg", file=sys.stderr, flush=True)
        pipe, log = cascade_pipeline(engines)
        res = pipe.serve(toks)
        exact = name == "placed"
        _assert_same(base, res, f"{name}/serve", answers=exact)
        _assert_same(res, pipe.serve_stream(toks, max_chunk=N_QUERIES),
                     f"{name}/serve_stream")
        if not exact:
            sharded = [f"tier{j} " + held_to_reference(
                cfg, params[j], base_log[j][0], log[j][0])
                for j in range(len(params))]
        for j, eng in enumerate(engines):
            at = (f"{eng.device.platform}:{eng.device.id}"
                  if eng.device is not None else mesh_desc(eng.mesh))
            where.append(f"{name} tier{j} -> {at} (params on devices "
                         f"{_param_devices(eng)})")
    _say("5 four chips", t0, (
        f"{cfg.name} cascade, tier_counts {base.tier_counts} | "
        f"{'; '.join(where)} | placed serve and serve_stream bit-identical "
        f"to the one-device run (answers, cost, stopped_at, tier_counts) | "
        f"sharded serve and serve_stream: cost, stopped_at, tier_counts "
        f"identical, answers {'; '.join(sharded)} | peak device memory "
        f"{_peak_memory()}"))
    return {"base": base, "placement": placement, "plan": plan}


# -- entry point -------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip placement and "
                         "sharding phase")
    args = ap.parse_args(argv)
    if not __debug__:
        print("chip_smoke: its checks are asserts, which -O removes",
              file=sys.stderr)
        return 2
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no program next to this script ({src} is "
              f"missing); run it from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.configs.registry import ARCHS
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    info = phase_device(args.chips)
    cfg = ARCHS[ARCH]
    if args.chips == 4:
        phase_four_chips(cfg)
    else:
        phase_launcher()
        out = phase_cascade(cfg)
        phase_kernels(cfg, out["params"][0], out["engines"][0],
                      out["prompts"], out["check"]["ref_head"])
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
