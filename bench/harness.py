"""One run of one cell: set-up, the measured window, the check, the line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, its traffic mix ``bench/traffic/<traffic>.json``
and the mix's arrival process ``bench/arrivals/<arrivals>.py``, each
tier's family program adapter, reference and counts
(``bench/families/<family>_{program,reference,counts}.py``) and one
reader per metric (``bench/metrics/<metric>.py``, a ``read(run)`` that
returns a number, or None where it finds nothing to read).

The tiers of a cell are the configurations its configuration file lists
under ``tiers`` (names of ``BENCHMARK.json`` configurations, cheapest
first), or else two tiers of the configuration itself. The window drives
the program as users drive it: one ``GenerationEngine`` per tier (weights
from a seed drawn from the run's seed and the tier's place, tier j priced
10**j times the first) behind a ``ServingPipeline``, served through
``serve_stream`` and its ``TierScheduler``, with the benchmark's scorer.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import check, traffic as traffic_mod

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: tier j is priced (per 10M tokens) at 10**j times this; the powers of
#: two keep every charged product exact in float32 (check.tier_cost)
BASE_PRICE = {"per_10m_input": 1e7 * 2.0 ** -20, "per_10m_output": 1e7 * 2.0 ** -19}


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list
    root: Path = ROOT
    tier_configs: list = dataclasses.field(default_factory=list)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    files = {c["name"]: root / c["file"] for c in spec["configs"]}
    config = json.loads(files[w["config"]].read_text())
    tier_configs = [config if t == w["config"] else json.loads(files[t].read_text())
                    for t in config.get("tiers", [w["config"]] * 2)]
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    reports = lambda m: name in m.get("workloads", [name])  # noqa: E731
    e2e = [m for m in spec["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in e2e_names else [])]
    return Cell(name, int(w["chips"]), config, mix, e2e, layer, root, tier_configs)


def family(config: dict, part: str, root: Path = ROOT):
    """A configuration's family ``program``, ``reference`` or ``counts``
    module, ``bench/families/<family>_<part>.py``."""
    return traffic_mod.load(root / "bench" / "families" / f"{config['family']}_{part}.py")


def metric_reader(name: str, root: Path = ROOT):
    """``read`` of ``bench/metrics/<name>.py``."""
    return traffic_mod.load(root / "bench" / "metrics" / f"{name}.py").read


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def tier_seed(seed: int, tier: int) -> int:
    """A 32-bit weight seed for one tier, from any whole run seed."""
    return int(np.random.SeedSequence([int(seed), 1000 + tier]).generate_state(1)[0])


def tier_prices(tier: int) -> dict:
    return {k: v * 10 ** tier for k, v in BASE_PRICE.items()}


@dataclasses.dataclass
class Tier:
    """One tier of a cell's cascade as the benchmark sees it."""

    index: int
    config: dict
    seed: int                      # the run's seed
    root: Path = ROOT

    @property
    def program(self):
        return family(self.config, "program", self.root)

    @property
    def reference(self):
        return family(self.config, "reference", self.root)

    @property
    def counts(self):
        return family(self.config, "counts", self.root)

    @property
    def prices(self) -> dict:
        return tier_prices(self.index)

    def weights(self):
        """The tier's weights, made on the device from the run's seed."""
        return self.program.make_params(self.config, tier_seed(self.seed, self.index))


def tiers_of(cell: Cell, seed: int) -> list[Tier]:
    return [Tier(j, c, seed, cell.root) for j, c in enumerate(cell.tier_configs)]


@dataclasses.dataclass
class Run:
    """What the metric readers see."""

    cell: Cell
    traffic: object
    tiers: list = dataclasses.field(default_factory=list)
    served: object = None          # the program's ServeResult
    calls: list = dataclasses.field(default_factory=list)
    setup_s: float = 0.0
    window_s: float = 0.0          # start of the stream -> last answer
    memory_peak_bytes: int | None = None
    trace: object = None           # bench.trace.Reduced, traced runs only
    peak: dict | None = None
    window_compiles: int = 0
    setup_parts: dict = dataclasses.field(default_factory=dict)

    @property
    def latencies(self) -> np.ndarray:
        """Due time -> answer of every request due in the window, in
        seconds; a request that was not answered counts as infinite."""
        lat = np.asarray(self.served.ingress["request_latency"], np.float64)
        missing = len(self.traffic.prompts) - len(lat)
        return np.concatenate([lat, np.full(missing, np.inf)])


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile: an observed value, never interpolated
    across a missing (infinite) one."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


class _CompileMonitor:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache) per phase of the run, and how many programs it
    compiled or loaded in each: a sound window has none."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load"}

    def __init__(self):
        import jax

        self.phase = "setup"
        self.seconds: dict = {}
        self.programs: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw):
        kind = self.EVENTS.get(event)
        if kind is None or self.phase is None:
            return
        key = (self.phase, kind)
        self.seconds[key] = self.seconds.get(key, 0.0) + secs
        if kind == "compile":
            self.programs[self.phase] = self.programs.get(self.phase, 0) + 1


def build(cell: Cell, tiers: list, traffic):
    """The system under test: engines, pipeline and the tier-call log."""
    import jax

    from repro.core.cost import ApiCost
    from repro.serving.engine import GenerationEngine
    from repro.serving.pipeline import ServingPipeline, TierSpec

    n_new = traffic.new_tokens
    engines = [GenerationEngine(t.program.program_config(t.config), t.weights(),
                                max_new_tokens=n_new)
               for t in tiers]
    calls: list = []
    generated = [dict() for _ in tiers]
    request = {row.tobytes(): i for i, row in enumerate(traffic.prompts)}

    def tier(t, eng):
        span = f"bench.tier{t.index}"

        def answer(tokens):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(span):
                gen = eng.generate(tokens, n_new)
            t1 = time.perf_counter()
            keys = [row.tobytes() for row in tokens]
            for k, g in zip(keys, gen):
                generated[t.index].setdefault(k, g)
            # rows that pad the chunk repeat its last request
            rows = list(dict.fromkeys(request.get(k, -1) for k in keys))
            calls.append({"tier": t.index, "rows": len(rows), "requests": rows,
                          "padded_rows": len(tokens), "prompt": tokens.shape[1],
                          "n_new": n_new, "t0": t0, "t1": t1})
            return np.asarray([check.answer_id(g) for g in gen], np.int64)

        p = t.prices
        return TierSpec(f"{t.config['name']}/tier{t.index}", answer,
                        ApiCost(p["per_10m_input"], p["per_10m_output"]),
                        n_out=n_new)

    n_tiers = len(tiers)

    def scorer(tokens, answers):
        with jax.profiler.TraceAnnotation("bench.scorer"):
            return traffic_mod.scores(tokens, n_tiers)

    pipe = ServingPipeline(
        tiers=[tier(t, e) for t, e in zip(tiers, engines)],
        thresholds=traffic_mod.thresholds(n_tiers), scorer=scorer,
        pad_token=0, batch_size=cell.mix["max_chunk"])
    return engines, pipe, calls, generated


def warm_up(engines, pipe, traffic, max_chunk: int):
    """Run every shape the window can use once: each engine's prefill and
    decode programs at every batch bucket a chunk can fill, and each
    tier's price model at every chunk size and over the whole request
    set, as the pipeline calls it (token counts as int64 arrays)."""
    import jax

    prompts, n_new = traffic.prompts, traffic.new_tokens
    width = prompts.shape[1]
    for eng in engines:
        buckets = sorted({eng.bucket_key(b, width, n_new)[0]
                          for b in traffic_mod.chunk_buckets(max_chunk)})
        for b in buckets:
            rows = np.resize(prompts, (b, width))
            # two new tokens reach the same prefill and decode programs
            # as the full count whenever the cache bucket is the same
            n = 2 if eng.bucket_key(b, width, 2) == eng.bucket_key(b, width, n_new) else n_new
            eng.generate(rows, n)
    sizes = traffic_mod.chunk_buckets(max_chunk) + [len(prompts)]
    for spec in pipe.tiers:
        for b in sizes:
            jax.block_until_ready(spec.price.query_cost(
                np.full(b, width, np.int64), np.full(b, spec.n_out, np.int64)))
    warm_together(engines, prompts, n_new, max_chunk)


def warm_together(engines, prompts, n_new: int, max_chunk: int):
    """Every tier runs a chunk of ``max_chunk`` rows at once, one thread a
    tier as the scheduler's workers run them, three times, each round
    starting another tier first. The tiers share the chip, so the window's
    peak of device memory is one tier's largest program running while the
    others hold their largest caches; left to the window, whether that
    meeting happens depends on how its chunks fall. Here it happens in
    every run, so ``memory_peak_bytes`` reads the capacity the cell needs."""
    from concurrent.futures import ThreadPoolExecutor

    if len(engines) < 2:
        return
    width = prompts.shape[1]
    rows = np.resize(prompts, (max_chunk, width))

    def n_for(eng):
        same = eng.bucket_key(max_chunk, width, 2) == eng.bucket_key(max_chunk, width, n_new)
        return 2 if same else n_new

    with ThreadPoolExecutor(len(engines)) as pool:
        for r in range(3):
            order = engines[r % len(engines):] + engines[: r % len(engines)]
            for f in [pool.submit(eng.generate, rows, n_for(eng)) for eng in order]:
                f.result()


def serve_window(pipe, traffic, mix: dict):
    import jax

    with jax.profiler.TraceAnnotation("bench.serve"):
        return pipe.serve_stream(traffic.prompts, traffic.arrivals,
                                 max_chunk=mix["max_chunk"],
                                 holdback=mix["holdback_s"])


def window_seconds(served, traffic) -> float:
    """Start of the stream to its last answer: the latest due time plus
    latency where every request was answered, else the stream's wall."""
    lat = np.asarray(served.ingress["request_latency"], np.float64)
    if len(lat) == len(traffic.arrivals) and len(lat):
        return float(np.max(traffic.arrivals + lat))
    return float(served.latency["total"])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             device_kind: str | None = None, controls: bool = False) -> dict:
    """Set up, measure, check; returns the result line's fields. With
    ``controls`` the float8 control is also put in the program's place on
    the same sample and judged by the same limits (``out["control"]``);
    that reading sets limits and is never part of ``correct``."""
    import jax

    monitor = _CompileMonitor()
    t_imported = time.perf_counter()
    tiers = tiers_of(cell, seed)
    vocab = min(t.config["vocab_size"] for t in tiers)
    tr = traffic_mod.generate(cell.mix, vocab, seed, seconds, len(tiers), cell.root)
    run = Run(cell, tr, tiers)
    if device_kind is not None:
        run.peak = peaks(device_kind)
    engines, pipe, calls, generated = build(cell, tiers, tr)
    jax.block_until_ready([e.params for e in engines])
    t_built = time.perf_counter()
    warm_up(engines, pipe, tr, cell.mix["max_chunk"])
    run.setup_s = time.perf_counter() - t0
    run.setup_parts = {"imports_and_device": t_imported - t0,
                       "traffic_and_weights": t_built - t_imported,
                       "warm_up": t0 + run.setup_s - t_built,
                       **{f"jax_{k}_s": v for (p, k), v in monitor.seconds.items()
                          if p == "setup"}}

    monitor.phase = "window"
    if trace:
        from bench import trace as trace_mod

        with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
            with trace_mod.capture(d):
                served = serve_window(pipe, tr, cell.mix)
            monitor.phase = None
            run.trace = trace_mod.Reduced(trace_mod.extract(d))
    else:
        served = serve_window(pipe, tr, cell.mix)
    monitor.phase = None
    run.served, run.calls = served, calls
    run.window_compiles = monitor.programs.get("window", 0)
    run.window_s = window_seconds(served, tr)
    peaks_in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in jax.devices()[: cell.chips]]
    run.memory_peak_bytes = max((p for p in peaks_in_use if p is not None),
                                default=None)

    # the program's state goes before the reference runs
    del engines, pipe
    gc.collect()
    numbers = {"cascade_mismatches": check.cascade_mismatches(
        served, tr, generated, [t.prices for t in tiers])}
    sample = traffic_mod.check_sample(tr, calls, cell.mix["check_per_tier"], seed,
                                      len(tiers))
    gaps = check.token_gaps(tiers, tr, sample, generated, controls=controls)
    numbers["logit_gap"] = gaps["logit_gap"]
    limits = {"cascade_mismatches": 0, "logit_gap": cell.config["logit_gap_limit"]}

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"], cell.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    n = len(tr.prompts)
    answered = sum(a is not None for a in served.answers)
    out = {"run": run, "correct": check.verdict(numbers, limits), "attempted": n,
           "failed": n - answered, "metrics": metrics,
           "checks": {k: {"value": numbers[k], "limit": limits[k]} for k in limits},
           "tokens_checked": gaps["tokens_checked"]}
    if controls:
        low = dict(numbers, logit_gap=gaps["control_logit_gap"])
        out["control"] = {"correct": check.verdict(low, limits),
                          "checks": {k: {"value": low[k], "limit": limits[k]}
                                     for k in limits}}
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips(cell: Cell):
    """The devices the cell runs on; raises NoChip off the accelerator."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (first device: {devs[0].platform} "
                     f"{devs[0].device_kind}); the benchmark never runs on the CPU")
    if len(devs) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX sees {len(devs)}")
    return devs[: cell.chips]


def use_compile_cache(root: Path = ROOT) -> Path:
    """JAX's persistent compilation cache at the fixed ``<checkout>/.jax_cache``
    (the path is part of the cache key), every program cached and none
    evicted. With eviction on, JAX reads an access-time file beside every
    entry before it writes one, and an entry written without it (by a
    process with eviction off) makes every later write fail."""
    import jax

    path = root / ".jax_cache"
    path.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv, t0: float) -> int:
    args = parse(argv)
    cell = load_cell(args.workload)
    use_compile_cache()
    try:
        devs = chips(cell)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0,
                   device_kind=devs[0].device_kind)
    run = out["run"]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"], "device": device,
            "window_compiles": run.window_compiles, "setup_parts": run.setup_parts}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.top_ops,
                             "idle_gaps": run.trace.idle_gaps}
    line["checks"] = out["checks"]
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(_finite(line)))
    return 0


def _finite(x):
    """Strict JSON: a number that is not finite is written as a string."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x
