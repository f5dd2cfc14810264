"""From a profiler trace to device busy time, program time and idle gaps.

``capture`` records a JAX profiler trace of a block; ``extract`` reads
the ``.xplane.pb`` it wrote into a plain dict of interval lists (the form
a small recorded trace is kept in for the tests); ``Reduced`` turns that
into the numbers the per-layer metrics read:

- busy: the union of the intervals in which a program ran on a device,
  clipped to the window (the host span ``bench.serve`` around the served
  window), averaged over the devices used;
- program time: the summed device time of one program's executions,
  by its module name (``jit_<function>``, with any ``(<id>)`` suffix
  dropped);
- idle gaps: the parts of the window in which no program ran, grouped by
  the benchmark's own host spans open at the time (``bench.tier0``,
  ``bench.tier1``, ``bench.scorer``), which say what the host was doing.
"""
from __future__ import annotations

import collections
import contextlib
import glob
import os
import re

#: host spans the benchmark opens; the window is the first
WINDOW_SPAN = "bench.serve"
SPAN_PREFIX = "bench."
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace the block: device activity and the host's annotations, with
    the Python call tracer off (it would slow the host it measures)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _device_index(plane_name: str) -> int | None:
    m = re.match(r"/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def extract(log_dir: str) -> dict:
    """{"modules": {device: [[name, start_ns, end_ns], ...]}, "ops": {...},
    "spans": [[name, start_ns, end_ns], ...]} from the trace in
    ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    out: dict = {"modules": {}, "ops": {}, "spans": []}
    for plane in data.planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            if dev is not None and line.name in (MODULE_LINE, OP_LINE):
                key = "modules" if line.name == MODULE_LINE else "ops"
                out[key][str(dev)] = [[e.name, e.start_ns, e.end_ns]
                                      for e in line.events]
            elif dev is None and plane.name.startswith("/host"):
                out["spans"] += [[e.name, e.start_ns, e.end_ns]
                                 for e in line.events
                                 if e.name.startswith(SPAN_PREFIX)]
    return out


#: ops whose events hold the events of the ops they run: counting them
#: would count those twice
CONTAINERS = ("while", "conditional", "call")


def op_name(op: str) -> str:
    """An op event's HLO text ``%fusion.12 = bf16[8,5120]{...} fusion(...)``
    -> ``fusion.12 bf16[8,5120]``: its name and its result's type."""
    head, _, rest = op.partition(" = ")
    shape = re.match(r"[\w\[\],]+", rest)
    return (head.lstrip("%") + (" " + shape.group(0) if shape else "")).strip()


def program_name(module: str) -> str:
    """``jit_fn(1234)`` -> ``jit_fn``."""
    return re.sub(r"\(\d+\)$", "", module).strip()


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


class Reduced:
    """Numbers of one traced window. Times in seconds."""

    def __init__(self, events: dict, top: int = 10):
        windows = [(a, b) for n, a, b in events["spans"] if n == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        self.lo, self.hi = windows[0]
        self.window_s = (self.hi - self.lo) * 1e-9
        mods = events["modules"]
        self.devices = sorted(mods)
        busy = {d: _union([(a, b) for _, a, b in mods[d]], self.lo, self.hi)
                for d in self.devices}
        self.busy_s = (sum(b - a for d in busy for a, b in busy[d])
                       / max(1, len(busy)) * 1e-9)
        self._program = collections.Counter()
        for d in self.devices:
            for name, a, b in mods[d]:
                a, b = max(a, self.lo), min(b, self.hi)
                if b > a:
                    self._program[program_name(name)] += (b - a) * 1e-9
        ops = collections.Counter()
        for d, evs in events.get("ops", {}).items():
            for name, a, b in evs:
                name = op_name(name)
                a, b = max(a, self.lo), min(b, self.hi)
                if b > a and not name.startswith(CONTAINERS):
                    ops[name] += (b - a) * 1e-9 / max(1, len(self.devices))
        self.top_ops = [[n, s] for n, s in ops.most_common(top)]
        first = busy[self.devices[0]] if self.devices else []
        self.idle_gaps = self._gaps(first, events["spans"], top)

    def program_seconds(self, name: str) -> float:
        """Device seconds of program ``name`` in the window, summed over
        the devices used."""
        return self._program.get(name, 0.0)

    @property
    def programs(self) -> dict:
        return dict(self._program)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def _gaps(self, busy, spans, top: int) -> list:
        """Idle time of the first device, by the host spans open then."""
        gaps, t = [], self.lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.hi > t:
            gaps.append((t, self.hi))
        # one sweep over gap and span edges: between two edges, idle time
        # goes to the label of the spans open then
        edges = [(a, 1, "gap") for a, _ in gaps] + [(b, -1, "gap") for _, b in gaps]
        for n, a, b in spans:
            if n != WINDOW_SPAN:
                edges += [(a, 1, n), (b, -1, n)]
        edges.sort(key=lambda e: (e[0], e[1]))
        open_: collections.Counter = collections.Counter()
        by_label: collections.Counter = collections.Counter()
        t_prev = None
        for t, step, name in edges:
            if t_prev is not None and open_["gap"] > 0 and t > t_prev:
                label = "+".join(sorted(n for n, c in open_.items()
                                        if c > 0 and n != "gap"))
                by_label[label or "no tier call"] += (t - t_prev) * 1e-9
            open_[name] += step
            t_prev = t
        return [[n, s] for n, s in by_label.most_common(top)]

