"""Spans and counters of the served path.

**Spans** are ``jax.profiler.TraceAnnotation``s: they land on the
profiler's host plane, on the clock its device planes use, so a captured
trace shows what the host was doing while the device waited. With no
profile being captured a span costs a flag check, and its attributes are
formatted only while one is. The names are stable (no tier index or
shape in them):

=======================  ==================================================
``serve.stream``         one served stream (either stream back-end)
``sched.admit``          embed, cache lookup and routing of one admission
``sched.chunk``          one tier chunk's ``tier_step`` (or a speculative
                         pre-invoke)
``cascade.invoke``       the tier call inside ``tier_step``
``cascade.score``        the scorer call inside ``tier_step``
``engine.prefill``       host padding and dispatch of one prefill
``engine.decode``        one whole decode loop
``engine.decode.fetch``  the host blocked on a token: the wait for the
                         device and the copy back
``engine.decode.dispatch``  host work from a token on the host to the next
                         decode step enqueued
=======================  ==================================================

**Counters**: a stream back-end opens a ``ChunkCounters`` record around
each chunk (``counting``) on the thread that runs it; the engine and
``tier_step`` add to the record open in their context and add nothing
when none is, so warm-up, direct ``generate`` calls and the offline
executor count nothing. ``StreamTelemetry`` folds each record into its
tier's totals and keeps the chunk and request-visit records that
``ServeResult.ingress`` publishes (``tier_wait``, ``tier_counters``,
``spans``, ``t0_ns``).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

STREAM = "serve.stream"
ADMIT = "sched.admit"
CHUNK = "sched.chunk"
INVOKE = "cascade.invoke"
SCORE = "cascade.score"
PREFILL = "engine.prefill"
DECODE = "engine.decode"
DECODE_FETCH = "engine.decode.fetch"
DECODE_DISPATCH = "engine.decode.dispatch"
#: in-memory record of one request's stay in one tier: queue and chunk
VISIT = "request.visit"


def span(name: str, **attrs):
    """A profiler span; ``attrs`` are attached only while a profile is
    being captured."""
    if attrs and TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **attrs)
    return TraceAnnotation(name)


@dataclasses.dataclass
class ChunkCounters:
    """Host work of one chunk, added to by the layers that do it."""

    prefill_calls: int = 0
    prefill_flash_calls: int = 0        # of them, attention in the flash
                                        # kernel
    prefill_dispatch_s: float = 0.0     # host padding + dispatch
    decode_steps: int = 0
    decode_dispatch_s: float = 0.0      # host from a token to the next step
    decode_fetch_s: float = 0.0         # host blocked on tokens (the first
                                        # one waits on the prefill)
    cascade_s: float = 0.0              # tier_step outside the tier call


COUNTERS = tuple(f.name for f in dataclasses.fields(ChunkCounters))
_open: contextvars.ContextVar[ChunkCounters | None] = contextvars.ContextVar(
    "repro_chunk_counters", default=None)


def current() -> ChunkCounters | None:
    """The record open in this context, or None."""
    return _open.get()


@contextlib.contextmanager
def counting(rec: ChunkCounters):
    """Open ``rec`` for the work done inside the block, on this thread."""
    token = _open.set(rec)
    try:
        yield rec
    finally:
        _open.reset(token)


class StreamTelemetry:
    """Per-tier counter totals and span records of one stream. The caller
    serializes ``fold`` (the parallel scheduler holds its lock)."""

    def __init__(self, n_tiers: int):
        self.t0_ns: int | None = None
        self.totals = [dict(chunks=0, **ChunkCounters().__dict__)
                       for _ in range(n_tiers)]
        self.records: list[dict] = []

    def start(self):
        """Mark the stream clock's zero on the profiler's clock
        (``time.time_ns``): record times ``start``/``end`` are seconds
        after ``t0_ns``."""
        self.t0_ns = time.time_ns()

    def fold(self, j: int, rec: ChunkCounters, batch=None,
             start: float | None = None, end: float | None = None):
        """Add ``rec`` to tier j's totals. With ``batch`` (the chunk's
        requests, before any moves on to the next tier) it was a cascade
        chunk: count it and record its span, ``start``-``end`` on the
        stream clock, and one visit per request, from entering tier j's
        queue to the chunk's end."""
        tot = self.totals[j]
        for k in COUNTERS:
            tot[k] += getattr(rec, k)
        if batch is None:
            return
        tot["chunks"] += 1
        cid = len(self.records)
        self.records.append({"name": CHUNK, "id": cid, "parent": None,
                             "tier": j, "rows": len(batch),
                             "start": start, "end": end})
        for r in batch:
            self.records.append({"name": VISIT, "id": len(self.records),
                                 "parent": cid, "tier": j, "rows": 1,
                                 "rid": r.rid, "start": r.t_enqueued,
                                 "end": end})

    def publish(self, answered) -> dict:
        """The ``ServeResult.ingress`` keys; ``tier_wait`` follows the
        order of ``answered``."""
        return {
            "tier_wait": np.asarray([r.tier_wait for r in answered],
                                    np.float64),
            "tier_counters": [dict(t) for t in self.totals],
            "spans": list(self.records),
            "t0_ns": self.t0_ns,
        }
