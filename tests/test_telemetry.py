"""Spans and counters of the served path (repro.core.telemetry): per-tier
host counters, summed tier-queue waits and chunk/visit span records
published by both stream back-ends, and the profiler spans' nesting."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core import telemetry
from repro.core.cost import ApiCost
from repro.models import transformer as T
from repro.serving.engine import GenerationEngine
from repro.serving.pipeline import ServingPipeline, TierSpec

N_NEW = 4
WIDTH = 8
BACKENDS = pytest.mark.parametrize("parallel", [True, False],
                                   ids=["scheduler", "serial"])


@pytest.fixture(scope="module")
def engines():
    cfg = ARCHS["gemma3-1b"].reduced()
    engs = [GenerationEngine(cfg, T.init_params(jax.random.PRNGKey(k), cfg))
            for k in range(2)]
    rows = np.ones((4, WIDTH), np.int32)
    for eng in engs:                # warm-up: outside any stream
        eng.generate(rows, N_NEW)
    return engs


def _pipeline(engines):
    """Two engine-backed tiers; a leading token divisible by 4 escalates."""

    def tier(k, eng):
        return TierSpec(f"tier{k}",
                        lambda t: eng.generate(t, N_NEW)[:, 0].astype(np.int64),
                        ApiCost(1.0 + k, 2.0 + k), n_out=N_NEW)

    return ServingPipeline(
        tiers=[tier(k, e) for k, e in enumerate(engines)], thresholds=[0.5],
        scorer=lambda t, a: np.where(t[:, 0] % 4 == 0, 0.1, 0.9),
        pad_token=-1, batch_size=4)


def _tokens(n=16):
    toks = np.random.default_rng(0).integers(1, 200, (n, WIDTH)).astype(np.int32)
    toks[:, 0] = np.arange(n)       # a quarter escalate
    return toks


def _serve(engines, parallel, n=16):
    toks = _tokens(n)
    return _pipeline(engines).serve_stream(
        toks, np.linspace(0.0, 0.1, n), max_chunk=4, parallel=parallel)


@pytest.fixture(scope="module")
def served(engines):
    return {p: _serve(engines, p) for p in (True, False)}


@BACKENDS
def test_counters_count_prefill_and_decode_per_chunk(served, parallel):
    ing = served[parallel].ingress
    assert [t["chunks"] for t in ing["tier_counters"]] == ing["chunks_per_tier"]
    for t in ing["tier_counters"]:
        assert t["chunks"] > 0
        assert t["prefill_calls"] == t["chunks"]
        assert t["decode_steps"] == (N_NEW - 1) * t["chunks"]
        for k in ("prefill_dispatch_s", "decode_dispatch_s",
                  "decode_fetch_s", "cascade_s"):
            assert t[k] > 0.0, k


@BACKENDS
def test_host_times_fit_inside_the_chunks(served, parallel):
    ing = served[parallel].ingress
    for j, t in enumerate(ing["tier_counters"]):
        chunk_s = sum(r["end"] - r["start"] for r in ing["spans"]
                      if r["name"] == telemetry.CHUNK and r["tier"] == j)
        assert t["decode_dispatch_s"] + t["decode_fetch_s"] <= chunk_s
        assert (t["prefill_dispatch_s"] + t["decode_dispatch_s"]
                + t["decode_fetch_s"] + t["cascade_s"]) <= chunk_s


@BACKENDS
def test_tier_wait_lies_between_admission_and_answer(served, parallel):
    ing = served[parallel].ingress
    wait, lat, qw = ing["tier_wait"], ing["request_latency"], ing["queue_wait"]
    assert len(wait) == len(lat) == 16
    assert np.all(wait >= 0.0) and np.any(wait > 0.0)
    assert np.all(wait <= lat - qw + 1e-9)


@BACKENDS
def test_visits_point_at_their_chunk(served, parallel):
    res = served[parallel]
    recs = res.ingress["spans"]
    chunks = {r["id"]: r for r in recs if r["name"] == telemetry.CHUNK}
    visits = [r for r in recs if r["name"] == telemetry.VISIT]
    assert len(chunks) == res.ingress["n_chunks"]
    assert len(visits) == sum(res.tier_counts)
    for v in visits:
        c = chunks[v["parent"]]
        assert c["tier"] == v["tier"] and v["end"] == c["end"]
        assert v["start"] <= c["start"] <= c["end"]
    # each chunk's rows are its visits
    for cid, c in chunks.items():
        assert c["rows"] == sum(v["parent"] == cid for v in visits)
    # a request's visits, queue to chunk start, add up to its tier_wait
    # (requests arrive, and are listed, in submission order here)
    per_rid = np.zeros(16)
    for v in visits:
        per_rid[v["rid"]] += chunks[v["parent"]]["start"] - v["start"]
    wait = res.ingress["tier_wait"]
    assert np.all(per_rid >= wait - 1e-9)
    np.testing.assert_allclose(per_rid, wait, atol=5e-3)


def test_both_backends_publish_the_same_keys(served):
    a, b = served[True].ingress, served[False].ingress
    keys = {"tier_wait", "tier_counters", "spans", "t0_ns"}
    assert keys <= set(a) and keys <= set(b)
    assert set(a["tier_counters"][0]) == set(b["tier_counters"][0]) == (
        {"chunks"} | set(telemetry.COUNTERS))
    assert {k for r in a["spans"] for k in r} == {k for r in b["spans"] for k in r}
    assert isinstance(a["t0_ns"], int) and isinstance(b["t0_ns"], int)


def test_generate_outside_a_stream_counts_nothing(engines):
    eng = engines[0]
    rows = np.ones((4, WIDTH), np.int32)
    assert telemetry.current() is None
    eng.generate(rows, N_NEW)                   # nothing open: no error
    rec = telemetry.ChunkCounters()
    with telemetry.counting(rec):
        eng.generate(rows, N_NEW)
    assert telemetry.current() is None
    assert (rec.prefill_calls, rec.decode_steps) == (1, N_NEW - 1)
    eng.generate(rows, N_NEW)                   # closed again: unchanged
    assert (rec.prefill_calls, rec.decode_steps) == (1, N_NEW - 1)
    # a stream's totals hold its own chunks only, not the warm-up above
    ing = _serve(engines, True).ingress
    assert all(t["prefill_calls"] == t["chunks"] for t in ing["tier_counters"])


def test_summary_prints_tier_wait_and_decode_host_time(served):
    s = served[True].summary()
    assert "tier queue wait p95" in s and "us/step" in s


def _host_events(log_dir):
    from jax.profiler import ProfileData

    f = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True))[-1]
    data = ProfileData.from_file(f)
    start_ns = None
    lines = []
    for plane in data.planes:
        if plane.name == "Task Environment":
            start_ns = dict(plane.stats)["profile_start_time"]
        if plane.name.startswith("/host"):
            for line in plane.lines:
                lines.append([(e.name, e.start_ns, e.end_ns,
                               dict(e.stats) if e.name == telemetry.CHUNK
                               else None) for e in line.events])
    return lines, start_ns


def test_profile_nests_decode_fetch_in_decode_in_chunk(engines, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        res = _serve(engines, True, n=8)
    lines, profile_start = _host_events(str(tmp_path))

    def inside(evs, name, a, b):
        return any(n == name and s <= a and b <= e for n, s, e, _ in evs)

    nested = 0
    for evs in lines:
        for n, a, b, _ in evs:
            if n == telemetry.DECODE_FETCH:
                assert inside(evs, telemetry.DECODE, a, b)
                assert inside(evs, telemetry.CHUNK, a, b)
                nested += 1
    assert nested == sum(t["decode_steps"] + t["prefill_calls"]
                         for t in res.ingress["tier_counters"])
    names = {n for evs in lines for n, *_ in evs}
    assert {telemetry.STREAM, telemetry.ADMIT, telemetry.CHUNK,
            telemetry.INVOKE, telemetry.SCORE, telemetry.PREFILL,
            telemetry.DECODE_DISPATCH} <= names
    # the chunk records, laid on the profiler's clock through t0_ns,
    # start where their annotations do
    ann = sorted((st["tier"], s) for evs in lines for n, s, _, st in evs
                 if n == telemetry.CHUNK)
    rec = sorted((r["tier"], res.ingress["t0_ns"] - profile_start
                  + r["start"] * 1e9) for r in res.ingress["spans"]
                 if r["name"] == telemetry.CHUNK)
    assert [t for t, _ in ann] == [t for t, _ in rec]
    gap_ms = max(abs(a - b) for (_, a), (_, b) in zip(ann, rec)) / 1e6
    assert gap_ms < 50.0, gap_ms
