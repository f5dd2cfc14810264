"""The trace reduction: busy union, idle share, program time and idle
gaps by host span, on a hand-made trace and on one recorded on the chip."""
import json
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def hand_trace():
    """Window 0-100 ms. Device busy 10-30 (prefill, overlapping op-level
    pieces), 25-40 (decode, overlaps the prefill by 5), 60-70 (decode)
    and 95-110 (decode, cut by the window at 100)."""
    return {
        "spans": [["bench.serve", 0, 100 * MS],
                  ["bench.tier0", 5 * MS, 45 * MS],
                  ["bench.tier1", 50 * MS, 80 * MS],
                  ["bench.scorer", 45 * MS, 48 * MS]],
        "modules": {"0": [["jit_fn(123)", 10 * MS, 30 * MS],
                          ["jit__decode_body(7)", 25 * MS, 40 * MS],
                          ["jit__decode_body(7)", 60 * MS, 70 * MS],
                          ["jit__decode_body(7)", 95 * MS, 110 * MS]]},
        "ops": {"0": [["fusion.1", 10 * MS, 20 * MS], ["fusion.2", 20 * MS, 30 * MS],
                      ["fusion.1", 60 * MS, 70 * MS]]},
    }


def test_hand_trace():
    r = T.Reduced(hand_trace())
    assert r.window_s == pytest.approx(0.100)
    # union: 10-40, 60-70, 95-100
    assert r.busy_s == pytest.approx(0.045)
    assert r.idle_share == pytest.approx(0.55)
    assert r.program_seconds("jit_fn") == pytest.approx(0.020)
    assert r.program_seconds("jit__decode_body") == pytest.approx(0.015 + 0.010 + 0.005)
    assert r.top_ops == [["fusion.1", pytest.approx(0.020)], ["fusion.2", pytest.approx(0.010)]]
    gaps = dict(r.idle_gaps)
    # idle 0-10 (tier0 open 5-10), 40-60 (tier0 to 45, scorer 45-48,
    # tier1 from 50), 70-95 (tier1 to 80)
    assert gaps["bench.tier0"] == pytest.approx(0.005 + 0.005)
    assert gaps["bench.scorer"] == pytest.approx(0.003)
    assert gaps["bench.tier1"] == pytest.approx(0.010 + 0.010)
    assert gaps["no tier call"] == pytest.approx(0.005 + 0.002 + 0.015)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)


def test_program_name():
    assert T.program_name("jit__decode_body(4398046511104)") == "jit__decode_body"
    assert T.program_name("jit_fn") == "jit_fn"


def test_no_window_span():
    t = hand_trace()
    t["spans"] = t["spans"][1:]
    with pytest.raises(ValueError):
        T.Reduced(t)


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("trace_*.json")))
def test_recorded_trace(name):
    events = json.loads((DATA / name).read_text())
    r = T.Reduced(events)
    assert 0 < r.busy_s <= r.window_s
    total = sum(r.programs.values()) / len(r.devices)
    # programs never overlap on one device, so their time is the busy time
    assert total == pytest.approx(r.busy_s, rel=1e-6)
    assert sum(s for _, s in r.idle_gaps) <= r.window_s - r.busy_s + 1e-9
    assert len(r.top_ops) <= 10 and len(r.idle_gaps) <= 10
