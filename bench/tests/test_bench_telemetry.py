"""The readers of the program's own spans and counters: finite numbers on
a served window, and nothing, without raising, from a program that
publishes no such keys."""
import math
import time

import pytest

from bench import harness
from bench.tests import tiny

READERS = ["tier_wait_ms_p95", "decode_dispatch_us", "cascade_ms_per_chunk"]


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("checkout"))
    cell = harness.load_cell("tiny.open", root)
    out = harness.run_cell(cell, 2 ** 31 + 77, 1.0, False, time.perf_counter())
    assert out["correct"], out["checks"]
    return out["run"]


@pytest.mark.parametrize("name", READERS)
def test_reader_is_finite_on_a_served_window(run, name):
    value = harness.metric_reader(name)(run)
    assert value is not None and math.isfinite(value) and value >= 0.0


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_the_keys(run, name):
    class Served:
        ingress = {k: v for k, v in run.served.ingress.items()
                   if k not in ("tier_wait", "tier_counters", "spans", "t0_ns")}

    class Bare:
        served = Served()

    assert harness.metric_reader(name)(Bare()) is None
