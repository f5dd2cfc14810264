"""The harness finds a cell's files by name, generates the same work from
one seed, refuses to measure off the chip, and a sound run is correct."""
import json
import time

import numpy as np
import pytest

from bench import harness, traffic
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


def test_finds_config_traffic_and_metric_it_has_not_seen(tmp_path):
    root = tiny.make_root(tmp_path)
    (root / "bench" / "metrics" / "answers_total.py").write_text(
        "def read(run):\n    return float(len(run.served.answers))\n")
    (root / "bench" / "arrivals" / "steady.py").write_text(
        "import numpy as np\n"
        "def count(mix, seconds):\n    return int(mix['per_s'] * seconds)\n"
        "def times(mix, n, rng):\n    return np.arange(n) / mix['per_s']\n")
    (root / "bench" / "traffic" / "fresh.json").write_text(json.dumps(
        dict(tiny.MIXES["tiny-long"], arrivals="steady", per_s=6.0)))
    (root / "bench" / "configs" / "fresh-sc2.json").write_text(json.dumps(
        dict(tiny.CONFIGS["tiny-sc2"], name="fresh-sc2", hidden_size=32)))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "fresh-sc2", "file": "bench/configs/fresh-sc2.json"})
    spec["workloads"].append({"name": "fresh.cell", "config": "fresh-sc2",
                              "traffic": "fresh", "chips": 1})
    spec["end_to_end"].append({"name": "answers_total", "unit": "answers",
                               "workloads": ["fresh.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("fresh.cell", root)
    assert cell.config["hidden_size"] == 32 and cell.mix["per_s"] == 6.0
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "answers_total"]
    out = harness.run_cell(cell, 5, 1.0, False, time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["metrics"]["answers_total"]["value"] == 6.0
    assert set(out["metrics"]) == {"setup_s", "answers_total"}
    np.testing.assert_allclose(out["run"].traffic.arrivals, np.arange(6) / 6.0)


def test_tiers_come_from_the_configuration_list(tmp_path):
    """A chain listed in a configuration file: one engine per entry, the
    escalations and tier counts worked out from the list."""
    root = tiny.make_root(tmp_path)
    (root / "bench" / "configs" / "chain.json").write_text(json.dumps(
        {"name": "chain", "tiers": ["tiny-sc2", "tiny-nemo", "tiny-sc2"],
         "logit_gap_limit": 0.05}))
    (root / "bench" / "traffic" / "three.json").write_text(json.dumps(
        dict(tiny.MIXES["tiny-long"], backlog_per_s=16.0, escalate_share=[0.5, 0.5])))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "chain", "file": "bench/configs/chain.json"})
    spec["workloads"].append({"name": "chain.cell", "config": "chain",
                              "traffic": "three", "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("chain.cell", root)
    assert [c["name"] for c in cell.tier_configs] == ["tiny-sc2", "tiny-nemo", "tiny-sc2"]
    out = harness.run_cell(cell, 9, 1.0, False, time.perf_counter())
    assert out["correct"], out["checks"]
    assert list(out["run"].served.tier_counts) == [16, 8, 4]
    assert {c["tier"] for c in out["run"].calls} == {0, 1, 2}


def test_unknown_workload(root):
    with pytest.raises(KeyError):
        harness.load_cell("no.such", root)


@pytest.mark.parametrize("mix", sorted(tiny.MIXES) + ["chat", "longctx"])
def test_traffic_same_seed_same_requests(mix):
    spec = (tiny.MIXES[mix] if mix in tiny.MIXES else
            json.loads((tiny.BENCH / "traffic" / f"{mix}.json").read_text()))
    seed = 2 ** 31 + 12345
    a = traffic.generate(spec, 4096, seed, 5.0)
    b = traffic.generate(spec, 4096, seed, 5.0)
    c = traffic.generate(spec, 4096, seed + 1, 5.0)
    for x, y in ((a.prompts, b.prompts), (a.arrivals, b.arrivals), (a.stop, b.stop)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.prompts, c.prompts)
    # another seed: other tokens, the same work at the same times
    assert a.prompts.shape == c.prompts.shape
    np.testing.assert_array_equal(a.arrivals, c.arrivals)
    np.testing.assert_array_equal(a.stop, c.stop)
    assert (a.prompts >= 1).all() and (a.prompts < 4096).all()
    np.testing.assert_array_equal(traffic.scores(a.prompts) < traffic.thresholds()[0],
                                  a.escalate)


@pytest.mark.parametrize("n_tiers", [2, 3, 4])
def test_scorer_stops_each_request_at_its_tier(n_tiers):
    mix = dict(tiny.MIXES["tiny-long"], backlog_per_s=40.0, escalate_share=0.5)
    t = traffic.generate(mix, 4096, 2 ** 33 + 1, 5.0, n_tiers)
    np.testing.assert_array_equal(traffic.stop_tier(t.prompts, n_tiers), t.stop)
    s, th = traffic.scores(t.prompts, n_tiers), traffic.thresholds(n_tiers)
    for j in range(n_tiers - 1):
        # tier j accepts exactly the requests that stop there
        reach = t.stop >= j
        np.testing.assert_array_equal((s >= th[j])[reach], (t.stop == j)[reach])
    counts = [int((t.stop >= j).sum()) for j in range(n_tiers)]
    assert counts[0] == 200 and all(counts[j + 1] == round(counts[j] / 2)
                                    for j in range(n_tiers - 1))


def test_bursts_keep_the_mean_rate():
    """Bursty arrivals are data only: the same count at the same mean
    rate, three times as dense in the bursts as between them."""
    mix = json.loads((tiny.BENCH / "traffic" / "chat.json").read_text())
    bursty = dict(mix, bursts={"period_s": 4.0, "on_share": 0.25, "factor": 3.0})
    plain = traffic.generate(mix, 32768, 3, 40.0).arrivals
    t = traffic.generate(bursty, 32768, 3, 40.0).arrivals
    assert len(t) == len(plain) and np.all(np.diff(t) >= 0)
    assert t[-1] == pytest.approx(plain[-1], rel=0.05)
    on = np.mod(t, 4.0) < 1.0
    # rate in bursts over rate between them: (on / 1 s) / (off / 3 s)
    assert 2.4 < (on.sum() / 1.0) / ((~on).sum() / 3.0) < 3.6


def test_open_loop_spans_the_window():
    spec = json.loads((tiny.BENCH / "traffic" / "chat.json").read_text())
    t = traffic.generate(spec, 32768, 7, 30.0)
    assert len(t.arrivals) == round(spec["rate_per_s"] * 30)
    assert t.arrivals[-1] == pytest.approx(30.0, rel=0.05)


def test_refuses_to_measure_on_cpu(capsys):
    rc = harness.main(["--workload", "nemo.chat", "--seed", "1", "--seconds", "1"],
                      t0=time.perf_counter())
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err


@pytest.mark.parametrize("name", ["tiny.open", "tiny.long"])
def test_sound_run_is_correct(root, name):
    cell = harness.load_cell(name, root)
    out = harness.run_cell(cell, 2 ** 32 + 3, 1.0, False, time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == len(out["run"].traffic.prompts)
    assert out["checks"]["cascade_mismatches"]["value"] == 0
    assert out["tokens_checked"] > 0
    assert out["run"].window_compiles == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(out["checks"]) == ["cascade_mismatches", "logit_gap"]
