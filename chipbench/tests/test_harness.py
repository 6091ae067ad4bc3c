"""The harness's own pieces that need no solve."""

import collections
import os

import numpy as np
import pytest

from chipbench import harness

OPEN_LOOP = harness.load_module("drivers", "open_loop.py")


def test_every_seed_offers_the_same_gaps_in_its_own_order():
    a = OPEN_LOOP.arrival_schedule(1.8, 45.0, 3_000_000_101)
    b = OPEN_LOOP.arrival_schedule(1.8, 45.0, 3_000_000_102)
    assert len(a) == len(b) == 81
    assert a[0] == b[0] == 0.0
    gaps = [np.sort(np.diff(np.append(s, 45.0))) for s in (a, b)]
    assert np.allclose(gaps[0], gaps[1])
    assert not np.allclose(a, b)
    assert a[-1] < 45.0
    assert np.array_equal(a, OPEN_LOOP.arrival_schedule(1.8, 45.0,
                                                        3_000_000_101))


def test_bursts_arrive_together_at_the_same_mean_rate():
    a = OPEN_LOOP.arrival_schedule(2.0, 30.0, 7, burst=4)
    assert len(a) == 60
    assert np.array_equal(a[0::4], a[3::4])
    assert len(set(a.tolist())) == 15
    assert a[-1] < 30.0


def test_round_robin_cycles_over_the_fields():
    picks = OPEN_LOOP.gauge_picks(10, 4, {}, 7)
    assert picks.tolist() == [0, 1, 2, 3, 0, 1, 2, 3, 0, 1]


def test_zipf_gives_every_seed_the_same_counts_in_its_own_order():
    traffic = {"gauge_pick": "zipf", "zipf_s": 1.1}
    a = OPEN_LOOP.gauge_picks(200, 20, traffic, 3_000_000_101)
    b = OPEN_LOOP.gauge_picks(200, 20, traffic, 3_000_000_102)
    assert len(a) == 200
    assert collections.Counter(a.tolist()) == collections.Counter(b.tolist())
    assert not np.array_equal(a, b)
    counts = np.bincount(a, minlength=20)
    assert counts[0] > counts[1] > counts[5] > 0
    with pytest.raises(ValueError):
        OPEN_LOOP.gauge_picks(4, 2, {"gauge_pick": "bogus"}, 1)


@pytest.mark.parametrize("workload", ["wilson-24c48.light",
                                      "wilson-24c48.heavy",
                                      "tm-16c32.serve", "tm-16c32.overload"])
def test_every_cell_resolves_its_files_and_metrics(bench, workload):
    cell = harness.read_cell(bench, workload)
    names = [m["name"] for m in cell.end_to_end]
    assert names[0] == "setup_s" and len(names) == 2
    assert cell.per_layer
    moved = {m["moves"] for m in cell.per_layer}
    assert moved <= set(names)
    for m in cell.end_to_end + cell.per_layer:
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
    assert hasattr(harness.load_module(
        "drivers", cell.traffic["driver"] + ".py"), "run")
    assert os.path.exists(os.path.join(
        harness.BENCH_DIR, "references", cell.config["reference"] + ".py"))


def test_admitted_cells_come_from_benchmark_json_staged_from_staged(bench):
    admitted = {w["name"] for w in bench["workloads"]}
    assert {"wilson-24c48.light", "wilson-24c48.heavy"} <= admitted
    assert "tm-16c32.serve" not in admitted
    # a staged cell reports the admitted metrics that name no workloads
    cell = harness.read_cell(bench, "tm-16c32.serve")
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "serve_p90_s"]


def test_overrides_reach_config_and_traffic(bench):
    cell = harness.read_cell(bench, "tm-16c32.serve",
                             {"gauge_fields": 1, "traffic.rate_per_s": 9.0})
    assert cell.config["gauge_fields"] == 1
    assert cell.traffic["rate_per_s"] == 9.0
    with pytest.raises(KeyError):
        harness.read_cell(bench, "no-such.cell")


def test_light_traffic_sets_its_own_mass(bench):
    assert harness.read_cell(bench, "wilson-24c48.light").mass == -1.4
    assert harness.read_cell(bench, "wilson-24c48.heavy").mass == 0.1
