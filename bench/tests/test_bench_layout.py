"""The benchmark's files, found by name, and the layout of
``BENCHMARK.json`` that a run relies on."""

import json

import pytest

from bench import cell as cells
from bench import traffic

SPEC = cells.load_json(cells.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]


def test_benchmark_json_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert {m["name"] for m in SPEC["end_to_end"]} >= {"setup_s", "sim_rate"}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in CELLS


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load(name):
    c = cells.load_cell(name)
    entry = next(w for w in SPEC["workloads"] if w["name"] == name)
    assert c.config["name"] == entry["config"]
    assert c.chips == entry["chips"]
    req = c.request()
    assert req.host_seconds > 0 and req.host_slots > 0
    numbers = cells.load_module("requests", c.workload["request"]).NUMBERS
    assert req.limits and set(req.limits) <= set(numbers)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "sim_rate"}
    assert c.per_layer


@pytest.mark.parametrize("name", METRICS)
def test_every_metric_has_a_reader(name):
    assert callable(cells.load_module("metrics", name).read)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_files(entry):
    path = cells.ROOT / entry["file"]
    cfg = json.loads(path.read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg["guarantees"]) and cfg["assumed"]


def test_unknown_cell_is_refused():
    with pytest.raises(cells.UnknownCell):
        cells.load_cell("no-such-cell")
    with pytest.raises(cells.UnknownCell):
        cells.load_module("metrics", "no_such_metric")


@pytest.mark.parametrize("name,points", [("calib-l3fwd-lattice", 2016),
                                         ("calib-l3fwd-lowload", 420)])
def test_lattice_sizes(name, points):
    wl = cells.load_json(cells.BENCH / "workloads" / f"{name}.json")
    assert traffic.lattice_points(
        traffic.calibration_lattice(wl["lattice"])) == points


def test_full_lattice_is_sweep_frontiers():
    """The cell's copy of the paper's calibration lattice is the one
    ``benchmarks/sweep_frontier.lattice`` defines."""
    import numpy as np

    from benchmarks.sweep_frontier import lattice

    ref = lattice(quick=False)
    wl = cells.load_json(cells.BENCH / "workloads" / "calib-l3fwd-lattice.json")
    lat = traffic.calibration_lattice(wl["lattice"])
    np.testing.assert_array_equal(lat["t_s_us"], ref["t_s_grid"])
    np.testing.assert_array_equal(lat["t_l_us"], ref["t_l_grid"])
    np.testing.assert_array_equal(lat["m"], ref["m_grid"])
    np.testing.assert_array_equal(lat["rho"], ref["rhos"])
    assert lat["seeds_per_point"] == len(ref["seeds"])
    assert (wl["duration_us"], wl["slot_us"]) == (ref["duration_us"],
                                                  ref["slot_us"])


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3, -9])
def test_request_seeds(seed):
    got = [traffic.request_seed(seed, i) for i in range(-1, 20)]
    assert got == [traffic.request_seed(seed, i) for i in range(-1, 20)]
    assert len(set(got)) == len(got)
    assert all(0 <= s < 2**31 for s in got)
