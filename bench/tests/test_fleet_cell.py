"""The fleet cell: its files and sizes, the refusal of a program that does
not measure its tail, the readers of its program spans on fabricated
records, a run rehearsed on the CPU at a tiny size, and the plain
reference's verdict on the closed form the program used to read."""

import dataclasses
import itertools
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import cell as cells
from bench import reference_fleet as ref_fleet
from repro.runtime import tracing

CELL = "fleet1000-hedge-ladder"
READERS = ("fleet_scan_ns_per_host_slot", "fleet_host_ms", "fleet_tail_ms")
HOST_SLOTS = 1e6


def test_cell_is_the_1000_host_hedge_ladder():
    c = cells.load_cell(CELL)
    req = c.request()
    assert c.chips == 1
    assert (req.n_points, req.n_hosts) == (8, 1000)
    assert req.points() == [(r, d) for r in (0.3, 0.5)
                            for d in (0.0, 80.0, 40.0, 20.0)]
    assert req.host_seconds == pytest.approx(160.0)
    assert req.host_slots == pytest.approx(8 * 1000 * 40_000)
    assert req.ref_hosts >= 64
    cfg = c.config
    assert cfg["n_hosts"] == 1000 and cfg["lb"] == "uniform"
    assert "Maglev" in cfg["source"] and "Tail at Scale" in cfg["source"]
    assert {"stall_rate_per_us", "stall_mean_us"} <= set(cfg["assumed"])
    assert set(cfg["hedging"]) >= {"trigger", "partner", "full_ring"}
    assert all(cfg["guarantees"].values())
    per_layer = {m["name"] for m in c.per_layer}
    assert set(READERS) | {"device_idle_share"} <= per_layer
    assert {m["name"] for m in c.end_to_end} == {"sim_rate", "setup_s"}


def test_request_refuses_a_program_that_keeps_no_histogram(monkeypatch):
    from repro.runtime import fleet

    @dataclasses.dataclass
    class Unmeasured:
        p999: float = 0.0

    monkeypatch.setattr(fleet, "FleetStats", Unmeasured)
    with pytest.raises(RuntimeError, match="histogram"):
        cells.load_cell(CELL).request()


def _span(ids, name, start_s, end_s, parent=None):
    s = tracing.Span(name)
    s.id = next(ids)
    s.start_ns, s.end_ns = round(start_s * 1e9), round(end_s * 1e9)
    if parent is None:
        s.root = s.id
    else:
        s.parent, s.root = parent.id, parent.root
        parent.children.append((s.start_ns, s.end_ns))
    return s


def _request(ids, t, scan_s, tail_s, rollup_s):
    """One fleet request's program spans from ``t`` (seconds): a 1 s
    sweep holding a scan of ``scan_s``, then the tail and the rollup."""
    sweep = _span(ids, "fleet.sweep", t, t + 1.0)
    out = [_span(ids, "fleet.scan", t + 0.1, t + 0.1 + scan_s, sweep),
           _span(ids, "fleet.fetch", t + 0.1 + scan_s, t + 0.95, sweep),
           sweep]
    out.append(_span(ids, "fleet.tail", t + 1.0, t + 1.0 + tail_s))
    out.append(_span(ids, "fleet.rollup", t + 1.0 + tail_s,
                     t + 1.0 + tail_s + rollup_s))
    return out


@pytest.fixture
def run(monkeypatch):
    """A warm-up request (index -1) slow in every layer, then two window
    requests: scans of 0.7 and 0.8 s, tails of 0.05 and 0.07 s, rollups
    of 0.2 and 0.3 s."""
    ids = itertools.count(1)
    program = (_request(ids, 0.0, 0.9, 1.0, 1.0)
               + _request(ids, 10.0, 0.7, 0.05, 0.2)
               + _request(ids, 12.0, 0.8, 0.07, 0.3))
    bench = [("grid_prep", -1, -0.01, 0.0), ("sweep", -1, 0.0, 1.0),
             ("grid_prep", 0, 9.99, 10.0), ("sweep", 0, 10.0, 11.0),
             ("grid_prep", 1, 11.99, 12.0), ("sweep", 1, 12.0, 13.0)]
    monkeypatch.setattr(tracing, "records", lambda: list(program))
    return SimpleNamespace(spans=SimpleNamespace(records=bench),
                           request=SimpleNamespace(host_slots=HOST_SLOTS))


# the window's requests alone: scan (0.7 + 0.8) s over two sweeps of
# 1e6 host-slots; sweep minus scan 0.3 and 0.2 s; tail plus rollup 0.25
# and 0.37 s
WANT = {"fleet_scan_ns_per_host_slot": 1e9 * 1.5 / (2 * HOST_SLOTS),
        "fleet_host_ms": 250.0, "fleet_tail_ms": 310.0}


@pytest.mark.parametrize("name", READERS)
def test_reader_excludes_warm_up_and_divides_by_its_base(name, run):
    got = cells.load_module("metrics", name).read(run)
    assert got == pytest.approx(WANT[name], rel=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_fleet_spans(name, run, monkeypatch):
    read = cells.load_module("metrics", name).read
    monkeypatch.setattr(tracing, "records", lambda: [])
    assert read(run) is None
    import repro.runtime

    monkeypatch.delattr(repro.runtime, "tracing")
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    assert read(run) is None


def test_tiny_traced_rehearsal_reports_the_fleet_metrics(monkeypatch):
    """A traced run on the CPU at a tiny size (16 hosts, 2 ms, 4 hosts in
    the reference) runs the whole request and reads the three metrics;
    at this size the comparison is not the cell's."""
    from bench import run as bench_run

    monkeypatch.setattr(bench_run, "enable_cache", lambda: "off")
    monkeypatch.setattr(bench_run.Tracer, "__init__",
                        lambda self, n: setattr(self, "on", False))
    monkeypatch.setattr(bench_run.Tracer, "reduce",
                        lambda self, spans, chips, log: None)
    wl = cells.load_json(cells.BENCH / "workloads" / f"{CELL}.json")
    wl.update(duration_us=2_000.0, reference_hosts=4)
    cell = cells.load_cell(CELL, workload=wl)
    cell.config = dict(cell.config, n_hosts=16)
    res = bench_run.run_cell(cell, 2**31 + 5, 0.0, True, time.perf_counter())
    assert (res["attempted"], res["failed"]) == (1, 0)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(READERS) <= set(m) and all(v > 0 for v in m.values())
    assert set(res["checks"]) == set(wl["limits"])
    assert res["checks"]["dup_unaccounted"]["value"] < 1e-4


@pytest.fixture(scope="module")
def hedged_point():
    """The reference at the cell's host, rho 0.5, D = 20 us, over 24
    hosts of 20 ms."""
    cfg = cells.load_json(cells.BENCH / "configs"
                          / "l3fwd-10g-fleet1000-hedged.json")
    kw = ref_fleet.host_kwargs(cfg, 20_000.0, t_s_us=12.0, t_l_us=500.0,
                               m=3)
    seeds = list(range(7, 31))
    rate = 0.5 * cfg["service_rate_mpps"]
    rerun = lambda r: ref_fleet.sample_hosts(kw, r, seeds)
    first = rerun(rate)
    point = ref_fleet.fleet_point(first, 20.0, 1000, rerun=rerun,
                                  rate_mpps=rate, seed=7,
                                  requests=300_000)
    return cfg, first, point


def test_the_parent_closed_form_reads_not_correct(hedged_point):
    """The closed form the program read before it measured its tail
    (an exponential body at each host's mean plus a stall component of
    mass stall rate x stall mean at scale stall mean), fed the
    reference's own hosts, is off the reference's p99.9 by more than
    twice the cell's limit."""
    from repro.runtime import hedged_latency_quantile

    cfg, first, point = hedged_point
    means = [h["mean_latency_us"] for h in first]
    closed = hedged_latency_quantile(
        0.999, means, hedge_deadline_us=20.0,
        tail_prob=cfg["stall_rate_per_us"] * cfg["stall_mean_us"],
        tail_scale_us=cfg["stall_mean_us"])
    req = cells.load_cell(CELL).request()
    gap = req.rel(closed, point["p999_us"])
    assert gap > 2 * req.limits["p999_gap"], (closed, point["p999_us"])


def test_reference_hedging_shortens_the_tail_and_loads_the_partners(
        hedged_point):
    _, first, point = hedged_point
    unhedged = ref_fleet.first_completion(first, 0.0, 1000,
                                          requests=300_000, seed=3)
    assert point["p999_us"] < 0.5 * np.quantile(unhedged, 0.999)
    assert 0.0 < point["dup_share"] < 0.05


def test_reference_sojourns_are_littles_law_on_a_quiet_host(hedged_point):
    """Without stalls the ring never fills, and the FIFO curves' packet
    sojourns average to Little's law's mean (the area under the backlog
    over the packets served)."""
    cfg, _, _ = hedged_point
    kw = ref_fleet.host_kwargs(dict(cfg, stall_rate_per_us=0.0), 20_000.0,
                               t_s_us=12.0, t_l_us=500.0, m=3)
    for seed in (1, 2):
        h = ref_fleet.simulate_host(**kw, rate_mpps=14.88, seed=seed)
        assert h["sojourns_us"].mean() == pytest.approx(
            h["mean_latency_us"], rel=0.03)
