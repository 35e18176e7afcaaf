"""The readers of the program's own spans, fed a fabricated run: the
benchmark's span records and the program's span records on one clock."""

import itertools
import sys
from types import SimpleNamespace

import pytest

from bench import cell as cells
from repro.runtime import tracing

READERS = ("scan_ns_per_host_slot", "sweep_host_ms", "distill_ms",
           "spot_check_ms")
HOST_SLOTS = 1e6


def _span(ids, name, start_s, end_s, parent=None, **counts):
    s = tracing.Span(name, **counts)
    s.id = next(ids)
    s.start_ns, s.end_ns = round(start_s * 1e9), round(end_s * 1e9)
    if parent is None:
        s.root = s.id
    else:
        s.parent, s.root = parent.id, parent.root
        parent.children.append((s.start_ns, s.end_ns))
    return s


def _request(ids, t, scan_s, checks_s, distill_s):
    """One calibration request's program spans from ``t`` (seconds): a
    1 s sweep holding a scan of ``scan_s``, then a table that spends
    ``distill_s`` on its own and runs one spot check per ``checks_s``."""
    sweep = _span(ids, "batched.sweep", t, t + 1.0)
    out = [_span(ids, "batched.scan", t + 0.1, t + 0.1 + scan_s, sweep),
           _span(ids, "batched.fetch", t + 0.1 + scan_s, t + 0.95, sweep),
           sweep]
    t += 1.0
    table = _span(ids, "calibrate.table", t,
                  t + distill_s + sum(checks_s))
    t += distill_s
    for k, c in enumerate(checks_s):
        out.append(_span(ids, "calibrate.spot_check", t, t + c, table,
                         rho=k))
        t += c
    return out + [table]


@pytest.fixture
def run(monkeypatch):
    """A warm-up request (index -1) slow in every layer, then two window
    requests: scans of 0.7 and 0.8 s, three spot checks of 0.1 s, and
    0.2 and 0.3 s of distillation."""
    ids = itertools.count(1)
    program = (_request(ids, 0.0, 4.0, [1.0, 1.0, 1.0], 3.0)
               + _request(ids, 10.0, 0.7, [0.1] * 3, 0.2)
               + _request(ids, 12.0, 0.8, [0.1] * 3, 0.3))
    bench = [("grid_prep", -1, -0.01, 0.0), ("sweep", -1, 0.0, 1.0),
             ("grid_prep", 0, 9.99, 10.0), ("sweep", 0, 10.0, 11.0),
             ("grid_prep", 1, 11.99, 12.0), ("sweep", 1, 12.0, 13.0)]
    monkeypatch.setattr(tracing, "records", lambda: list(program))
    return SimpleNamespace(spans=SimpleNamespace(records=bench),
                           request=SimpleNamespace(host_slots=HOST_SLOTS))


# the window's requests alone: scan (0.7 + 0.8) s over two sweeps of
# 1e6 host-slots; sweep minus scan 0.3 and 0.2 s; distillation 0.2 and
# 0.3 s; spot checks 0.3 s per table
WANT = {"scan_ns_per_host_slot": 1e9 * 1.5 / (2 * HOST_SLOTS),
        "sweep_host_ms": 250.0, "distill_ms": 250.0, "spot_check_ms": 300.0}


@pytest.mark.parametrize("name", READERS)
def test_reader_excludes_warm_up_and_divides_by_its_base(name, run):
    got = cells.load_module("metrics", name).read(run)
    assert got == pytest.approx(WANT[name], rel=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_spans(name, run, monkeypatch):
    read = cells.load_module("metrics", name).read
    warm_up_only = SimpleNamespace(
        spans=SimpleNamespace(records=run.spans.records[:2]),
        request=run.request)
    assert read(warm_up_only) is None
    monkeypatch.setattr(tracing, "records", lambda: [])
    assert read(run) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_on_a_program_without_spans(name, run,
                                                     monkeypatch):
    import repro.runtime

    monkeypatch.delattr(repro.runtime, "tracing")
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    assert cells.load_module("metrics", name).read(run) is None


def test_traced_rehearsal_reads_the_program_inside_the_benchmark_spans(
        monkeypatch):
    """A traced run on the CPU at a tiny size (32 points of 400 us, two
    spot checks) reports the four metrics, and the program's spans sit
    inside the benchmark's own around the same calls."""
    import time

    from bench import run as bench_run

    monkeypatch.setattr(bench_run, "enable_cache", lambda: "off")
    cell = "calib-l3fwd-lattice"
    wl = cells.load_json(cells.BENCH / "workloads" / f"{cell}.json")
    wl["lattice"] = {"t_s_us": [8.0, 20.0, 40.0, 60.0], "t_l_us": [500.0],
                     "m": [2, 3], "rho": [0.1, 0.5], "seeds_per_point": 2}
    wl["duration_us"] = 400.0
    wl["spot_check"] = 2
    res = bench_run.run_cell(cells.load_cell(cell, workload=wl), 2**31 + 5,
                             0.0, True, time.perf_counter())
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(READERS) <= set(m)
    assert m["scan_ns_per_host_slot"] <= m["sweep_ns_per_host_slot"]
    slots = 32 * 400.0 / wl["slot_us"]
    assert (m["scan_ns_per_host_slot"] * slots / 1e6 + m["sweep_host_ms"]
            <= m["sweep_ns_per_host_slot"] * slots / 1e6)
    assert m["distill_ms"] + m["spot_check_ms"] <= m["table_distill_ms"]
    assert m["spot_check_ms"] > 0
