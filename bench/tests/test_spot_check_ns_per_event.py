"""``spot_check_ns_per_event`` fed fabricated runs: the benchmark's span
records and the program's span records on one clock, the spot checks'
spans carrying the event engine's counts or not."""

import itertools
import sys
from types import SimpleNamespace

import pytest

from bench import cell as cells
from bench.tests.test_program_span_readers import HOST_SLOTS, _span
from repro.runtime import tracing

NAME = "spot_check_ns_per_event"


def _request(ids, t, checks_s, events):
    """One calibration request's program spans from ``t`` (seconds): a
    1 s sweep, then a table that runs one spot check per ``checks_s``,
    each counting ``events`` (wakes, drain rounds); none where
    ``events`` is ``None``."""
    sweep = _span(ids, "batched.sweep", t, t + 1.0)
    out = [_span(ids, "batched.scan", t + 0.1, t + 0.9, sweep), sweep]
    t += 1.0
    table = _span(ids, "calibrate.table", t, t + sum(checks_s))
    counts = ({} if events is None
              else dict(wakes=events[0], drain_rounds=events[1]))
    for k, c in enumerate(checks_s):
        out.append(_span(ids, "calibrate.spot_check", t, t + c, table,
                         rho=k, **counts))
        t += c
    return out + [table]


def _run(monkeypatch, events=(1_000, 4_000)):
    """A warm-up request (index -1) whose three spot checks take 1 s for
    20 events each, then two window requests whose spot checks take 0.1
    and 0.2 s, each for ``events``."""
    ids = itertools.count(1)
    program = (_request(ids, 0.0, [1.0] * 3, (10, 10))
               + _request(ids, 10.0, [0.1] * 3, events)
               + _request(ids, 12.0, [0.2] * 3, events))
    bench = [("grid_prep", -1, -0.01, 0.0), ("sweep", -1, 0.0, 1.0),
             ("grid_prep", 0, 9.99, 10.0), ("sweep", 0, 10.0, 11.0),
             ("grid_prep", 1, 11.99, 12.0), ("sweep", 1, 12.0, 13.0)]
    monkeypatch.setattr(tracing, "records", lambda: list(program))
    return SimpleNamespace(spans=SimpleNamespace(records=bench),
                           request=SimpleNamespace(host_slots=HOST_SLOTS))


def _read(run):
    return cells.load_module("metrics", NAME).read(run)


@pytest.mark.parametrize("events", [(1_000, 4_000), (5_000, 0), (1, 99)])
def test_reads_the_window_spot_checks_time_per_event(events, monkeypatch):
    """(0.3 + 0.6) s over six checks of ``sum(events)`` each; the slow
    warm-up request counts for nothing."""
    got = _read(_run(monkeypatch, events))
    assert got == pytest.approx(0.9e9 / (6 * sum(events)), rel=1e-9)


def test_is_silent_without_spans(monkeypatch):
    run = _run(monkeypatch)
    warm_up_only = SimpleNamespace(
        spans=SimpleNamespace(records=run.spans.records[:2]),
        request=run.request)
    assert _read(warm_up_only) is None
    monkeypatch.setattr(tracing, "records", lambda: [])
    assert _read(run) is None


def test_is_silent_without_counts(monkeypatch):
    """A program whose spot checks count no events (one from before the
    event engine counted them) leaves the metric out, and the spot
    checks' time is still read."""
    run = _run(monkeypatch, events=None)
    assert _read(run) is None
    assert cells.load_module("metrics", "spot_check_ms").read(run) == (
        pytest.approx(450.0))


def test_is_silent_on_a_program_without_tracing(monkeypatch):
    import repro.runtime

    run = _run(monkeypatch)
    monkeypatch.delattr(repro.runtime, "tracing")
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    assert _read(run) is None


def test_traced_rehearsal_reports_the_metric(monkeypatch):
    """A traced run on the CPU at a tiny size (32 points of 400 us, two
    spot checks) reports the metric, and it agrees with the spot checks'
    time over the events their spans count."""
    import time

    from bench import run as bench_run

    monkeypatch.setattr(bench_run, "enable_cache", lambda: "off")
    cell = "calib-l3fwd-lattice"
    wl = cells.load_json(cells.BENCH / "workloads" / f"{cell}.json")
    wl["lattice"] = {"t_s_us": [8.0, 20.0, 40.0, 60.0], "t_l_us": [500.0],
                     "m": [2, 3], "rho": [0.1, 0.5], "seeds_per_point": 2}
    wl["duration_us"] = 400.0
    wl["spot_check"] = 2
    res = bench_run.run_cell(cells.load_cell(cell, workload=wl), 2**31 + 7,
                             0.0, True, time.perf_counter())
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m[NAME] > 0
    assert m["spot_check_ms"] > 0
