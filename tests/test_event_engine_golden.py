"""The exact event engine's event sequence, frozen: every case runs
``simulate_run`` and compares what the run produced — counters, sums of
the per-cycle arrays, reservoir quantiles, per-queue counters, windowed
and binned series — with ``==`` against values recorded before the
engine's loop was rewritten for host speed.  Any change to the order or
the arithmetic of a draw, an accumulation or an event shows here.

The cases cover the spot checks' own points (the l3fwd-10g deployment's
environment: hr_sleep, a 1,024-descriptor ring, 50 ms), both sleep
models, stalls and interference, four queues under each dispatcher and
assignment, windows and time-series bins, every workload kind, a load
schedule, a saturated ring, and adaptive and fixed policies.

Record the golden anew only where a change is meant to alter the event
sequence: ``PYTHONPATH=src python tests/test_event_engine_golden.py``.
"""

import json
import math
from pathlib import Path

import numpy as np
import pytest

from repro.core import MetronomeConfig
from repro.runtime import (
    CBRWorkload,
    DedicatedAssignment,
    EqualTimeoutsPolicy,
    FixedPeriodPolicy,
    FlowHashDispatch,
    LeastLoadedDispatch,
    MetronomePolicy,
    OnOffBurstyWorkload,
    PoissonWorkload,
    RoundRobinDispatch,
    SharedAssignment,
    SimRunConfig,
    StealingAssignment,
    StepSchedule,
    TraceReplayWorkload,
    simulate_run,
)
from repro.runtime.sim import NANOSLEEP_MODEL

GOLDEN = Path(__file__).parent / "golden" / "event_engine_runs.json"
MU = 29.76

# the environment of the calibration cells' spot checks (l3fwd-10g)
L3FWD = dict(duration_us=50_000.0, service_rate_mpps=MU, queue_capacity=1024)


def _calib(rho, t_s, t_l, m, seed=0):
    """A spot check's run: a fixed Metronome point in l3fwd-10g."""
    return (lambda: MetronomePolicy(
                MetronomeConfig(m=m, v_target_us=t_s, t_long_us=t_l,
                                ts_min_us=min(1.0, t_s)), adaptive=False),
            lambda: PoissonWorkload(rho * MU),
            SimRunConfig(seed=seed, **L3FWD), {})


def _adaptive(**cfg):
    return lambda: MetronomePolicy(MetronomeConfig(m=3, v_target_us=10.0,
                                                   t_long_us=500.0), **cfg)


_DISPATCH = {"rr": RoundRobinDispatch, "flowhash": FlowHashDispatch,
             "leastloaded": LeastLoadedDispatch}
_ASSIGN = {"shared": SharedAssignment, "dedicated": DedicatedAssignment,
           "stealing": StealingAssignment}


def _multiqueue(d, a):
    return (lambda: MetronomePolicy(MetronomeConfig(m=4, v_target_us=10.0,
                                                    t_long_us=250.0)),
            lambda: PoissonWorkload(0.6 * MU),
            SimRunConfig(duration_us=8_000.0, n_queues=4, seed=17),
            dict(dispatcher=_DISPATCH[d], assignment=_ASSIGN[a]))


# name -> (policy factory, workload factory, config, {dispatcher /
# assignment factories})
CASES = {
    "calib_rho05_ts80_tl900_m2": _calib(0.05, 80.0, 900.0, 2),
    "calib_rho25_ts40_tl250_m2": _calib(0.25, 40.0, 250.0, 2, seed=2**33 + 1),
    "calib_rho45_ts20_tl500_m3": _calib(0.45, 20.0, 500.0, 3),
    "calib_rho85_ts5_tl250_m3": _calib(0.85, 5.0, 250.0, 3),
    "calib_rho85_ts12_tl900_m2": _calib(0.85, 12.0, 900.0, 2, seed=7),
    "nanosleep_tail": (
        lambda: FixedPeriodPolicy(30.0, threads=2),
        lambda: PoissonWorkload(0.3 * MU),
        SimRunConfig(duration_us=30_000.0, sleep_model=NANOSLEEP_MODEL,
                     seed=4, queue_capacity=512), {}),
    "stalls_interference": (
        _adaptive(),
        lambda: PoissonWorkload(0.4 * MU),
        SimRunConfig(duration_us=30_000.0, seed=3, interference_prob=0.05,
                     interference_mean_us=50.0, stall_rate_per_us=2e-4,
                     stall_mean_us=100.0), {}),
    **{f"q4_{d}_{a}": _multiqueue(d, a) for d in _DISPATCH for a in _ASSIGN},
    "windows": (
        _adaptive(),
        lambda: PoissonWorkload(0.5 * MU),
        SimRunConfig(duration_us=20_000.0, seed=9, window_us=2_000.0), {}),
    "timeseries_bins": (
        _adaptive(),
        lambda: PoissonWorkload(0.5 * MU),
        SimRunConfig(duration_us=20_000.0, seed=10,
                     timeseries_bin_us=1_000.0), {}),
    "cbr": (
        lambda: FixedPeriodPolicy(20.0, threads=2),
        lambda: CBRWorkload(10.0),
        SimRunConfig(duration_us=20_000.0, seed=11), {}),
    "on_off_drops": (
        lambda: FixedPeriodPolicy(50.0, threads=2),
        lambda: OnOffBurstyWorkload(20.0, on_mean_us=2_000.0,
                                    off_mean_us=5_000.0),
        SimRunConfig(duration_us=40_000.0, seed=12, queue_capacity=512), {}),
    "poisson_profile": (
        _adaptive(),
        lambda: PoissonWorkload(
            profile=lambda t: 3.0 if t < 10_000.0 else 18.0),
        SimRunConfig(duration_us=20_000.0, seed=13), {}),
    "trace_replay": (
        lambda: EqualTimeoutsPolicy(MetronomeConfig(m=2, v_target_us=15.0)),
        lambda: TraceReplayWorkload(np.cumsum(np.full(40_000, 0.4)),
                                    jitter=0.2),
        SimRunConfig(duration_us=15_000.0, seed=14), {}),
    "load_schedule_windows": (
        _adaptive(),
        lambda: PoissonWorkload(0.3 * MU),
        SimRunConfig(duration_us=30_000.0, seed=15, window_us=3_000.0,
                     schedule=StepSchedule(times_us=(0.0, 10_000.0, 20_000.0),
                                           scales=(1.0, 2.5, 0.5))), {}),
    "saturated_truncations": (
        lambda: MetronomePolicy(MetronomeConfig(m=2, v_target_us=10.0,
                                                t_long_us=200.0),
                                adaptive=False),
        lambda: PoissonWorkload(1.3 * MU),
        SimRunConfig(duration_us=10_000.0, seed=16, queue_capacity=256), {}),
}


def _plain(x):
    """JSON-exact form: Python ints and floats, NaN as None."""
    if isinstance(x, (list, tuple, np.ndarray)):
        return [_plain(v) for v in np.asarray(x).tolist()]
    if isinstance(x, (bool, np.bool_)):
        return bool(x)
    if isinstance(x, (int, np.integer)):
        return int(x)
    x = float(x)
    return None if math.isnan(x) else x


def fingerprint(rs) -> dict:
    """Everything a run produced that its event sequence decides."""
    out = {f: _plain(getattr(rs, f)) for f in (
        "wakeups", "cycles", "busy_tries", "items", "offered", "dropped",
        "awake_ns", "latency_area_us", "energy_uj", "drain_truncations")}
    for f in ("vacations_us", "busies_us", "n_v"):
        a = np.asarray(getattr(rs, f))
        out[f"{f}_len"] = int(a.size)
        out[f"{f}_sum"] = _plain(np.sum(a))
    lat = np.asarray(rs.latency_us)
    out["latency_count"] = rs.latency_us.count
    out["latency_p50_p99"] = (_plain(np.percentile(lat, [50, 99]))
                              if lat.size else None)
    out["per_queue"] = [
        [q.offered, q.dropped, q.serviced, q.busy_tries, q.cycles,
         q.latency_us.count,
         _plain(np.percentile(np.asarray(q.latency_us), 99))
         if q.latency_us else None]
        for q in rs.per_queue]
    for f in ("rho_series", "ts_series", "tput_series_mpps",
              "offered_series_mpps", "series_t_us"):
        out[f] = _plain(getattr(rs, f))
    w = rs.windows
    out["windows"] = None if w is None else {
        f: _plain(getattr(w, f)) for f in (
            "offered", "served", "lat_area_us", "awake_us", "energy_uj",
            "rho_sum", "rho_cnt", "ts_sum", "p99_latency_us",
            "spill_offered", "spill_served", "spill_lat_area_us",
            "spill_awake_us", "spill_energy_uj")}
    return out


def run_case(name: str):
    mk_policy, mk_workload, cfg, routing = CASES[name]
    return simulate_run(mk_policy(), mk_workload(), cfg,
                        **{k: mk() for k, mk in routing.items()})


@pytest.fixture(scope="module")
def golden():
    return json.loads(GOLDEN.read_text())


def test_golden_covers_every_case(golden):
    assert sorted(golden) == sorted(CASES)


@pytest.mark.parametrize("name", sorted(CASES))
def test_event_sequence_matches_golden_bit_for_bit(name, golden):
    assert fingerprint(run_case(name)) == golden[name]


if __name__ == "__main__":
    GOLDEN.write_text(json.dumps(
        {name: fingerprint(run_case(name)) for name in sorted(CASES)},
        indent=1, sort_keys=True) + "\n")
