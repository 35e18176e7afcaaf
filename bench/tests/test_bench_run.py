"""A run rehearsed on the CPU at a tiny size: the window and its result
line, the comparison with the plain reference failing under each fault
the calibration cells can have, the control, and the command's refusal
to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import cell as cells
from bench import run as bench_run
from bench.control import readings as control_readings

CELL = "calib-l3fwd-lattice"


@pytest.fixture(scope="module")
def tiny():
    """The lattice cell with its limits, on 32 points of 20 ms."""
    wl = cells.load_json(cells.BENCH / "workloads" / f"{CELL}.json")
    wl["lattice"] = {"t_s_us": [8.0, 20.0, 40.0, 60.0], "t_l_us": [500.0],
                     "m": [2, 3], "rho": [0.1, 0.5], "seeds_per_point": 2}
    wl["duration_us"] = 20_000.0
    wl["check_points"] = 12
    wl["spot_check"] = 0
    return cells.load_cell(CELL, workload=wl)


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(bench_run, "enable_cache", lambda: "off")


def run(cell, seed=2**31 + 77, log=None):
    return bench_run.run_cell(cell, seed, 0.0, False, time.perf_counter(),
                              log=log or sys.stderr)


def over(result):
    return [k for k, c in result["checks"].items() if c["value"] > c["limit"]]


def test_run_result_line(tiny, capsys):
    res = run(tiny)
    out = capsys.readouterr()
    assert "window_compiles=0" in out.out
    assert res["correct"], res["checks"]
    assert (res["attempted"], res["failed"]) == (1, 0)
    req = tiny.request()
    assert req.n_points == 32
    assert req.host_seconds == 32 * 0.02
    m = res["metrics"]
    assert set(m) == {"sim_rate", "setup_s"}
    assert m["sim_rate"]["unit"] == "host-s/s" and m["sim_rate"]["value"] > 0
    assert list(res)[-1] == "checks"
    json.dumps(res)
    tail = out.err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail)


def _zeroed(bs):
    for k in ("offered", "dropped", "serviced", "wakeups", "busy_tries",
              "cycles", "awake_us", "lat_area", "vac_sum", "nv_sum",
              "ts_arms", "energy_uj"):
        getattr(bs, k)[:] = 0.0
    return bs


def _half(bs):
    n = len(bs.grid)
    for k in ("offered", "dropped", "serviced", "wakeups", "busy_tries",
              "cycles", "awake_us", "lat_area", "vac_sum", "nv_sum",
              "ts_arms", "energy_uj"):
        v = getattr(bs, k)
        v[n // 2:] = v[:n // 2].mean()
    return bs


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_scan_faults_are_not_correct(tiny, monkeypatch, fault):
    """A scan step that returns its state unchanged leaves every counter
    at its initial zero; half of the batch left out gets the mean of the
    rest."""
    import repro.runtime as rt

    real = rt.simulate_batch
    broken = _zeroed if fault == "state_unchanged" else _half
    monkeypatch.setattr(rt, "simulate_batch",
                        lambda *a, **k: broken(real(*a, **k)))
    res = run(tiny)
    assert not res["correct"]
    assert over(res), res["checks"]


def test_altered_table_is_not_correct(tiny, monkeypatch):
    """An answer altered where it is produced: one rung of the operating
    table moved to another T_S of the lattice."""
    from dataclasses import replace

    import repro.runtime as rt

    real = rt.build_operating_table

    def altered(**kw):
        table = real(**kw)
        p = table.points[0]
        other = next(t for t in kw["t_s_grid"] if t != p.t_s_us)
        return replace(table, points=(replace(p, t_s_us=float(other)),)
                       + table.points[1:])

    monkeypatch.setattr(rt, "build_operating_table", altered)
    res = run(tiny)
    assert not res["correct"]
    assert res["checks"]["table_mismatch"]["value"] >= 1


def test_control_is_not_correct(tiny):
    """The reference computed in bfloat16, in the program's place, reads
    above the cell's limits; the program's own readings do not."""
    got = control_readings(tiny, 0.0, [11], [12], require_tpu=False)
    limits = tiny.request().limits
    assert all(v <= limits[k] for k, v in got["lower"].items()), got
    assert any(v > limits[k] for k, v in got["upper"].items()), got


def _command(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_refuses_without_a_tpu():
    p = _command(cells.ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert "{" not in p.stdout


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_reference_agrees_with_the_event_engine():
    """``bench.reference`` is written apart from the program; over a few
    seeds its means agree with the program's event engine."""
    from repro.core import MetronomeConfig
    from repro.runtime import (
        MetronomePolicy,
        PoissonWorkload,
        SimRunConfig,
        simulate_run,
    )

    from bench.reference import simulate_host

    cfg = SimRunConfig(duration_us=20_000.0)
    for ts, tl, m, rho in ((12.0, 500.0, 3, 0.55), (40.0, 250.0, 2, 0.2)):
        ev = [simulate_run(MetronomePolicy(MetronomeConfig(
            m=m, v_target_us=ts, t_long_us=tl, ts_min_us=1.0),
            adaptive=False), PoissonWorkload(rho * 29.76),
            SimRunConfig(duration_us=20_000.0, seed=s)) for s in range(4)]
        ref = [simulate_host(t_s_us=ts, t_l_us=tl, m=m, rate_mpps=rho * 29.76,
                             duration_us=cfg.duration_us,
                             service_rate_mpps=29.76, queue_capacity=1024,
                             wake_cost_us=1.0, sleep_base_us=2.8,
                             sleep_slope=0.027, sleep_sigma_us=0.5, seed=s)
               for s in range(4)]
        lat_e = np.mean([r.mean_sojourn_us for r in ev])
        lat_r = np.mean([r["mean_latency_us"] for r in ref])
        cpu_e = np.mean([r.cpu_fraction for r in ev])
        cpu_r = np.mean([r["cpu_fraction"] for r in ref])
        assert abs(lat_r - lat_e) <= 0.06 * lat_e, (lat_r, lat_e)
        assert abs(cpu_r - cpu_e) <= 0.01 + 0.02 * cpu_e, (cpu_r, cpu_e)


def test_bf16_round():
    from bench.reference import bf16_round

    assert bf16_round(1.0) == 1.0
    assert bf16_round(257.0) == 256.0          # 8 significant bits
    assert bf16_round(259.0) == 260.0
    assert bf16_round(50_000.0) == 49_920.0    # spacing 256 above 32,768
    assert bf16_round(-3.0) == -3.0
    assert bf16_round(0.0) == 0.0
