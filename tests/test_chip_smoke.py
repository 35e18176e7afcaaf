"""``chip_smoke.py`` rehearsed on the CPU: each phase at a tiny size, the
four-chip phase on four virtual host devices, and the entry point's
refusal to run anywhere but on a TPU."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load()


def _cpu_env(**extra):
    return dict(os.environ, JAX_PLATFORMS="cpu", **extra)


# a corner of the full lattice where the analytic guard admits every
# load: 8 points of 50 ms at 0.5 us slots
TINY_LATTICE = dict(t_s_grid=np.linspace(3.0, 80.0, 14)[[4, 5]],
                    t_l_grid=np.asarray([250.0]), m_grid=(2, 3),
                    rhos=np.asarray([0.25, 0.7]), seeds=(0,),
                    duration_us=50_000.0, slot_us=0.5)


@pytest.mark.parametrize("stepping", ["fixed", "adaptive"])
def test_calibration_phase_tiny(cs, stepping):
    points = cs.parity_points(TINY_LATTICE, picks=((1, 250.0, 3, 0.7, 0),))
    rec = cs.calibration(TINY_LATTICE, stepping, points)
    assert rec["points"] == 8 and rec["parity_points"] == 1
    assert rec["stepping"] == stepping
    assert rec["table"].count(":") == 2


def test_parity_points_lie_on_the_full_lattice(cs):
    from benchmarks.sweep_frontier import lattice

    lat = lattice(quick=False)
    n = (len(lat["t_s_grid"]) * len(lat["t_l_grid"]) * len(lat["m_grid"])
         * len(lat["rhos"]) * len(lat["seeds"]))
    assert n == 2016
    for p in cs.parity_points(lat):
        assert np.isclose(lat["t_s_grid"], p["t_s_us"]).sum() == 1
        assert p["t_l_us"] in lat["t_l_grid"] and p["m"] in lat["m_grid"]
        assert np.isclose(lat["rhos"] * 29.76, p["rate_mpps"]).sum() == 1


def test_calibration_phase_rejects_a_point_off_the_lattice(cs):
    points = cs.parity_points(TINY_LATTICE, picks=((1, 900.0, 3, 0.7, 0),))
    with pytest.raises(cs.PhaseFailed, match="not in the lattice"):
        cs.calibration(TINY_LATTICE, "adaptive", points)


def test_fleet_phase_tiny(cs):
    rec = cs.fleet(8, 30_000.0, 1.0, True)
    assert rec["points"] == 4 + 8
    assert rec["ladder_backend"] == rec["scale_backend"] == "vmap"
    assert rec["scale_points_x_hosts"] == 800


def test_four_chip_phase_on_virtual_devices():
    """The sharded sweep against one device, on four host devices, in a
    child process of its own (the device count is fixed at start-up)."""
    code = ("import importlib.util, json, sys\n"
            f"spec = importlib.util.spec_from_file_location('cs', {str(SCRIPT)!r})\n"
            "cs = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(cs)\n"
            "print(json.dumps(cs.four_chips(True, 4)))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=ROOT,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["backend"] == "shard_map(4)"
    assert rec["single_backend"] == "vmap"
    assert rec["worst_err_over_tol"] <= 1.0


def test_serving_phase_tiny(cs):
    rec = cs.serving(["--arch", "gemma-2b", "--smoke", "--requests", "5",
                      "--rate", "200", "--max-new", "4"])
    assert rec["requests"] == 5 and rec["tokens"] == 20
    assert rec["arch"] == "gemma-2b-smoke"


def test_main_refuses_the_cpu():
    out = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                         text=True, timeout=120, cwd=ROOT, env=_cpu_env())
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "TPU" in out.stderr



@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_is_set_at_entry_only(tmp_path, env_dir):
    """Importing the entry points leaves JAX's persistent cache off;
    ``jax_cache.enable()`` keeps ``JAX_COMPILATION_CACHE_DIR`` where it
    is set and otherwise picks ``.jax_cache/`` of the checkout."""
    code = ("import jax, repro.launch.serve, benchmarks.run\n"
            "from repro.launch import jax_cache\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "print(before, jax_cache.enable(),"
            " jax.config.jax_compilation_cache_dir)\n")
    env = _cpu_env(PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(ROOT / ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    before, chosen, after = out.stdout.split()
    assert before == ("None" if env_dir is None else want)
    assert chosen == after == want
