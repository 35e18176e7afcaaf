"""One generator for every traffic mix: a workload file's parameters in,
the sweep a request runs out.

A calibration mix is a (T_S x T_L x M x load x seed) lattice, the shape
of ``benchmarks/sweep_frontier.lattice``, copied here as data (the
workload files) so that the benchmark keeps its own definitions.  Each
request draws fresh seeds from the run's ``--seed`` and its index, and
nothing else changes between requests: every request of a cell has the
same shapes.
"""

from __future__ import annotations

import numpy as np


def axis(spec) -> np.ndarray:
    """One lattice axis: a list of values or ``{"linspace": [lo, hi, n]}``."""
    if isinstance(spec, dict):
        lo, hi, n = spec["linspace"]
        return np.linspace(float(lo), float(hi), int(n))
    return np.asarray(spec, dtype=np.float64)


def request_seed(seed: int, index: int) -> int:
    """Base seed of request ``index`` of a run seeded ``seed``: 31 bits,
    so that a fleet's per-host seeds (base + host) stay within 32."""
    ss = np.random.SeedSequence([int(seed) % 2**64, int(index) + 1])
    return int(ss.generate_state(1, np.uint32)[0]) % 2**31


def calibration_lattice(spec: dict) -> dict:
    """The lattice axes of a calibration workload."""
    return {"t_s_us": axis(spec["t_s_us"]), "t_l_us": axis(spec["t_l_us"]),
            "m": axis(spec["m"]).astype(int), "rho": axis(spec["rho"]),
            "seeds_per_point": int(spec["seeds_per_point"])}


def lattice_points(lat: dict) -> int:
    return (lat["t_s_us"].size * lat["t_l_us"].size * lat["m"].size
            * lat["rho"].size * lat["seeds_per_point"])
