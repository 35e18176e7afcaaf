"""Fixtures shared by the kernel tests."""

import contextlib
import time

import pytest


class _Captured(Exception):
    pass


@pytest.fixture
def capture_kernel(monkeypatch):
    """``capture(module, attr, build, run)``: the jitted kernel and the
    inputs that the entry point ``run`` hands it.  The module's compile
    cache ``attr`` is swapped for one that builds the kernel with
    ``build`` and stops the entry point before anything runs."""
    def capture(module, attr, build, run):
        seen = {}

        def cache(*static):
            fn = build(*static)

            def call(*args):
                seen["fn"], seen["args"] = fn, args
                raise _Captured
            return call

        monkeypatch.setattr(module, attr, cache)
        with pytest.raises(_Captured):
            run()
        return seen["fn"], seen["args"]
    return capture


@contextlib.contextmanager
def _driven_by(driver):
    from repro.runtime import batched, tracing

    kernel = driver == "kernel"
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(batched, "_fused", lambda n_seg, n_windows:
                   kernel and not n_seg and not n_windows)
        start = time.perf_counter_ns()
        yield
    fused = {r.counts["fused"] for r in tracing.records()
             if r.name == "batched.scan" and r.start_ns >= start}
    assert fused <= {int(kernel)}, (driver, fused)


@pytest.fixture(scope="session")
def driven_by():
    """``with driven_by(driver):`` runs the fixed scan's slots by
    ``driver`` inside the block, whatever the backend: "scan", the
    ``lax.scan`` over slots, or "kernel", the Pallas kernel that a TPU
    runs for a stationary build without windows (in interpret mode off
    a TPU).  It checks that every sweep in the block ran that driver."""
    return _driven_by


@pytest.fixture
def scan_kernel(capture_kernel):
    """``kernel(kind, q_max=1)``: the jitted ``kind`` kernel ("fixed" or
    "fleet") and its inputs, at a tiny shape with
    ``q_max`` queues per point (per host, in the fleet).  Every named
    stage has operations: the sleep model has a tail, the host has
    interference and stalls, the fleet a topology and a hedge."""
    from repro.runtime import (
        FleetConfig,
        FleetGrid,
        SimRunConfig,
        SweepGrid,
        batched,
        fleet,
        simulate_batch,
    )

    noisy = SimRunConfig(duration_us=100.0, interference_prob=0.1,
                         interference_mean_us=5.0, stall_rate_per_us=1e-3,
                         stall_mean_us=50.0)

    def kernel(kind, q_max=1):
        axes = dict(t_s_us=[10.0], t_l_us=[100.0], m=[2],
                    n_queues=[q_max], rate_mpps=[3.0])
        if kind == "fleet":
            fgrid = FleetGrid.product(
                fleet=FleetConfig(n_hosts=2, near_cost_us=1.0),
                hedge_deadline_us=[20.0], **axes)
            return capture_kernel(
                fleet, "_compiled_fleet_sweep", fleet._build_fleet_sweep,
                lambda: fleet.simulate_fleet(fgrid, noisy, shard=False))
        grid = SweepGrid.product(**axes)
        return capture_kernel(
            batched, "_compiled_sweep", batched._build_sweep,
            lambda: simulate_batch(grid, noisy))
    return kernel
