"""Fixtures shared by the kernel tests."""

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


@pytest.fixture
def scan_kernel(capture_kernel):
    """``kernel(kind, q_max=1)``: the jitted ``kind`` kernel ("fixed",
    "adaptive" or "fleet") and its inputs, at a tiny shape with
    ``q_max`` queues per point (per host, in the fleet).  Every named
    stage has operations: the sleep model has a tail, the host has
    interference and stalls, the fleet a topology and a hedge."""
    from repro.runtime import (
        FleetConfig,
        FleetGrid,
        SimRunConfig,
        SweepGrid,
        batched,
        batched_adaptive,
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
        if kind == "adaptive":
            return capture_kernel(
                batched_adaptive, "_compiled_adaptive_sweep",
                batched_adaptive._build_adaptive_sweep,
                lambda: simulate_batch(grid, noisy, stepping="adaptive"))
        return capture_kernel(
            batched, "_compiled_sweep", batched._build_sweep,
            lambda: simulate_batch(grid, noisy))
    return kernel
