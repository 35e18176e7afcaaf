"""Fleet-scale serving benchmark: hedged Metronome fleet vs busy-poll fleet.

The paper's single-host claim — sleep&wake retrieval trades a few
microseconds of mean latency for most of a core — has a fleet-level
counterpart this benchmark measures end to end: on a *noisy shared
cluster* (correlated stall windows per host, independent across hosts),
a fleet of Metronome hosts behind a load balancer, with hedged requests
duplicated to a second replica after a deadline D, serves the same
offered load as a busy-poll fleet at

  verdict: strictly lower total CPU (cores) AND equal-or-better p99.9
  end-to-end latency.

The mechanism is the interesting part: a single Metronome host has a
*worse* tail than a spinner (stall windows park its wake-ups), but
stalls are independent across replicas, so "duplicate after D; first
completion wins" collapses the stall tail (both replicas must stall)
while the busy-poll fleet pays H full cores and still eats the
co-runner stalls.  The Metronome fleet's p99.9 is measured in the fleet
kernel (``FleetStats.quantile``); the busy-poll comparator, which the
kernel does not run, is scored by the two-component tail model
(``hedged_latency_quantile`` at D=0) on its event-engine spin-model
mean.

Rows (suite convention: ``name,value,derived``):
  - ``fleet/H<H>/<lb>/D<D>``  one fleet operating point: value = total
    CPU cores; derived has p999/mean latency, loss, offered (incl.
    hedge duplicates) and the backend (vmap vs shard_map);
  - ``fleet/busy_poll/H<H>``  the busy-poll comparator fleet;
  - ``verdict/hedged_vs_busy_poll``  the claim above, machine-readable;
  - ``fleet/scale/...``       a 1000-host x 8-point sweep in ONE jit
    call: wall-clock and points*hosts/sec throughput.

CLI: ``python -m benchmarks.fleet [--smoke]`` — ``--smoke`` runs the
small grid and exits nonzero on a failed verdict (the CI job).
"""

from __future__ import annotations

import sys
import time

import numpy as np

ROWS = list[tuple[str, float, str]]

MU_MPPS = 29.76
RHO = 0.5                     # per-host utilization at uniform split
T_S_US, T_L_US, M = 12.0, 500.0, 3
# noisy shared cluster: correlated stall windows (Exp(150us) bursts
# every ~4ms per host, independent ACROSS hosts) — the regime where
# hedging pays.  tail_prob = stall_rate * stall_mean ~= 3.75%.
STALLS = dict(stall_rate_per_us=2.5e-4, stall_mean_us=150.0)
# hedge ladder: loosest -> tightest, all above the drain-time scale
# (tighter deadlines duplicate aggressively enough to inflate host
# means — the cost side the offered_with_hedges column tracks)
HEDGE_LADDER = (0.0, 80.0, 40.0, 20.0)


def fleet_env(duration_us: float):
    from repro.runtime import SimRunConfig

    return SimRunConfig(duration_us=duration_us, **STALLS)


def ladder_grid(fleet):
    """One fleet at the per-host load ``RHO``, over the hedge ladder."""
    from repro.runtime import FleetGrid

    return FleetGrid.product(
        fleet=fleet, t_s_us=(T_S_US,), t_l_us=(T_L_US,),
        rate_mpps=(RHO * MU_MPPS * fleet.n_hosts,), m=(M,),
        hedge_deadline_us=HEDGE_LADDER)


def scale_sweep(quick: bool):
    """The whole-cluster sweep run as ONE jit call: 1000 hosts x 8
    operating points (hedge ladder x 2 loads).  Returns the grid, its
    environment and its slot width."""
    from repro.runtime import FleetConfig, FleetGrid

    n_hosts = 100 if quick else 1000
    duration, slot_us = (2_000.0, 1.0) if quick else (5_000.0, 1.0)
    fgrid = FleetGrid.product(
        fleet=FleetConfig(n_hosts=n_hosts), t_s_us=(T_S_US,),
        t_l_us=(T_L_US,), m=(M,),
        rate_mpps=(0.35 * MU_MPPS * n_hosts, 0.55 * MU_MPPS * n_hosts),
        hedge_deadline_us=HEDGE_LADDER)
    return fgrid, fleet_env(duration), slot_us


def _busy_poll_mean_us(cfg) -> float:
    """Event-engine spin-model mean sojourn at the per-host rate."""
    from repro.runtime import BusyPollPolicy, PoissonWorkload, simulate_run

    rs = simulate_run(BusyPollPolicy(), PoissonWorkload(RHO * MU_MPPS), cfg)
    return float(rs.mean_sojourn_us)


def fleet_bench(quick: bool = False) -> ROWS:
    from repro.runtime import (
        FleetConfig,
        hedged_latency_quantile,
        simulate_fleet,
    )

    duration = 20_000.0 if quick else 60_000.0
    slot_us = 1.0 if quick else 0.5
    sizes = (4, 16) if quick else (4, 16, 64)
    cfg = fleet_env(duration)
    tail_prob = min(STALLS["stall_rate_per_us"] * STALLS["stall_mean_us"],
                    0.5)
    tail_scale = STALLS["stall_mean_us"]

    rows: ROWS = []
    verdicts = []
    lbs = {
        "uniform": lambda H: FleetConfig(n_hosts=H),
        "weighted": lambda H: FleetConfig(
            n_hosts=H, lb="weighted",
            host_weights=tuple(1.0 + 0.5 * (h % 2) for h in range(H))),
        "least-loaded": lambda H: FleetConfig(
            n_hosts=H, lb="least-loaded", lb_stale_us=200.0),
    }
    busy_mean = _busy_poll_mean_us(cfg)

    for H in sizes:
        # busy-poll comparator: H spinning hosts, the same stall tail
        busy_p999 = hedged_latency_quantile(
            0.999, np.full(H, busy_mean), hedge_deadline_us=0.0,
            tail_prob=tail_prob, tail_scale_us=tail_scale)
        rows.append((
            f"fleet/busy_poll/H{H}", float(H),
            f"p999_us={busy_p999:.1f};mean_lat_us={busy_mean:.2f};"
            f"cpu_cores={H};spin=True"))

        for lb, make in lbs.items():
            fgrid = ladder_grid(make(H))
            fs = simulate_fleet(fgrid, cfg, slot_us=slot_us)
            for i in range(len(fs)):
                d = float(fgrid.hedge_deadline_us[i])
                p999 = fs.quantile(i, 0.999)
                rows.append((
                    f"fleet/H{H}/{lb}/D{d:g}",
                    float(fs.total_cpu_cores[i]),
                    f"p999_us={p999:.1f};"
                    f"mean_lat_us={fs.mean_latency_us[i]:.2f};"
                    f"loss_frac={fs.loss_fraction[i]:.4f};"
                    f"offered_w_hedges_pkts="
                    f"{fs.offered_with_hedges[i]:.0f};"
                    f"backend={fs.backend}"))
                if lb == "uniform" and d > 0.0:
                    verdicts.append((H, d, float(fs.total_cpu_cores[i]),
                                     p999, busy_p999))

    # verdict at the largest fleet: the best hedged uniform point must
    # beat the busy-poll fleet on BOTH axes (cores and p99.9)
    H = sizes[-1]
    cands = [v for v in verdicts if v[0] == H]
    best = min(cands, key=lambda v: v[3])
    _, best_d, best_cpu, best_p999, busy_p999 = best
    ok = bool(best_cpu < H and best_p999 <= busy_p999)
    rows.append((
        "verdict/hedged_vs_busy_poll", float(ok),
        f"ok={ok};n_hosts={H};hedge_deadline_us={best_d:g};"
        f"metronome_cpu_cores={best_cpu:.1f};busy_poll_cpu_cores={H};"
        f"metronome_p999_us={best_p999:.1f};"
        f"busy_poll_p999_us={busy_p999:.1f}"))

    # scale row: a whole-cluster sweep in ONE jit call — 1000 hosts x
    # 8 operating points (hedge ladder x 2 loads), point axis sharded
    # across however many devices are visible
    fgrid, cfg_big, slot_big = scale_sweep(quick)
    H_big = fgrid.fleet.n_hosts
    dur_big = cfg_big.duration_us
    t0 = time.time()
    fs = simulate_fleet(fgrid, cfg_big, slot_us=slot_big)
    np.asarray(fs.serviced)            # block on the device computation
    wall = time.time() - t0
    ph = len(fgrid) * H_big
    rows.append((
        "fleet/scale/one_jit_call", wall,
        f"points={len(fgrid)};n_hosts={H_big};points_x_hosts={ph};"
        f"pts_hosts_per_s={ph / max(wall, 1e-9):.0f};"
        f"host_slots_per_s="
        f"{ph * int(dur_big / slot_big) / max(wall, 1e-9):.3g};"
        f"one_jit_call=True;backend={fs.backend}"))
    return rows


def main() -> None:
    quick = "--smoke" in sys.argv or "--quick" in sys.argv
    rows = fleet_bench(quick=quick)
    print("name,value,derived")
    for name, val, derived in rows:
        print(f"{name},{val:.4f},{derived}")
    if "--smoke" in sys.argv:
        ok = next(v for n, v, _ in rows
                  if n == "verdict/hedged_vs_busy_poll")
        if not ok:
            print("SMOKE FAILED: hedged Metronome fleet did not beat the "
                  "busy-poll fleet on CPU + p99.9", file=sys.stderr)
            sys.exit(1)
        print("# smoke ok", file=sys.stderr)


if __name__ == "__main__":
    main()
