"""CPU-vs-latency trade-off frontier from a batched parameter sweep.

The paper's central claim is that sleep&wake retrieval traces a much
better CPU/latency frontier than busy polling, *if* (T_S, T_L, M) are
chosen per load.  This benchmark reproduces that frontier empirically
from thousands of simulated operating points in one JIT-compiled
batched-engine call (``repro.runtime.batched``), then runs the
calibration layer over the same sweep and checks its promise:

  verdict: for every load on the ladder, the calibrated operating table
  meets the mean-latency target at CPU <= the best *fixed*-(T_S, T_L, M)
  configuration that meets the target at every load (the static
  provisioning a paper reader would deploy).  The inequality holds per
  load by construction — the fixed config is one of the candidates the
  per-load argmin sees — so a False here means the calibration layer
  regressed, not that the experiment got unlucky.

Rows (suite convention: ``name,value,derived``):
  - ``frontier/<rho>/...``  per-load Pareto frontier samples (CPU at a
    latency band), plus busy-poll's corner (CPU=1);
  - ``table/<rho>``         the calibrated operating point per load;
  - ``verdict/...``         the calibrated-vs-fixed comparison above;
  - ``sweep/…``             sweep size and wall time (one jit call),
    split into first-call (``wall_s`` = trace + compile + execute) and
    second-call (``execute_s``, a compile-cache hit) timings, with
    ``compile_s`` their difference and throughput on the execute time.

CLI: ``python -m benchmarks.sweep_frontier [--smoke] [--interference]``
— ``--smoke`` runs a tiny grid and exits nonzero on a failed verdict
(the CI job); ``--interference`` runs the whole pipeline on a *noisy
shared host* (per-wake OS interference + correlated stall windows
through the batched engine, an analytic guard widened by the
environment's interference slack, and event-engine spot checks in that
same noisy environment) with a correspondingly relaxed latency target
and loss budget — the CPU-sharing counterpart of the quiet-host
frontier.
"""

from __future__ import annotations

import sys
import time

import numpy as np

ROWS = list[tuple[str, float, str]]

MU_MPPS = 29.76
TARGET_MEAN_LAT_US = 15.0
MAX_LOSS = 1e-3
# noisy-shared-host mode (--interference): a fifth of all wakes delayed
# by Exp(15us) co-runner preemption, Exp(100us) stall windows every
# ~5ms; latency target and loss budget relaxed to match the host
NOISY_ENV = dict(interference_prob=0.2, interference_mean_us=15.0,
                 stall_rate_per_us=1.0 / 5_000.0, stall_mean_us=100.0)
NOISY_TARGET_MEAN_LAT_US = 30.0
NOISY_MAX_LOSS = 0.05


def lattice(quick: bool) -> dict:
    """The (T_S x T_L x M x load x seed) calibration lattice, its
    simulated duration and slot width.  The full one is 2,016 points of
    50 ms at 0.5 us slots."""
    if quick:
        return dict(t_s_grid=np.linspace(4.0, 60.0, 8),
                    t_l_grid=np.asarray([120.0, 500.0]), m_grid=(2, 3),
                    rhos=np.asarray([0.15, 0.35, 0.55, 0.75]), seeds=(0,),
                    duration_us=30_000.0, slot_us=1.0)
    return dict(t_s_grid=np.linspace(3.0, 80.0, 14),
                t_l_grid=np.asarray([120.0, 250.0, 500.0, 900.0]),
                m_grid=(2, 3, 4),
                rhos=np.asarray([0.1, 0.25, 0.4, 0.55, 0.7, 0.85]),
                seeds=(0, 1), duration_us=50_000.0, slot_us=0.5)


def _sweep(quick: bool, noisy: bool = False):
    from repro.runtime import SimRunConfig, SweepGrid, simulate_batch

    lat = lattice(quick)
    t_s_grid, t_l_grid, m_grid = lat["t_s_grid"], lat["t_l_grid"], lat["m_grid"]
    rhos, seeds, slot_us = lat["rhos"], lat["seeds"], lat["slot_us"]
    cfg = SimRunConfig(duration_us=lat["duration_us"],
                       **(NOISY_ENV if noisy else {}))
    grid = SweepGrid.product(t_s_us=t_s_grid, t_l_us=t_l_grid, m=m_grid,
                             rate_mpps=rhos * MU_MPPS, seeds=seeds)
    t0 = time.time()
    bs = simulate_batch(grid, cfg, slot_us=slot_us)
    wall = time.time() - t0          # trace + compile + execute
    t1 = time.time()
    simulate_batch(grid, cfg, slot_us=slot_us)
    execute = time.time() - t1       # compile-cache hit: execute only
    return (cfg, grid, bs, wall, execute, t_s_grid, t_l_grid, m_grid,
            rhos, seeds, slot_us)


def sweep_frontier(quick: bool = False, noisy: bool = False) -> ROWS:
    from repro.runtime import build_operating_table
    from repro.runtime.calibrate import analytic_guard_mask

    target = NOISY_TARGET_MEAN_LAT_US if noisy else TARGET_MEAN_LAT_US
    max_loss = NOISY_MAX_LOSS if noisy else MAX_LOSS
    (cfg, grid, bs, wall, execute, t_s_grid, t_l_grid, m_grid, rhos,
     seeds, slot_us) = _sweep(quick, noisy)

    # seed-averaged (ts, tl, m, rho) lattice
    lat = bs.reshaped("mean_latency_us").mean(axis=-1)[:, :, :, 0, :]
    cpu = bs.reshaped("cpu_fraction").mean(axis=-1)[:, :, :, 0, :]
    loss = bs.reshaped("loss_fraction").mean(axis=-1)[:, :, :, 0, :]
    vac = bs.reshaped("mean_vacation_us").mean(axis=-1)
    # the same validity rule the calibration layer applies (incl. the
    # noisy-host slack), so the fixed baseline and the table argmin over
    # one candidate set (this is what makes the verdict hold by
    # construction)
    valid = analytic_guard_mask(
        vac, t_s_grid, t_l_grid, m_grid, rhos, guard_rel=0.6,
        slot_us=slot_us,
        slack_us=cfg.interference_slack_us())[:, :, :, 0, :]

    rows: ROWS = [(
        "sweep/points", float(len(grid)),
        f"one_jit_call=True;wall_s={wall:.2f};"
        f"compile_s={max(wall - execute, 0.0):.2f};"
        f"execute_s={execute:.2f};slots_per_point="
        f"{int(cfg.duration_us / slot_us)};"
        f"pts_per_s={len(grid) / max(execute, 1e-9):.0f};"
        f"interference={cfg.is_noisy}")]

    # per-load Pareto frontiers: min CPU within sliding latency bands,
    # and the same cut through power (the energy model charges the whole
    # host: active awake time + per-arm C-state residency + transitions)
    em = cfg.energy_model
    busy_w = em.active_power_w * em.dvfs_busy_scale
    watts = bs.reshaped("mean_power_w").mean(axis=-1)[:, :, :, 0, :]
    bands = [5.0, 10.0, 15.0, 25.0, 50.0]
    for k, rho in enumerate(rhos):
        flat_lat = lat[..., k].ravel()
        flat_cpu = cpu[..., k].ravel()
        flat_w = watts[..., k].ravel()
        ok = loss[..., k].ravel() <= max_loss
        for band in bands:
            sel = ok & (flat_lat <= band)
            if not sel.any():
                continue
            rows.append((
                f"frontier/rho{rho:.2f}/lat_le_{band:g}us",
                float(flat_cpu[sel].min()),
                f"points={int(sel.sum())};"
                f"best_lat_us={flat_lat[sel][flat_cpu[sel].argmin()]:.2f}"))
            rows.append((
                f"pfrontier/rho{rho:.2f}/lat_le_{band:g}us",
                float(flat_w[sel].min()),
                f"points={int(sel.sum())};busy_poll_w={busy_w:.2f};"
                f"best_lat_us={flat_lat[sel][flat_w[sel].argmin()]:.2f}"))
        rows.append((f"frontier/rho{rho:.2f}/busy_poll", 1.0,
                     "spinning baseline: one full core by construction"))
        rows.append((
            f"pfrontier/rho{rho:.2f}/busy_poll_w", busy_w,
            "spinning baseline: one core at dvfs-pinned active power, "
            "flat in load"))

    # calibrated table over the same environment — reusing this sweep's
    # BatchStats, so the 2000+ points are simulated exactly once
    table = build_operating_table(
        rhos=rhos, target_mean_latency_us=target,
        t_s_grid=t_s_grid, t_l_grid=t_l_grid, m_grid=m_grid, cfg=cfg,
        seeds=seeds, slot_us=slot_us, max_loss=max_loss,
        spot_check=0 if quick else 3, sweep=bs)
    for p in table.points:
        rows.append((
            f"table/rho{p.rho:.2f}", p.cpu_fraction,
            f"t_s_us={p.t_s_us:.1f};t_l_us={p.t_l_us:.0f};m={p.m};"
            f"mean_lat_us={p.mean_latency_us:.2f};"
            f"meets_target={p.meets_target}"))

    # fixed baseline: the cheapest single (ts, tl, m) meeting the target
    # at EVERY load — what you would statically provision.  Restricted
    # to guard-valid cells, the same filter the table's argmin saw.
    meets_all = (valid & (lat <= target)
                 & (loss <= max_loss)).all(axis=-1)
    verdict_ok = all(p.meets_target for p in table.points)
    if meets_all.any():
        total_cpu = np.where(meets_all, cpu.sum(axis=-1), np.inf)
        i, j, l = np.unravel_index(int(np.argmin(total_cpu)),
                                   total_cpu.shape)
        base_cpu = cpu[i, j, l, :]
        tab_cpu = np.asarray([p.cpu_fraction for p in table.points])
        per_load_ok = bool(np.all(tab_cpu <= base_cpu + 1e-9))
        verdict_ok = verdict_ok and per_load_ok
        rows.append((
            "verdict/calibrated_vs_fixed_ts",
            float(base_cpu.sum() - tab_cpu.sum()),
            f"fixed_t_s_us={t_s_grid[i]:.1f};"
            f"fixed_t_l_us={t_l_grid[j]:.0f};fixed_m={m_grid[l]};"
            f"fixed_cpu_sum={base_cpu.sum():.3f};"
            f"calibrated_cpu_sum={tab_cpu.sum():.3f};"
            f"calibrated_leq_fixed_at_every_load={per_load_ok};"
            f"all_loads_meet_{target:g}us_target="
            f"{all(p.meets_target for p in table.points)}"))
    else:
        verdict_ok = False
        rows.append(("verdict/calibrated_vs_fixed_ts", float("nan"),
                     "no fixed configuration meets the target at every "
                     "load — widen the grid"))
    rows.append(("verdict/ok", float(verdict_ok), f"ok={verdict_ok}"))
    return rows


def main() -> None:
    quick = "--smoke" in sys.argv or "--quick" in sys.argv
    rows = sweep_frontier(quick=quick, noisy="--interference" in sys.argv)
    print("name,value,derived")
    for name, val, derived in rows:
        print(f"{name},{val:.4f},{derived}")
    if "--smoke" in sys.argv:
        ok = next(v for n, v, _ in rows if n == "verdict/ok")
        if not ok:
            print("SMOKE FAILED: calibrated table did not beat the fixed "
                  "baseline while meeting the latency target",
                  file=sys.stderr)
            sys.exit(1)
        print("# smoke ok", file=sys.stderr)


if __name__ == "__main__":
    main()
