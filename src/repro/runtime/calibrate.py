"""Calibration layer: batched sweeps -> validated operating tables.

The paper picks (T_S, T_L, M) from closed forms (Eq 6/12/13); the closed
forms ignore sleep overshoot, wake cost, role churn and queue-capacity
clipping, so a configuration that is optimal on paper is merely a good
initial guess.  This module closes the loop empirically:

  1. sweep a dense (T_S, T_L, M) x load grid through the batched JAX
     engine (``repro.runtime.batched``) — thousands of operating points
     in one JIT-compiled call;
  2. cross-check every point's measured mean vacation against the
     ``repro.core.analytics`` closed form (``mean_vacation_general``,
     evaluated at the per-queue load, widened by the environment's
     interference slack) — points where engine and analysis disagree
     wildly are discarded as untrustworthy rather than silently
     selected;
  3. optionally spot-check selected points against the exact
     event-driven engine (``simulate_run``) within the batched engine's
     documented parity tolerance — in the same environment the sweep
     ran in, OS interference and correlated stalls included;
  4. for each offered load, select the cheapest point — min CPU by
     default, min predicted energy with ``objective="energy"`` — whose
     mean latency meets the target -> an ``OperatingTable`` that
     records the environment it was calibrated for.

The table is a feed-forward term for the runtime control plane:
``MetronomeController``/``MetronomePolicy`` accept it (the Eq 10 EWMA
keeps estimating rho; the table maps rho to a pre-validated operating
point), ``Server(..., operating_table=...)`` loads one at startup, and
``OperatingTable.save/load`` round-trips through JSON so calibration can
run offline (e.g. benchmarks/sweep_frontier.py) and deploy later.
"""

from __future__ import annotations

import json
from dataclasses import asdict, dataclass, replace

import numpy as np

from repro.core import analytics

from . import tracing
from .batched import SweepGrid, simulate_batch, validate_batched_config
from .simcore import SimRunConfig

__all__ = [
    "OperatingPoint",
    "OperatingTable",
    "CalibrationMismatch",
    "analytic_guard_mask",
    "build_operating_table",
    "schedule_spot_check",
]


class CalibrationMismatch(AssertionError):
    """A selected operating point failed its event-engine spot check."""


@dataclass(frozen=True)
class OperatingPoint:
    """One calibrated row: at load ``rho``, run (t_s, t_l, m) and expect
    the predicted mean latency / CPU.  ``meets_target=False`` marks
    loads where no swept point met the latency target (the returned
    point is then the latency-minimizing fallback)."""

    rho: float
    t_s_us: float
    t_l_us: float
    m: int
    mean_latency_us: float
    cpu_fraction: float
    loss_fraction: float
    meets_target: bool = True
    # predicted package energy over the calibration run (EnergyModel
    # accounting; divide by the environment's duration_us for watts);
    # 0.0 on tables predating the field
    energy_uj: float = 0.0


@dataclass(frozen=True)
class OperatingTable:
    """Load -> operating point map with interpolating lookups.

    ``timeouts_us(rho)`` is the feed-forward surface consumed by
    ``MetronomeController``: piecewise-linear interpolation of (T_S,
    T_L) between calibrated loads, clamped to the calibrated range.
    ``lookup(rho)`` returns the governing row — the nearest calibrated
    load at or *above* the request, so feasibility is conservative.

    ``environment`` records the ``SimRunConfig`` the table was
    calibrated in (sleep model, wake cost, n_queues, OS interference /
    stall injection, ...) as a JSON-safe dict, so a table calibrated on
    a noisy shared host is never mistaken for a quiet-host table (and
    vice versa) once deployed.  ``None`` only on tables predating the
    field or built by hand.
    """

    target_mean_latency_us: float
    service_rate_mpps: float
    points: tuple[OperatingPoint, ...]
    environment: dict | None = None

    def __post_init__(self):
        object.__setattr__(
            self, "points",
            tuple(sorted(self.points, key=lambda p: p.rho)))
        if not self.points:
            raise ValueError("OperatingTable needs at least one point")

    # -- lookups ---------------------------------------------------------------
    @property
    def rhos(self) -> np.ndarray:
        return np.asarray([p.rho for p in self.points])

    def lookup(self, rho: float) -> OperatingPoint:
        i = int(np.searchsorted(self.rhos, rho, side="left"))
        return self.points[min(i, len(self.points) - 1)]

    def timeouts_us(self, rho: float) -> tuple[float, float]:
        rhos = self.rhos
        t_s = float(np.interp(rho, rhos, [p.t_s_us for p in self.points]))
        t_l = float(np.interp(rho, rhos, [p.t_l_us for p in self.points]))
        return t_s, t_l

    def t_s_us(self, rho: float) -> float:
        return self.timeouts_us(rho)[0]

    # -- persistence -----------------------------------------------------------
    def to_json(self) -> str:
        return json.dumps({
            "target_mean_latency_us": self.target_mean_latency_us,
            "service_rate_mpps": self.service_rate_mpps,
            "environment": self.environment,
            "points": [asdict(p) for p in self.points],
        }, indent=2)

    @classmethod
    def from_json(cls, text: str) -> "OperatingTable":
        d = json.loads(text)
        return cls(target_mean_latency_us=d["target_mean_latency_us"],
                   service_rate_mpps=d["service_rate_mpps"],
                   environment=d.get("environment"),
                   points=tuple(OperatingPoint(**p) for p in d["points"]))

    def save(self, path) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())

    @classmethod
    def load(cls, path) -> "OperatingTable":
        with open(path) as f:
            return cls.from_json(f.read())


def analytic_guard_mask(vac_measured, t_s_grid, t_l_grid, m_grid, rhos, *,
                        guard_rel: float, slot_us: float,
                        n_queues=(1,), slack_us: float = 0.0) -> np.ndarray:
    """True where a sweep point's measured mean vacation roughly agrees
    with the App-C closed form (``mean_vacation_general``); a
    disagreement beyond ``guard_rel`` (plus a couple of slots of
    quantization allowance) means the engine and the model describe
    different systems and the point must not be selected silently.

    ``vac_measured`` has the seed-averaged lattice shape
    ``(len(t_s_grid), len(t_l_grid), len(m_grid), len(n_queues),
    len(rhos))``.  ``n_queues`` is the grid's queue-count axis: the
    engines measure vacations *per queue*, and under uniform dispatch
    each of the ``nq`` queues carries ~``rho / nq`` while receiving only
    ~``1/nq`` of the claim events (each wake claims one queue), so the
    closed form is evaluated at the per-queue load and scaled by ``nq``
    (feeding it the aggregate rho — the old literal-``[0]`` placeholder
    — compared per-queue vacations against the wrong prediction for
    every multi-queue sweep).  ``slack_us`` widens the band additively
    for noisy-host sweeps — pass
    ``SimRunConfig.interference_slack_us()``, the expected mean-vacation
    shift of the environment's OS-interference injection (per-wake
    Bernoulli x Exp plus the stall process's E[W^2]/2 residual tail) —
    so contention-honest sweeps are not rejected against a quiet-host
    prediction.

    Shared by ``build_operating_table`` and the sweep-frontier
    benchmark's fixed baseline, so both sides filter candidates with the
    *same* rule (the calibrated-vs-fixed verdict compares argmins over
    one candidate set).
    """
    ts_ax = np.atleast_1d(np.asarray(t_s_grid, dtype=np.float64))
    tl_ax = np.atleast_1d(np.asarray(t_l_grid, dtype=np.float64))
    m_ax = np.atleast_1d(np.asarray(m_grid))
    nq_ax = np.atleast_1d(np.asarray(n_queues, dtype=np.float64))
    rhos = np.atleast_1d(np.asarray(rhos, dtype=np.float64))
    TS, TL, M, NQ, RHO = np.meshgrid(ts_ax, tl_ax, m_ax, nq_ax, rhos,
                                     indexing="ij")
    NQ = np.maximum(NQ, 1.0)
    vac_pred = NQ * analytics.mean_vacation_general(
        TS, TL, M, analytics.primary_prob(RHO / NQ))
    return np.abs(vac_measured - vac_pred) <= (guard_rel * vac_pred
                                               + 2.0 * slot_us
                                               + float(slack_us))


def _event_sim_point(p: OperatingPoint, cfg: SimRunConfig, rate_mpps: float):
    """Run one operating point through the exact event engine."""
    from repro.core.controller import MetronomeConfig

    from .policy import MetronomePolicy
    from .sim import simulate_run
    from .workload import PoissonWorkload

    policy = MetronomePolicy(
        MetronomeConfig(m=p.m, v_target_us=p.t_s_us, t_long_us=p.t_l_us,
                        ts_min_us=min(1.0, p.t_s_us)),
        adaptive=False)
    return simulate_run(policy, PoissonWorkload(rate_mpps), cfg)


def schedule_spot_check(table: OperatingTable, schedule, *,
                        cfg: SimRunConfig | None = None,
                        peak_rho: float | None = None,
                        window_us: float = 2_000.0,
                        max_violation: float = 0.5,
                        target_slack: float = 2.0):
    """Closed-loop, *nonstationary* validation of a calibrated table:
    run the exact event engine with the table installed as feed-forward
    while ``schedule`` modulates a Poisson load whose peak reaches the
    table's top calibrated rho, and judge the windowed tracking
    behavior.

    Raises ``CalibrationMismatch`` when the fraction of windows whose
    mean latency exceeds ``target_slack * table.target`` is above
    ``max_violation`` — a table that cannot keep latency within a
    generous multiple of its own target while the load moves across its
    calibrated range is not deployable as a feed-forward term, however
    good its per-load steady-state numbers look.  Returns the
    ``(RunStats, TrackingStats)`` pair for inspection.
    """
    from repro.core.controller import MetronomeConfig

    from .policy import MetronomePolicy
    from .sim import simulate_run
    from .workload import PoissonWorkload

    base = cfg or SimRunConfig(duration_us=60_000.0)
    run_cfg = replace(base, schedule=schedule, window_us=float(window_us))
    rho_peak = (float(peak_rho) if peak_rho is not None
                else float(np.max(table.rhos)))
    scales = schedule.segments(run_cfg.duration_us)[1]
    scale_max = float(np.max(scales)) if scales.size else 1.0
    base_rate = rho_peak * table.service_rate_mpps / max(scale_max, 1e-9)
    m = int(round(float(np.median([p.m for p in table.points]))))
    policy = MetronomePolicy(
        MetronomeConfig(m=m, t_long_us=float(table.points[-1].t_l_us)),
        operating_table=table)
    rs = simulate_run(policy, PoissonWorkload(base_rate), run_cfg)
    tk = rs.windows.tracking(
        schedule.transitions(run_cfg.duration_us),
        target_slack * table.target_mean_latency_us)
    if tk.violation_fraction > max_violation:
        raise CalibrationMismatch(
            f"operating table failed its schedule spot check: "
            f"{tk.violation_fraction:.0%} of {window_us:g}us windows "
            f"exceeded {target_slack:g}x the {table.target_mean_latency_us:g}us "
            f"calibration target under schedule "
            f"{schedule.descriptor()} (allowed {max_violation:.0%})")
    return rs, tk


def build_operating_table(
    *,
    rhos,
    target_mean_latency_us: float,
    t_s_grid,
    t_l_grid,
    m_grid=(2, 3, 4),
    cfg: SimRunConfig | None = None,
    seeds=(0, 1),
    slot_us: float = 0.5,
    max_loss: float = 1e-3,
    analytic_guard_rel: float = 0.6,
    spot_check: int = 0,
    spot_check_rel: float = 0.25,
    sweep=None,
    schedule_check=None,
    fleet=None,
    objective: str = "cpu",
) -> OperatingTable:
    """Sweep (t_s x t_l x m x rho x seed) through the batched engine and
    distill an ``OperatingTable``: per load, the minimum-cost point whose
    seed-averaged mean latency meets ``target_mean_latency_us`` (and
    loses at most ``max_loss``).

    ``objective`` picks the cost that is minimized over the feasible set:
    ``"cpu"`` (default, the historical behavior) selects minimum
    ``cpu_fraction``; ``"energy"`` selects minimum ``energy_uj`` under
    ``cfg.energy_model``.  The two tables genuinely differ under deep
    C-states: CPU cost is monotone in the wake rate ``m / T_S``, so the
    CPU argmin always stretches T_S to the latency-feasible maximum —
    but the energy objective also pays ``m * P(state(T))`` C-state
    residency plus per-wake transitions, so when the latency target
    binds below a residency floor it ranks the remaining (shallow-band)
    points differently and lands on another (T_S, T_L, M) entirely
    (``benchmarks/power.py`` pins one such divergence).  Every point
    records its ``energy_uj`` either way.

    ``analytic_guard_rel`` drops points whose measured mean vacation
    strays that far (relative) from the App-C closed form — a
    disagreement that large means the engine and the model describe
    different systems, and such a point must not be *selected* silently
    (see ``analytic_guard_mask``; the prediction is evaluated at the
    per-queue load rho/n_queues, and noisy-host environments widen the
    band by ``cfg.interference_slack_us()``).  ``spot_check > 0`` re-
    runs that many selected points through the exact event engine — in
    the *same* environment the sweep ran in, interference and stalls
    included, never a quieted copy — and raises ``CalibrationMismatch``
    if mean sojourn or CPU disagree beyond ``spot_check_rel`` (plus an
    absolute floor matching the batched engine's documented parity band
    for that environment).  ``sweep`` accepts a precomputed
    ``BatchStats`` for exactly this grid (same axes, same cfg/slot_us —
    e.g. one the caller also uses for frontier analysis) so the batch
    isn't simulated twice; its grid shape is validated.

    ``fleet`` (a ``repro.runtime.simcore.FleetConfig``) calibrates a
    *per-host* table for fleet deployment: each table rung still labels
    a per-host rho (the host sweep is unchanged — LB shares decide how
    much of the fleet-aggregate load a host sees), but the latency
    budget a host is given shrinks by the fleet's share-weighted
    topology delay (``FleetConfig.mean_topo_delay_us`` — rack cost plus
    bottleneck-link M/M/1 wait) evaluated at the fleet-aggregate peak
    rate ``max(rhos) * mu * n_hosts``, so "host meets target" composes
    into "fleet request meets target" end to end.  The fleet config is
    recorded in the table's ``environment`` under ``"fleet"``.

    ``schedule_check`` (a ``repro.runtime.schedule.LoadSchedule``)
    additionally validates the finished table *closed-loop under
    nonstationary load*: the exact event engine replays the schedule
    with the table installed as feed-forward and
    ``schedule_spot_check`` raises ``CalibrationMismatch`` if the
    windowed latency violates a generous multiple of the target too
    often.  Calibration sweeps themselves must be stationary —
    ``cfg.schedule`` is rejected (a moving rate would mislabel every
    rho rung of the table).

    The returned table records ``cfg`` as its ``environment``.
    """
    with tracing.span("calibrate.table"):
        cfg = cfg or SimRunConfig(duration_us=60_000.0)
        validate_batched_config(cfg)
        if objective not in ("cpu", "energy"):
            raise ValueError(
                f"objective must be 'cpu' or 'energy', got {objective!r}")
        if cfg.schedule is not None:
            raise ValueError(
                "calibration sweeps must run on stationary loads: each table "
                "rung is labeled with one rho, which a cfg.schedule would "
                "modulate mid-measurement.  Pass the schedule as "
                "schedule_check= to validate the finished table under "
                "nonstationary load instead")
        rhos = np.atleast_1d(np.asarray(rhos, dtype=np.float64))
        mu = cfg.service_rate_mpps
        if fleet is not None:
            fleet.validate()
            peak_fleet_mpps = float(np.max(rhos)) * mu * fleet.n_hosts
            topo_us = fleet.mean_topo_delay_us(peak_fleet_mpps)
            if topo_us >= target_mean_latency_us:
                raise ValueError(
                    f"fleet topology delay ({topo_us:.2f}us at peak "
                    f"{peak_fleet_mpps:.2f} Mpps) consumes the whole "
                    f"{target_mean_latency_us:g}us latency target — no host "
                    f"budget remains")
            target_mean_latency_us = target_mean_latency_us - topo_us
        grid = SweepGrid.product(t_s_us=t_s_grid, t_l_us=t_l_grid, m=m_grid,
                                 n_queues=(cfg.n_queues,),
                                 rate_mpps=rhos * mu, seeds=seeds)
        if sweep is None:
            bs = simulate_batch(grid, cfg, slot_us=slot_us)
        else:
            # the precomputed sweep must be THIS lattice simulated in THIS
            # environment — matching shape alone would let metrics from one
            # grid be labeled with another grid's parameters
            same_axes = (sweep.grid.shape == grid.shape and all(
                np.array_equal(getattr(sweep.grid, f), getattr(grid, f))
                for f in ("t_s_us", "t_l_us", "m", "n_queues", "rate_mpps",
                          "seed")))
            if not (same_axes and sweep.cfg == cfg
                    and sweep.slot_us == float(slot_us)):
                raise ValueError(
                    "precomputed sweep does not match the requested lattice/"
                    "environment (grid axes, SimRunConfig and slot_us must "
                    "all be identical)")
            bs = sweep

        # seed-averaged metrics on the (ts, tl, m, nq, rho, seed) lattice
        lat = bs.reshaped("mean_latency_us").mean(axis=-1)
        cpu = bs.reshaped("cpu_fraction").mean(axis=-1)
        loss = bs.reshaped("loss_fraction").mean(axis=-1)
        vac = bs.reshaped("mean_vacation_us").mean(axis=-1)
        energy = bs.reshaped("energy_uj").mean(axis=-1)

        ts_ax = np.atleast_1d(np.asarray(t_s_grid, dtype=np.float64))
        tl_ax = np.atleast_1d(np.asarray(t_l_grid, dtype=np.float64))
        m_ax = np.atleast_1d(np.asarray(m_grid))
        # analytic guard: engine and closed form must roughly agree — at the
        # per-queue load, with the noisy-host slack for this environment
        valid = analytic_guard_mask(vac, ts_ax, tl_ax, m_ax, rhos,
                                    guard_rel=analytic_guard_rel,
                                    slot_us=slot_us,
                                    n_queues=(cfg.n_queues,),
                                    slack_us=cfg.interference_slack_us())
        feasible = valid & (lat <= target_mean_latency_us) & (loss <= max_loss)

        cost = cpu if objective == "cpu" else energy
        points = []
        big = np.inf
        for k, rho in enumerate(rhos):
            feas_k = feasible[..., k]
            if feas_k.any():
                cost_k = np.where(feas_k, cost[..., k], big)
                i, j, l, _ = np.unravel_index(int(np.argmin(cost_k)),
                                              cost_k.shape)
                met = True
            else:
                lat_k = np.where(valid[..., k], lat[..., k], big)
                if not np.isfinite(lat_k).any():
                    lat_k = lat[..., k]                 # last resort: raw
                i, j, l, _ = np.unravel_index(int(np.argmin(lat_k)),
                                              lat_k.shape)
                met = False
            points.append(OperatingPoint(
                rho=float(rho), t_s_us=float(ts_ax[i]), t_l_us=float(tl_ax[j]),
                m=int(m_ax[l]), mean_latency_us=float(lat[i, j, l, 0, k]),
                cpu_fraction=float(cpu[i, j, l, 0, k]),
                loss_fraction=float(loss[i, j, l, 0, k]), meets_target=met,
                energy_uj=float(energy[i, j, l, 0, k])))

        env = asdict(cfg)
        if fleet is not None:
            env["fleet"] = asdict(fleet)
        # JSON-canonical from the start (tuples -> lists), so the recorded
        # environment survives a to_json/from_json round trip unchanged
        env = json.loads(json.dumps(env))
        table = OperatingTable(target_mean_latency_us=target_mean_latency_us,
                               service_rate_mpps=mu, points=tuple(points),
                               environment=env)

        if spot_check:
            # contention-honest: the exact engine re-examines selected points
            # in the environment the table claims to be calibrated for —
            # interference and stalls included.  (This used to quiet the
            # config first, laundering noisy-host tables through quiet-host
            # validation.)  Noisy environments get the batched engine's wider
            # documented parity floors.
            lat_floor, cpu_floor = (4.5, 0.04) if cfg.is_noisy else (2.0, 0.03)
            idxs = np.linspace(0, len(points) - 1,
                               min(spot_check, len(points))).astype(int)
            for i in sorted(set(idxs.tolist())):
                p = points[i]
                with tracing.span("calibrate.spot_check", rho=i):
                    rs = _event_sim_point(p, cfg, p.rho * mu)
                lat_err = abs(rs.mean_sojourn_us - p.mean_latency_us)
                cpu_err = abs(rs.cpu_fraction - p.cpu_fraction)
                if (lat_err > spot_check_rel * p.mean_latency_us + lat_floor
                        or cpu_err > spot_check_rel * p.cpu_fraction
                        + cpu_floor):
                    raise CalibrationMismatch(
                        f"operating point {p} failed its event-engine spot "
                        f"check: event mean sojourn "
                        f"{rs.mean_sojourn_us:.2f}us vs batched "
                        f"{p.mean_latency_us:.2f}us, event cpu "
                        f"{rs.cpu_fraction:.3f} vs batched "
                        f"{p.cpu_fraction:.3f}")
        if schedule_check is not None:
            schedule_spot_check(table, schedule_check, cfg=cfg)
        return table
