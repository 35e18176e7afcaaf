"""Request kind ``calibrate``: an operator calibrates Metronome's
(T_S, T_L, M) for one host.

One request is the pattern of the program's own calibration entry: one
``simulate_batch`` over the whole lattice (the device scan, with the
entry's default kernel), then ``build_operating_table(sweep=...)``, which
distills the sweep into an ``OperatingTable`` and spot-checks three of
its rungs with the program's event engine.  Its work is the simulated
host time of the lattice: points x ``duration_us``.

The comparison that decides ``correct`` covers both layers:

  - the scan: at the table's rungs and at points drawn from the seed,
    the program's per-point mean sojourn, CPU fraction, wakes and loss
    against ``bench.reference`` (an independent event simulation of the
    same host).  Each gap is in units of the scan's documented parity
    band, the widest over the points compared;
  - the distillation: a plain argmin over the program's own per-point
    counters, with the calibration layer's analytic guard copied here,
    must give the program's table rung for rung (exact).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from bench import traffic
from bench.reference import bf16_round, simulate_host

# documented parity band of the fixed-slot scan against an event
# simulation of the same host (repro/runtime/batched.py): mean sojourn
# within max(1.5 us, 12%), CPU within 0.02 + 5%; wakes within 15%
# (chip_smoke.py); loss within 0.01 + 10% of the reference's
LAT_ABS_US, LAT_REL = 1.5, 0.12
CPU_ABS, CPU_REL = 0.02, 0.05
WAKE_REL = 0.15
LOSS_ABS, LOSS_REL = 0.01, 0.10
# calibration layer's defaults for the analytic guard
GUARD_REL = 0.6
COUNTERS = ("offered", "dropped", "serviced", "wakeups", "awake_us",
            "lat_area", "vac_sum", "cycles")
NUMBERS = ("lat_gap", "cpu_gap", "wake_gap", "loss_gap", "table_mismatch")


def sim_config(config: dict, duration_us: float):
    from repro.runtime import SimRunConfig, SleepModel

    sm = config["sleep_model"]
    return SimRunConfig(
        duration_us=float(duration_us),
        service_rate_mpps=float(config["service_rate_mpps"]),
        queue_capacity=int(config["queue_capacity"]),
        n_queues=int(config["n_queues"]),
        wake_cost_us=float(config["wake_cost_us"]),
        sleep_model=SleepModel(base_us=float(sm["base_us"]),
                               slope=float(sm["slope"]),
                               sigma_us=float(sm["sigma_us"])),
        stall_rate_per_us=float(config["stall_rate_per_us"]),
        stall_mean_us=float(config["stall_mean_us"]))


@dataclass
class Record:
    """What one request produced, as the host received it."""

    seeds: tuple
    counters: dict        # name -> (points,) float64, lattice order
    table: list           # per rho: (t_s_us, t_l_us, m, meets_target)


def mean_vacation_general(t_s, t_l, m, p):
    """App C exact E[V] of the paper (as corrected in the program's
    ``repro.core.analytics``): the vacation a queue sees with M threads
    on timeouts T_S/T_L when a thread last saw the queue idle w.p. p."""
    p = np.asarray(p, dtype=np.float64)
    num = 1.0 - ((1.0 - p) * (1.0 - t_s / t_l)) ** m
    den = m * (p / t_s + (1.0 - p) / t_l)
    return num / np.maximum(den, 1e-12)


class Request:
    def __init__(self, workload: dict, config: dict):
        self.workload = workload
        self.config = config
        self.lat = traffic.calibration_lattice(workload["lattice"])
        self.duration_us = float(workload["duration_us"])
        self.slot_us = float(workload["slot_us"])
        self.cfg = sim_config(config, self.duration_us)
        g = config["guarantees"]
        self.target_us = float(g["target_mean_latency_us"])
        self.max_loss = float(g["max_loss"])
        self.n_points = traffic.lattice_points(self.lat)
        self.host_seconds = self.n_points * self.duration_us / 1e6
        self.host_slots = self.n_points * self.duration_us / self.slot_us
        self.limits = workload.get("limits", {})

    # -- the request ---------------------------------------------------------
    def seeds(self, seed: int, index: int) -> tuple:
        base = traffic.request_seed(seed, index)
        return tuple(base + k for k in range(self.lat["seeds_per_point"]))

    def run(self, index: int, seed: int, spans) -> Record:
        import repro.runtime as rt

        lat = self.lat
        seeds = self.seeds(seed, index)
        with spans.span("grid_prep"):
            grid = rt.SweepGrid.product(
                t_s_us=lat["t_s_us"], t_l_us=lat["t_l_us"], m=lat["m"],
                n_queues=(self.cfg.n_queues,),
                rate_mpps=lat["rho"] * self.cfg.service_rate_mpps,
                seeds=seeds)
        with spans.span("sweep"):
            bs = rt.simulate_batch(grid, self.cfg, slot_us=self.slot_us)
        with spans.span("table_distill"):
            table = rt.build_operating_table(
                rhos=lat["rho"], target_mean_latency_us=self.target_us,
                t_s_grid=lat["t_s_us"], t_l_grid=lat["t_l_us"],
                m_grid=lat["m"], cfg=self.cfg, seeds=seeds,
                slot_us=self.slot_us, max_loss=self.max_loss,
                spot_check=int(self.workload["spot_check"]), sweep=bs)
        return Record(
            seeds=seeds,
            counters={k: np.array(getattr(bs, k), dtype=np.float64)
                      for k in COUNTERS},
            table=[(p.t_s_us, p.t_l_us, p.m, p.meets_target)
                   for p in table.points])

    # -- the comparison --------------------------------------------------------
    def point(self, i: int, seeds: tuple) -> dict:
        """Parameters of lattice point ``i`` (the order of
        ``SweepGrid.product``: T_S, T_L, M, queues, load, seed)."""
        lat = self.lat
        shape = (lat["t_s_us"].size, lat["t_l_us"].size, lat["m"].size,
                 lat["rho"].size, len(seeds))
        a, b, c, d, e = np.unravel_index(i, shape)
        return {"t_s_us": float(lat["t_s_us"][a]),
                "t_l_us": float(lat["t_l_us"][b]), "m": int(lat["m"][c]),
                "rho": float(lat["rho"][d]), "seed": int(seeds[e])}

    def derived(self, c: dict) -> dict:
        """Per-point statistics from raw counters, derived plainly."""
        return {"mean_latency_us": c["lat_area"] / np.maximum(c["serviced"],
                                                               1.0),
                "cpu_fraction": c["awake_us"] / self.duration_us,
                "wakeups": c["wakeups"],
                "loss_fraction": c["dropped"] / np.maximum(c["offered"],
                                                           1.0)}

    def distill(self, c: dict, arith=None) -> list:
        """The operating table a plain argmin gives from per-point
        counters: per load, the cheapest (CPU) point whose seed-averaged
        mean sojourn meets the target and whose loss is within budget,
        among the points whose mean vacation agrees with App C's closed
        form; else the lowest-latency such point."""
        lat = self.lat
        shape = (lat["t_s_us"].size, lat["t_l_us"].size, lat["m"].size,
                 lat["rho"].size, lat["seeds_per_point"])
        d = self.derived(c)
        vac_pt = c["vac_sum"] / np.maximum(c["cycles"], 1.0)
        mean = lambda v: np.asarray(v).reshape(shape).mean(axis=-1)
        lat_m, cpu_m = mean(d["mean_latency_us"]), mean(d["cpu_fraction"])
        loss_m, vac_m = mean(d["loss_fraction"]), mean(vac_pt)
        if arith is not None:
            lat_m, cpu_m, loss_m, vac_m = (
                np.vectorize(arith)(x) for x in (lat_m, cpu_m, loss_m, vac_m))
        TS, TL, M, RHO = np.meshgrid(lat["t_s_us"], lat["t_l_us"],
                                     lat["m"].astype(np.float64), lat["rho"],
                                     indexing="ij")
        pred = mean_vacation_general(TS, TL, M, 1.0 - RHO)
        slack = (self.cfg.stall_rate_per_us * self.cfg.stall_mean_us ** 2)
        valid = np.abs(vac_m - pred) <= (GUARD_REL * pred + 2.0 * self.slot_us
                                         + slack)
        feasible = valid & (lat_m <= self.target_us) & (loss_m <= self.max_loss)
        rows = []
        for k in range(lat["rho"].size):
            if feasible[..., k].any():
                cost = np.where(feasible[..., k], cpu_m[..., k], np.inf)
                met = True
            else:
                cost = np.where(valid[..., k], lat_m[..., k], np.inf)
                if not np.isfinite(cost).any():
                    cost = lat_m[..., k]
                met = False
            a, b, l = np.unravel_index(int(np.argmin(cost)), cost.shape)
            rows.append((float(lat["t_s_us"][a]), float(lat["t_l_us"][b]),
                         int(lat["m"][l]), met))
        return rows

    def compared_points(self, records: list, seed: int) -> list:
        """(record, point) pairs the scan is held to: every table rung's
        first-seed point, and ``check_points`` points drawn from the
        seed over all the window's requests."""
        rng = np.random.default_rng([int(seed) % 2**64, 0xC4EC])
        lat = self.lat
        nseed = lat["seeds_per_point"]
        pairs = set()
        for r, rec in enumerate(records):
            for k, (ts, tl, m, _) in enumerate(rec.table):
                a = np.flatnonzero(np.isclose(lat["t_s_us"], ts))
                b = np.flatnonzero(np.isclose(lat["t_l_us"], tl))
                c = np.flatnonzero(lat["m"] == m)
                if not (a.size and b.size and c.size):
                    continue        # off the lattice: table_mismatch says so
                i = ((((a[0] * lat["t_l_us"].size + b[0]) * lat["m"].size
                       + c[0]) * lat["rho"].size + k) * nseed)
                pairs.add((r, int(i)))
        for _ in range(int(self.workload["check_points"])):
            pairs.add((int(rng.integers(len(records))),
                       int(rng.integers(self.n_points))))
        return sorted(pairs)

    def reference(self, p: dict, seed: int, arith=None) -> dict:
        cfg = self.cfg
        sm = cfg.sleep_model
        kw = dict(t_s_us=p["t_s_us"], t_l_us=p["t_l_us"], m=p["m"],
                  rate_mpps=p["rho"] * cfg.service_rate_mpps,
                  duration_us=self.duration_us,
                  service_rate_mpps=cfg.service_rate_mpps,
                  queue_capacity=cfg.queue_capacity,
                  wake_cost_us=cfg.wake_cost_us, sleep_base_us=sm.base_us,
                  sleep_slope=sm.slope, sleep_sigma_us=sm.sigma_us,
                  stall_rate_per_us=cfg.stall_rate_per_us,
                  stall_mean_us=cfg.stall_mean_us, seed=seed)
        if arith is None:
            return simulate_host(**kw)
        # a bfloat16 clock can stop advancing: bound the loop at a few
        # times the events the float64 run would need
        per_wake = p["t_s_us"] + sm.base_us
        cap = int(4 * p["m"] * self.duration_us / per_wake) + 10_000
        return simulate_host(**kw, arith=arith, max_events=cap)

    @staticmethod
    def gaps(got: dict, ref: dict) -> dict:
        """Gaps of one point, in units of the documented parity band."""
        lat_r, cpu_r = ref["mean_latency_us"], ref["cpu_fraction"]
        loss_r = ref["loss_fraction"]
        return {
            "lat_gap": abs(got["mean_latency_us"] - lat_r)
            / max(LAT_ABS_US, LAT_REL * lat_r),
            "cpu_gap": abs(got["cpu_fraction"] - cpu_r)
            / (CPU_ABS + CPU_REL * cpu_r),
            "wake_gap": abs(got["wakeups"] - ref["wakeups"])
            / (WAKE_REL * max(ref["wakeups"], 1.0)),
            "loss_gap": abs(got["loss_fraction"] - loss_r)
            / (LOSS_ABS + LOSS_REL * loss_r),
        }

    def readings(self, records: list, seed: int, *, control: bool = False,
                 worst: dict | None = None) -> dict:
        """The numbers compared.  Latency, CPU and wakes are compared at
        the points that the reference finds within the loss budget (the
        only ones a table may select), loss at every point compared.
        ``control=True`` puts the reference, computed in bfloat16, in the
        program's place.  ``worst`` collects the point behind each gap."""
        out = {k: 0.0 for k in NUMBERS}
        worst = {} if worst is None else worst
        if not records:
            return out
        rng = np.random.default_rng([int(seed) % 2**64, 0x5EF])
        for r, i in self.compared_points(records, seed):
            rec = records[r]
            p = self.point(i, rec.seeds)
            ref_seed = int(rng.integers(2**63))
            ref = self.reference(p, ref_seed)
            if control:
                got = self.reference(p, ref_seed, arith=bf16_round)
            else:
                got = {k: float(v[i]) for k, v in
                       self.derived(rec.counters).items()}
            within = ref["loss_fraction"] <= self.max_loss
            for k, v in self.gaps(got, ref).items():
                if k != "loss_gap" and not within:
                    continue
                v = v if math.isfinite(v) else math.inf
                if v >= out[k]:
                    out[k] = v
                    worst[k] = (p, got, {q: ref[q] for q in got})
        for rec in records:
            want = self.distill(rec.counters)
            got = (self.distill(rec.counters, arith=bf16_round) if control
                   else rec.table)
            out["table_mismatch"] += sum(
                1 for a, b in zip(got, want) if tuple(a) != tuple(b))
        return out
