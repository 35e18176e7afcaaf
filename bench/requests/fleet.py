"""Request kind ``fleet``: an operator sizes the hedge deadline of a fleet
of sleep&wake hosts.

One request is what the operator waits for: one ``simulate_fleet`` over
the cell's points (loads x hedge deadlines, every host of the fleet),
then the p99.9 and the p99 of every point (``FleetStats.quantiles``),
then ``to_run_stats`` of every point (``FleetStats.rollups``).  Its work
is the simulated host time: points x hosts x ``duration_us``.

The comparison that decides ``correct``, per point, against
``bench.reference_fleet`` (an independent event simulation of a seeded
sample of the fleet's hosts, first completion wins):

  - ``p999_gap`` and ``p99_gap``: the relative gap of the program's
    p99.9 and p99 to the reference's;
  - ``dup_gap``: the relative gap of the share of the balancer's
    packets the program duplicated to the reference's S(D);
  - ``lat_gap``, ``cpu_gap``, ``wake_gap`` and ``loss_gap``: the fleet's
    mean per-host sojourn, CPU share, wakes and loss against the
    reference sample's, in the parity-band units of the calibration
    cells (``bench.requests.calibrate.Request.gaps``);
  - ``overflow_share``: the served packets the program's sojourn ring
    could not see, as a share of those served (exact zero is not
    required: a stall longer than the horizon overflows);
  - ``dup_unaccounted``: duplicates issued that were neither admitted
    nor counted as dropped, as a share of those issued.

The program must measure its tail: the constructor refuses a program
whose ``FleetStats`` keeps no sojourn histogram.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass

import numpy as np

from bench import reference_fleet as ref_fleet
from bench import traffic
from bench.reference import bf16_round
from bench.requests.calibrate import Request as Calibration

NUMBERS = ("p999_gap", "p99_gap", "dup_gap", "lat_gap", "cpu_gap",
           "wake_gap", "loss_gap", "overflow_share", "dup_unaccounted")
# the statistic behind each gap
COMPARED = {"p999_gap": "p999_us", "p99_gap": "p99_us",
            "dup_gap": "dup_share", "lat_gap": "mean_latency_us",
            "cpu_gap": "cpu_fraction", "wake_gap": "wakeups",
            "loss_gap": "loss_fraction"}
# a relative gap's denominator never falls below this share of served
# packets (a duplicate share near zero is compared absolutely)
DUP_FLOOR = 1e-3


@dataclass
class Record:
    """What one request produced, as the host received it, per point."""

    seed: int
    p999_us: np.ndarray
    p99_us: np.ndarray
    dup_share: np.ndarray       # duplicates issued / balancer's admitted
    host: dict                  # name -> (points,) fleet mean per host
    overflow_share: np.ndarray
    dup_unaccounted: np.ndarray


class Request:
    def __init__(self, workload: dict, config: dict):
        from repro.runtime import fleet

        if "hist" not in {f.name for f in dataclasses.fields(
                fleet.FleetStats)}:
            raise RuntimeError(
                "this program's FleetStats keeps no sojourn histogram: "
                "its fleet tail is not measured, so this cell cannot run")
        self.workload = workload
        self.config = config
        op = workload["operating_point"]
        self.t_s_us, self.t_l_us = float(op["t_s_us"]), float(op["t_l_us"])
        self.m = int(op["m"])
        self.rho = [float(r) for r in workload["rho"]]
        self.deadlines = [float(d) for d in workload["hedge_deadline_us"]]
        self.n_hosts = int(config["n_hosts"])
        self.duration_us = float(workload["duration_us"])
        self.slot_us = float(workload["slot_us"])
        self.ref_hosts = int(workload["reference_hosts"])
        self.cfg = self.sim_config()
        self.n_points = len(self.rho) * len(self.deadlines)
        self.host_seconds = self.n_points * self.n_hosts * self.duration_us / 1e6
        self.host_slots = (self.n_points * self.n_hosts * self.duration_us
                           / self.slot_us)
        self.limits = workload.get("limits", {})

    def sim_config(self):
        from bench.requests.calibrate import sim_config

        return sim_config(self.config, self.duration_us)

    def points(self) -> list:
        """(rho, D) of every point, in the grid's order (load, then D)."""
        return [(r, d) for r in self.rho for d in self.deadlines]

    # -- the request ---------------------------------------------------------
    def run(self, index: int, seed: int, spans) -> Record:
        import repro.runtime as rt

        base = traffic.request_seed(seed, index)
        mu = self.cfg.service_rate_mpps
        with spans.span("grid_prep"):
            fgrid = rt.FleetGrid.product(
                fleet=rt.FleetConfig(n_hosts=self.n_hosts,
                                     lb=self.config["lb"]),
                t_s_us=(self.t_s_us,), t_l_us=(self.t_l_us,), m=(self.m,),
                rate_mpps=np.asarray(self.rho) * mu * self.n_hosts,
                seeds=(base,), hedge_deadline_us=self.deadlines)
        with spans.span("sweep"):
            fs = rt.simulate_fleet(fgrid, self.cfg, slot_us=self.slot_us)
        with spans.span("tail"):
            p999 = fs.quantiles(0.999)
            p99 = fs.quantiles(0.99)
        with spans.span("rollup"):
            fs.rollups()
        admitted = np.maximum((fs.offered - fs.dropped).sum(axis=1), 1.0)
        issued = fs.hedge_dup.sum(axis=1)
        served = np.maximum(fs.serviced.sum(axis=1), 1.0)
        return Record(
            seed=base, p999_us=p999, p99_us=p99,
            dup_share=issued / admitted,
            host={"mean_latency_us": fs.host_mean_latency_us.mean(axis=1),
                  "cpu_fraction": fs.host_cpu_fraction.mean(axis=1),
                  "wakeups": fs.wakeups.mean(axis=1),
                  "loss_fraction": fs.loss_fraction},
            overflow_share=fs.hist_overflow / served,
            dup_unaccounted=np.abs(
                issued - fs.hedge_dup_in.sum(axis=1)
                - fs.hedge_dup_dropped.sum(axis=1)) / np.maximum(issued, 1.0))

    # -- the reference ---------------------------------------------------------
    def reference(self, seed: int, *, arith=None) -> list:
        """``bench.reference_fleet`` at every point, over ``ref_hosts``
        hosts seeded from ``seed`` (one first pass per load, shared by
        its deadlines)."""
        rng = np.random.default_rng([int(seed) % 2**64, 0xF1EE7])
        seeds = [int(s) for s in rng.integers(2**62, size=self.ref_hosts)]
        kw = ref_fleet.host_kwargs(self.config, self.duration_us,
                                   t_s_us=self.t_s_us, t_l_us=self.t_l_us,
                                   m=self.m)
        mu = float(self.config["service_rate_mpps"])

        def hosts(rate):
            return ref_fleet.sample_hosts(kw, rate, seeds, arith=arith)

        out = []
        for rho in self.rho:
            first = hosts(rho * mu)
            for d in self.deadlines:
                out.append(ref_fleet.fleet_point(
                    first, d, self.n_hosts, rerun=hosts,
                    rate_mpps=rho * mu, seed=seeds[0]))
        return out

    # -- the comparison --------------------------------------------------------
    @staticmethod
    def rel(got: float, ref: float, floor: float = 0.0) -> float:
        if not (math.isfinite(got) and math.isfinite(ref)):
            return math.inf
        return abs(got - ref) / max(abs(ref), floor, 1e-12)

    def gaps(self, got: dict, ref: dict) -> dict:
        out = {"p999_gap": self.rel(got["p999_us"], ref["p999_us"]),
               "p99_gap": self.rel(got["p99_us"], ref["p99_us"]),
               "dup_gap": self.rel(got["dup_share"], ref["dup_share"],
                                   DUP_FLOOR)}
        out.update(Calibration.gaps(got, ref))
        return out

    def readings(self, records: list, seed: int, *, control: bool = False,
                 worst: dict | None = None) -> dict:
        """The numbers compared, the largest over the points of every
        record.  ``control=True`` puts the reference, computed in
        bfloat16 over other hosts, in the program's place."""
        out = {k: 0.0 for k in NUMBERS}
        worst = {} if worst is None else worst
        if not records:
            return out
        refs = self.reference(seed)
        ctrl = (self.reference(seed + 1, arith=bf16_round)
                if control else None)
        for rec in records:
            for i, (rho, d) in enumerate(self.points()):
                ref = refs[i]
                if control:
                    got = ctrl[i]
                    extra = {"overflow_share": 0.0, "dup_unaccounted": 0.0}
                else:
                    got = {"p999_us": float(rec.p999_us[i]),
                           "p99_us": float(rec.p99_us[i]),
                           "dup_share": float(rec.dup_share[i])}
                    got.update({k: float(v[i]) for k, v in rec.host.items()})
                    extra = {"overflow_share": float(rec.overflow_share[i]),
                             "dup_unaccounted": float(
                                 rec.dup_unaccounted[i])}
                vals = self.gaps(got, ref)
                vals.update(extra)
                for k, v in vals.items():
                    v = v if math.isfinite(v) else math.inf
                    if v >= out[k]:
                        out[k] = v
                        name = COMPARED.get(k, k)
                        worst[k] = ({"rho": rho, "hedge_deadline_us": d},
                                    got.get(name, extra.get(k)),
                                    ref.get(name))
        return out
