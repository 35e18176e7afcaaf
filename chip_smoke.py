#!/usr/bin/env python3
"""Drive the main path once on a TPU and check what comes out.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # four chips: the sharded fleet sweep

One process, phases in order; any failure exits non-zero.  There is no
CPU fallback: without a TPU the script prints no result and exits 2.

  device       platform, device kind and count.
  calibration  ``build_operating_table`` on the paper's l3fwd deployment
               (29.76 Mpps service rate, 1,024-descriptor ring) over the
               full ``sweep_frontier`` lattice, 2,016 points of 50 ms at
               0.5 us slots; the event engine spot-checks three table
               points, and a few lattice points are held to the quiet
               event-engine parity bands of ``tests/test_batched_engine``.
  fleet        ``simulate_fleet`` on the H=16 noisy cluster over the hedge
               ladder, then the 1000-host x 8-point sweep in one call.
  serving      ``repro.launch.serve`` with gemma-2b at published widths
               (bf16, weights from ``PRNGKey(0)``) behind Metronome; every
               request completes with the greedy tokens that
               ``Model.prefill``/``decode_step`` give when driven directly.

``--four-chips`` runs only the 1000-host x 8-point sweep, sharded over
four chips and on one device, and holds the two to the tolerance of
``tests/test_fleet.py::test_shard_path_matches_vmap_path``.

Each phase prints one line: wall seconds, compile seconds (JAX tracing,
lowering and backend compile, persistent-cache reads included), points
or requests done, and the device's peak bytes in use so far.  The last
line is the JSON verdict.  Unless ``JAX_COMPILATION_CACHE_DIR`` is set,
compiled programs are cached in ``.jax_cache/`` of the checkout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# parity bands of tests/test_batched_engine.py (quiet host, n_queues=1)
LAT_ABS_US, LAT_REL = 1.5, 0.12
CPU_ABS, CPU_REL = 0.02, 0.05
WAKE_REL = 0.15
MAX_LOSS = 1e-3
# lattice points held to those bands: (T_S index, T_L, M, rho, seed),
# all in the stable region, spread over the load ladder
PARITY_POINTS = ((1, 250.0, 3, 0.7, 0), (2, 900.0, 2, 0.7, 0),
                 (3, 500.0, 3, 0.55, 0), (5, 500.0, 2, 0.25, 0),
                 (5, 250.0, 3, 0.1, 0))
# shard_map vs one device (tests/test_fleet.py)
SHARD_RTOL, SHARD_ATOL = 1e-6, 1e-3
SHARD_FIELDS = ("serviced", "lat_area", "awake_us", "hedge_dup")
SERVE_ARGV = ("--arch", "gemma-2b", "--requests", "8")


class PhaseFailed(RuntimeError):
    pass


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (a persistent-cache hit counts its read time)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name: str, fn, clock: CompileClock, *args) -> dict:
    """Run one phase; print its line; return its record."""
    c0, t0 = clock.seconds, time.perf_counter()
    rec = fn(*args)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    rec = {"phase": name, "wall_s": wall, "compile_s": compile_s,
           "run_s": wall - compile_s, **rec, "peak_bytes": peak_bytes()}
    print(" ".join(f"{k}={v}" for k, v in rec.items()), flush=True)
    return rec


# -- calibration --------------------------------------------------------------

def l3fwd_env(duration_us: float):
    """The paper's l3fwd deployment as a simulation environment."""
    from repro.configs.metronome_l3fwd import PAPER_SIM
    from repro.runtime import SimRunConfig

    return SimRunConfig(duration_us=duration_us,
                        service_rate_mpps=PAPER_SIM.service_rate_mpps,
                        queue_capacity=PAPER_SIM.queue_capacity)


def _event_reference(point: dict, cfg):
    from repro.core import MetronomeConfig
    from repro.runtime import MetronomePolicy, PoissonWorkload, simulate_run

    policy = MetronomePolicy(
        MetronomeConfig(m=point["m"], v_target_us=point["t_s_us"],
                        t_long_us=point["t_l_us"],
                        ts_min_us=min(1.0, point["t_s_us"])),
        adaptive=False)
    return simulate_run(policy, PoissonWorkload(point["rate_mpps"]), cfg)


def check_parity(bs, cfg, points) -> int:
    """Hold lattice points of the sweep to the event engine."""
    g = bs.grid
    for p in points:
        idx = np.flatnonzero(
            np.isclose(g.t_s_us, p["t_s_us"]) & np.isclose(g.t_l_us, p["t_l_us"])
            & (g.m == p["m"]) & np.isclose(g.rate_mpps, p["rate_mpps"])
            & (g.seed == p["seed"]))
        _require(len(idx) == 1, f"parity point {p} is not in the lattice")
        i = int(idx[0])
        rs = _event_reference(p, cfg)
        lat_b, lat_e = float(bs.mean_latency_us[i]), rs.mean_sojourn_us
        cpu_b, cpu_e = float(bs.cpu_fraction[i]), rs.cpu_fraction
        tag = f"sweep at {p}"
        _require(abs(lat_b - lat_e) <= max(LAT_ABS_US, LAT_REL * lat_e),
                 f"{tag}: mean latency {lat_b:.3f}us vs event {lat_e:.3f}us")
        _require(abs(cpu_b - cpu_e) <= CPU_ABS + CPU_REL * cpu_e,
                 f"{tag}: cpu {cpu_b:.4f} vs event {cpu_e:.4f}")
        _require(abs(float(bs.wakeups[i]) - rs.wakeups)
                 <= WAKE_REL * rs.wakeups,
                 f"{tag}: wakeups {float(bs.wakeups[i])} vs {rs.wakeups}")
        _require(float(bs.loss_fraction[i]) < MAX_LOSS
                 and rs.loss_fraction < MAX_LOSS, f"{tag}: loss")
    return len(points)


def parity_points(lat: dict, picks=PARITY_POINTS) -> list[dict]:
    from repro.configs.metronome_l3fwd import PAPER_SIM

    return [dict(t_s_us=float(lat["t_s_grid"][i]), t_l_us=tl, m=m,
                 rate_mpps=rho * PAPER_SIM.service_rate_mpps, seed=seed)
            for i, tl, m, rho, seed in picks]


def calibration(lat: dict, points: list[dict], target_us: float = 15.0,
                spot_check: int = 3) -> dict:
    """One lattice sweep, distilled into an operating table (event-engine
    spot checks included), then parity.  ``fused`` says which driver ran
    the slots: 1 the Pallas kernel, 0 the ``lax.scan``."""
    from repro.runtime import (
        SweepGrid,
        build_operating_table,
        simulate_batch,
        tracing,
    )

    cfg = l3fwd_env(lat["duration_us"])
    grid = SweepGrid.product(
        t_s_us=lat["t_s_grid"], t_l_us=lat["t_l_grid"], m=lat["m_grid"],
        n_queues=(cfg.n_queues,),
        rate_mpps=np.asarray(lat["rhos"]) * cfg.service_rate_mpps,
        seeds=lat["seeds"])
    bs = simulate_batch(grid, cfg, slot_us=lat["slot_us"])
    fused = [r for r in tracing.records()
             if r.name == "batched.scan"][-1].counts["fused"]
    table = build_operating_table(
        rhos=lat["rhos"], target_mean_latency_us=target_us,
        t_s_grid=lat["t_s_grid"], t_l_grid=lat["t_l_grid"],
        m_grid=lat["m_grid"], cfg=cfg, seeds=lat["seeds"],
        slot_us=lat["slot_us"], spot_check=spot_check, sweep=bs)
    for f in ("mean_latency_us", "cpu_fraction", "loss_fraction"):
        _require(bool(np.all(np.isfinite(getattr(bs, f)))),
                 f"sweep: non-finite {f}")
    _require(len(table.points) == len(lat["rhos"]), "table has a missing rung")
    missed = [p.rho for p in table.points if not p.meets_target]
    _require(not missed, f"table misses the {target_us:g}us target at rho "
                         f"{missed}")
    n_parity = check_parity(bs, cfg, points)
    return {"fused": fused, "points": len(grid),
            "slots_per_point": int(round(lat["duration_us"] / lat["slot_us"])),
            "scan_len": int(bs.scan_len), "parity_points": n_parity,
            "table": ";".join(f"{p.rho:g}:ts{p.t_s_us:.1f}/tl{p.t_l_us:g}"
                              f"/m{p.m}" for p in table.points)}


# -- fleet --------------------------------------------------------------------

def fleet_ladder(n_hosts: int, duration_us: float, slot_us: float) -> dict:
    """The noisy cluster, uniform balancer, over the hedge ladder."""
    from benchmarks import fleet as fb
    from repro.runtime import FleetConfig, simulate_fleet

    fgrid = fb.ladder_grid(FleetConfig(n_hosts=n_hosts))
    fs = simulate_fleet(fgrid, fb.fleet_env(duration_us), slot_us=slot_us)
    p999 = fs.p999_latency_us
    offered = fs.offered_with_hedges
    cores = fs.total_cpu_cores
    _require(bool(np.all(np.isfinite(p999)) and np.all(np.isfinite(cores))),
             f"H={n_hosts} ladder: non-finite output")
    # the hedging trade of tests/test_fleet.py: a tighter deadline lowers
    # p99.9 and raises the offered load including duplicates
    _require(bool(np.all(np.diff(p999) <= 1e-9)),
             f"H={n_hosts} ladder: p99.9 not monotone {p999}")
    _require(bool(np.all(np.diff(offered) > 0)),
             f"H={n_hosts} ladder: hedge duplicates not rising {offered}")
    _require(bool(np.all(cores < n_hosts)),
             f"H={n_hosts} ladder: sleeping hosts burned {cores} cores")
    return {"points": len(fgrid), "n_hosts": n_hosts, "backend": fs.backend,
            "p999_us": ";".join(f"{v:.1f}" for v in p999)}


def fleet_scale(quick: bool, shard: bool | None = None):
    """The whole-cluster sweep in one call; returns the stats and record."""
    from benchmarks import fleet as fb
    from repro.runtime import simulate_fleet

    fgrid, cfg, slot_us = fb.scale_sweep(quick)
    fs = simulate_fleet(fgrid, cfg, slot_us=slot_us, shard=shard)
    n_hosts = fgrid.fleet.n_hosts
    for f in SHARD_FIELDS + ("offered", "dropped"):
        v = getattr(fs, f)
        _require(v.shape == (len(fgrid), n_hosts)
                 and bool(np.all(np.isfinite(v))), f"scale sweep: bad {f}")
    # conservation over the fleet: served + dropped <= offered + hedges
    # (a host may serve a duplicate of traffic offered to another)
    done = fs.serviced.sum(axis=1) + fs.dropped.sum(axis=1)
    _require(bool(np.all(done <= fs.offered_with_hedges * (1 + 1e-6))),
             f"scale sweep served+dropped {done} > offered "
             f"{fs.offered_with_hedges}")
    expect = np.asarray(fgrid.grid.rate_mpps) * cfg.duration_us
    _require(bool(np.allclose(fs.offered_total, expect, rtol=0.05)),
             f"scale sweep offered {fs.offered_total} vs {expect}")
    # stall windows overflow a few percent of the rings (2-4% on the CPU)
    _require(bool(np.all(fs.loss_fraction < 0.1)),
             f"scale sweep loss {fs.loss_fraction}")
    return fs, {"points": len(fgrid), "n_hosts": n_hosts,
                "points_x_hosts": len(fgrid) * n_hosts, "backend": fs.backend}


def fleet(n_hosts: int, duration_us: float, slot_us: float,
          quick_scale: bool) -> dict:
    ladder = fleet_ladder(n_hosts, duration_us, slot_us)
    _, scale = fleet_scale(quick_scale)
    _require(scale["backend"] == "vmap",
             f"one device should vmap, got {scale['backend']}")
    return {"points": ladder["points"] + scale["points"],
            "ladder_backend": ladder["backend"],
            "ladder_p999_us": ladder["p999_us"],
            "scale_points_x_hosts": scale["points_x_hosts"],
            "scale_backend": scale["backend"]}


def four_chips(quick_scale: bool, n_dev: int) -> dict:
    """The scale sweep sharded over ``n_dev`` devices vs on one device."""
    sharded, rec = fleet_scale(quick_scale, shard=True)
    _require(rec["backend"] == f"shard_map({n_dev})",
             f"expected shard_map({n_dev}), got {rec['backend']}")
    single, rec1 = fleet_scale(quick_scale, shard=False)
    _require(rec1["backend"] == "vmap", f"got {rec1['backend']}")
    worst = 0.0
    for f in SHARD_FIELDS:
        a, b = getattr(single, f), getattr(sharded, f)
        err = np.abs(a - b) / (SHARD_ATOL + SHARD_RTOL * np.abs(a))
        worst = max(worst, float(err.max()))
        _require(bool(np.all(err <= 1.0)),
                 f"sharded {f} disagrees with one device: max "
                 f"|diff| {float(np.abs(a - b).max())}")
    return {"points": rec["points"] * 2, "n_hosts": rec["n_hosts"],
            "backend": rec["backend"], "single_backend": rec1["backend"],
            "worst_err_over_tol": worst}


# -- serving ------------------------------------------------------------------

def reference_tokens(served) -> list[list[int]]:
    """Greedy tokens of ``Model.prefill``/``decode_step`` driven directly
    on the served prompts, with the engine's shapes (prefill bucket,
    slot batch, cache length) so that each row runs the same program."""
    import jax
    import jax.numpy as jnp

    model, params, ecfg = served.model, served.params, served.engine_cfg
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step, donate_argnums=(2,))
    out = []
    reqs = served.requests
    for lo in range(0, len(reqs), ecfg.max_slots):
        group = reqs[lo:lo + ecfg.max_slots]
        cache = model.init_cache(ecfg.max_slots, ecfg.max_len)
        toks = np.zeros(ecfg.max_slots, np.int32)
        pos = np.zeros(ecfg.max_slots, np.int32)
        gen = [[] for _ in group]
        for row, r in enumerate(group):
            bucket = next(b for b in ecfg.prefill_buckets
                          if len(r.prompt) <= b)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(r.prompt)] = r.prompt
            logits, pre = prefill(params, {"tokens": jnp.asarray(padded)})
            cache = jax.tree.map(
                lambda c, p, row=row: c.at[:, row, :p.shape[2]].set(
                    p[:, 0].astype(c.dtype)), cache, pre)
            toks[row] = int(jnp.argmax(logits[0, len(r.prompt) - 1]))
            pos[row] = len(r.prompt)
            gen[row].append(int(toks[row]))
        for _ in range(max(r.max_new_tokens for r in group) - 1):
            logits, cache = decode(params, jnp.asarray(toks), cache,
                                   jnp.asarray(pos))
            toks = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            pos += 1
            for row in range(len(group)):
                gen[row].append(int(toks[row]))
        out += [g[:r.max_new_tokens] for g, r in zip(gen, group)]
    return out


def serving(argv) -> dict:
    from repro.launch import serve

    served = serve.serve(list(argv))
    n = len(served.requests)
    _require(served.ok and served.completed == n,
             f"served {served.completed}/{n} requests")
    want = reference_tokens(served)
    got = [list(r.tokens) for r in served.requests]
    bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    _require(not bad, f"requests {bad} differ from the direct model: "
                      f"{[(got[i], want[i]) for i in bad]}")
    return {"requests": n, "arch": served.cfg.name,
            "tokens": sum(len(t) for t in got),
            "cpu_fraction": served.stats.cpu_fraction}


# -- entry --------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet sweep sharded over 4 chips, "
                         "against one device")
    args = ap.parse_args(argv)

    dev = device_info()
    want = 4 if args.four_chips else 1
    if dev["platform"] != "tpu" or dev["count"] < want:
        print(f"need {want} TPU chip(s); JAX sees {dev['count']} "
              f"{dev['platform']} device(s)", file=sys.stderr)
        return 2
    print(f"phase=device platform={dev['platform']} kind={dev['kind']!r} "
          f"count={dev['count']}", flush=True)

    from repro.launch import jax_cache

    print(f"compile_cache={jax_cache.enable()}", flush=True)
    clock = CompileClock()
    if args.four_chips:
        run_phase("fleet_4chip", four_chips, clock, False, 4)
    else:
        from benchmarks.sweep_frontier import lattice

        lat = lattice(quick=False)
        points = parity_points(lat)
        run_phase("calibration", calibration, clock, lat, points)
        run_phase("fleet", fleet, clock, 16, 60_000.0, 0.5, False)
        run_phase("serving", serving, clock, SERVE_ARGV)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
