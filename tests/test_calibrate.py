"""Calibration layer: operating-table construction, persistence,
controller feed-forward, serving integration, and the calibrated-vs-
fixed-baseline acceptance verdict."""

import numpy as np
import pytest

from repro.core import MetronomeConfig, MetronomeController
from repro.runtime import (
    MetronomePolicy,
    OperatingPoint,
    OperatingTable,
    SimRunConfig,
    build_operating_table,
)


def _tiny_table(**kw):
    args = dict(
        rhos=[0.15, 0.4, 0.65],
        target_mean_latency_us=15.0,
        t_s_grid=np.linspace(4.0, 48.0, 6),
        t_l_grid=[150.0, 500.0],
        m_grid=(2, 3),
        cfg=SimRunConfig(duration_us=30_000.0),
        seeds=(0,),
        slot_us=1.0,
    )
    args.update(kw)
    return build_operating_table(**args)


@pytest.fixture(scope="module")
def table():
    return _tiny_table()


def test_build_meets_target_and_scales_cpu_with_load(table):
    assert all(p.meets_target for p in table.points)
    assert all(p.mean_latency_us <= table.target_mean_latency_us
               for p in table.points)
    assert all(p.loss_fraction <= 1e-3 for p in table.points)
    cpus = [p.cpu_fraction for p in table.points]
    assert cpus == sorted(cpus)                  # more load, more CPU
    assert cpus[-1] < 1.0                        # still beats busy-poll


def test_table_from_the_kernel_driver_equals_the_scan_drivers(driven_by,
                                                              table):
    """The slots as a TPU runs them (the Pallas kernel, in interpret mode
    off a TPU) give the same table as the ``lax.scan``, rung for rung."""
    with driven_by("kernel"):
        kernel = _tiny_table()
    assert kernel.points == table.points


def test_spot_check_against_event_engine_passes():
    # same tiny grid, now cross-examined by the exact engine
    _tiny_table(spot_check=2)


def test_table_records_its_calibration_environment(table):
    """Tentpole: the table carries the SimRunConfig it was calibrated
    in, sleep model and interference knobs included, through JSON."""
    from repro.runtime import OperatingTable

    env = table.environment
    assert env is not None
    assert env["duration_us"] == 30_000.0
    assert env["interference_prob"] == 0.0
    assert env["stall_rate_per_us"] == 0.0
    assert "base_us" in env["sleep_model"]
    rt = OperatingTable.from_json(table.to_json())
    assert rt.environment == env
    assert rt == table


def test_fleet_calibration_shrinks_host_budget_and_records_fleet():
    """A fleet-aware table gives hosts a latency budget shrunk by the
    share-weighted topology delay at the fleet-aggregate peak rate, and
    records the FleetConfig in its environment (JSON-safe)."""
    from repro.runtime import FleetConfig, OperatingTable

    fleet = FleetConfig(n_hosts=8, far_fraction=0.5, near_cost_us=1.0,
                        far_cost_us=3.0, link_rate_mpps=200.0)
    table = _tiny_table(fleet=fleet)
    cfg = SimRunConfig(duration_us=30_000.0)
    topo = fleet.mean_topo_delay_us(0.65 * cfg.service_rate_mpps * 8)
    assert topo > 0.0
    assert table.target_mean_latency_us == pytest.approx(15.0 - topo)
    assert table.environment["fleet"]["n_hosts"] == 8
    assert table.environment["fleet"]["far_fraction"] == 0.5
    rt = OperatingTable.from_json(table.to_json())
    assert rt.environment["fleet"]["link_rate_mpps"] == 200.0
    # a topology that eats the whole budget is rejected loudly
    greedy = FleetConfig(n_hosts=8, far_fraction=1.0, far_cost_us=20.0)
    with pytest.raises(ValueError, match="latency target"):
        _tiny_table(fleet=greedy)


def test_noisy_host_calibration_is_contention_honest():
    """Tentpole: build_operating_table in an interference environment
    (a) records that environment, (b) spot-checks against the event
    engine WITHOUT quieting the config first, and (c) produces a table
    whose points reflect the noisy host (higher latency than the quiet
    table at the same grid/loads)."""
    noisy_cfg = SimRunConfig(duration_us=30_000.0,
                             interference_prob=0.2,
                             interference_mean_us=15.0,
                             stall_rate_per_us=1.0 / 5000.0,
                             stall_mean_us=100.0)
    noisy = _tiny_table(cfg=noisy_cfg, target_mean_latency_us=40.0,
                        max_loss=0.05, spot_check=2)
    assert noisy.environment["interference_prob"] == 0.2
    assert noisy.environment["stall_rate_per_us"] == 1.0 / 5000.0
    quiet = _tiny_table(target_mean_latency_us=40.0, max_loss=0.05)
    for np_, qp in zip(noisy.points, quiet.points):
        assert np_.mean_latency_us > qp.mean_latency_us


def test_spot_check_runs_in_the_calibration_environment(monkeypatch):
    """The event-engine spot check must see the caller's interference
    config — the old code laundered noisy tables through a quieted
    replace(cfg, interference_prob=0, stall_rate_per_us=0)."""
    from repro.runtime import calibrate as cal

    seen_cfgs = []
    real = cal._event_sim_point

    def spy(p, cfg, rate):
        seen_cfgs.append(cfg)
        return real(p, cfg, rate)

    monkeypatch.setattr(cal, "_event_sim_point", spy)
    noisy_cfg = SimRunConfig(duration_us=20_000.0,
                             interference_prob=0.15,
                             interference_mean_us=10.0,
                             stall_rate_per_us=1.0 / 8000.0,
                             stall_mean_us=80.0)
    _tiny_table(cfg=noisy_cfg, target_mean_latency_us=40.0,
                max_loss=0.05, spot_check=1)
    assert seen_cfgs, "spot check did not run"
    for c in seen_cfgs:
        assert c.interference_prob == 0.15
        assert c.stall_rate_per_us == 1.0 / 8000.0


def test_multi_queue_build_operating_table_regression():
    """Satellite bugfix: the analytic guard used a literal-[0] n_queues
    placeholder and the aggregate rho, so every multi-queue lattice was
    compared against the wrong closed form and wholesale-rejected
    (tables fell back to meets_target=False rows).  With the per-queue
    prediction, a plainly feasible multi-queue grid calibrates."""
    cfg = SimRunConfig(duration_us=30_000.0, n_queues=2)
    tbl = _tiny_table(cfg=cfg, target_mean_latency_us=25.0)
    assert all(p.meets_target for p in tbl.points)
    assert tbl.environment["n_queues"] == 2
    cpus = [p.cpu_fraction for p in tbl.points]
    assert cpus == sorted(cpus)


def test_guard_mask_uses_per_queue_load():
    """Direct unit check of the fixed meshgrid: at n_queues=nq the
    guard's prediction is nq * general(ts, tl, m, p(rho/nq))."""
    from repro.core import analytics
    from repro.runtime.calibrate import analytic_guard_mask

    ts, tl, m, rho, nq = 12.0, 300.0, 3, 0.6, 4
    pred_q = float(nq * analytics.mean_vacation_general(
        ts, tl, m, analytics.primary_prob(rho / nq)))
    vac = np.full((1, 1, 1, 1, 1), pred_q)
    ok = analytic_guard_mask(vac, [ts], [tl], [m], [rho],
                             guard_rel=0.05, slot_us=0.0, n_queues=(nq,))
    assert ok.all()
    # the aggregate-rho prediction (the old bug) is far outside the band
    pred_agg = float(analytics.mean_vacation_general(
        ts, tl, m, analytics.primary_prob(rho)))
    vac_bad = np.full((1, 1, 1, 1, 1), pred_agg)
    assert not analytic_guard_mask(vac_bad, [ts], [tl], [m], [rho],
                                   guard_rel=0.05, slot_us=0.0,
                                   n_queues=(nq,)).any()


def test_guard_mask_interference_slack_widens_band():
    cfg = SimRunConfig(interference_prob=0.25, interference_mean_us=20.0,
                       stall_rate_per_us=1.0 / 4000.0, stall_mean_us=100.0)
    slack = cfg.interference_slack_us()
    assert slack == pytest.approx(0.25 * 20.0 + 100.0 ** 2 / 4000.0)
    from repro.core import analytics
    from repro.runtime.calibrate import analytic_guard_mask

    ts, tl, m, rho = 10.0, 200.0, 2, 0.5
    pred = float(analytics.mean_vacation_general(
        ts, tl, m, analytics.primary_prob(rho)))
    # a measurement shifted by almost the whole slack passes only when
    # the slack is threaded through
    vac = np.full((1, 1, 1, 1, 1), pred * 1.05 + slack * 0.9)
    common = dict(guard_rel=0.05, slot_us=0.0)
    assert analytic_guard_mask(vac, [ts], [tl], [m], [rho],
                               slack_us=slack, **common).all()
    assert not analytic_guard_mask(vac, [ts], [tl], [m], [rho],
                                   **common).any()


def test_lookup_is_conservative_and_interp_clamps(table):
    lo, hi = table.points[0], table.points[-1]
    # below the ladder: governed by the lowest calibrated load
    assert table.lookup(0.0) == lo
    # between rungs: governed by the next rung UP (conservative)
    mid_rho = (table.points[0].rho + table.points[1].rho) / 2
    assert table.lookup(mid_rho) == table.points[1]
    # above the ladder: clamped to the top rung
    assert table.lookup(0.99) == hi
    # interpolation clamps outside the calibrated range
    assert table.timeouts_us(0.0) == (lo.t_s_us, lo.t_l_us)
    assert table.timeouts_us(1.0) == (hi.t_s_us, hi.t_l_us)
    t_s_mid, _ = table.timeouts_us(mid_rho)
    assert (min(lo.t_s_us, table.points[1].t_s_us) <= t_s_mid
            <= max(lo.t_s_us, table.points[1].t_s_us))


def test_json_roundtrip_and_save_load(table, tmp_path):
    assert OperatingTable.from_json(table.to_json()) == table
    path = tmp_path / "op_table.json"
    table.save(path)
    assert OperatingTable.load(path) == table


def test_points_sorted_and_validated():
    pts = (OperatingPoint(rho=0.7, t_s_us=10.0, t_l_us=500.0, m=3,
                          mean_latency_us=8.0, cpu_fraction=0.7,
                          loss_fraction=0.0),
           OperatingPoint(rho=0.2, t_s_us=40.0, t_l_us=500.0, m=2,
                          mean_latency_us=14.0, cpu_fraction=0.2,
                          loss_fraction=0.0))
    t = OperatingTable(target_mean_latency_us=15.0, service_rate_mpps=29.76,
                       points=pts)
    assert [p.rho for p in t.points] == [0.2, 0.7]
    with pytest.raises(ValueError):
        OperatingTable(target_mean_latency_us=15.0,
                       service_rate_mpps=29.76, points=())


# ---------------------------------------------------------------------------
# controller / policy / server integration
# ---------------------------------------------------------------------------

def _hand_table():
    return OperatingTable(
        target_mean_latency_us=15.0, service_rate_mpps=29.76,
        points=(
            OperatingPoint(rho=0.1, t_s_us=60.0, t_l_us=800.0, m=2,
                           mean_latency_us=12.0, cpu_fraction=0.1,
                           loss_fraction=0.0),
            OperatingPoint(rho=0.9, t_s_us=10.0, t_l_us=400.0, m=3,
                           mean_latency_us=9.0, cpu_fraction=0.9,
                           loss_fraction=0.0),
        ))


def test_controller_feedforward_follows_table():
    tbl = _hand_table()
    cfg = MetronomeConfig(m=3, v_target_us=10.0, t_long_us=500.0)
    ctl = MetronomeController(cfg, feedforward=tbl)
    # init at rho_init=0.5: the table's interpolated surface, not Eq 12
    ts_ff, tl_ff = tbl.timeouts_us(cfg.rho_init)
    assert ctl.t_short_us == pytest.approx(ts_ff)
    assert ctl.t_long_us == pytest.approx(tl_ff)
    # drive rho high: T_S slides toward the high-load rung
    for _ in range(200):
        ctl.on_cycle_end(busy_us=40.0, vacation_us=10.0)
    assert ctl.rho > 0.75
    ts_hi, tl_hi = tbl.timeouts_us(ctl.rho)
    assert ctl.t_short_us == pytest.approx(ts_hi)
    assert ctl.timeout_us(primary=False) == pytest.approx(tl_hi)
    # feed-forward beats the Eq-12 upper clamp at low load: the 60us
    # low-load rung survives even though resolved_ts_max() is 30us
    ctl2 = MetronomeController(cfg, feedforward=tbl)
    for _ in range(200):
        ctl2.on_cycle_end(busy_us=0.5, vacation_us=60.0)
    assert ctl2.t_short_us > cfg.resolved_ts_max()


def test_controller_clamps_tl_above_ts_with_adversarial_table():
    """Satellite bugfix: a calibrated rung whose T_L is below T_S (or a
    pathological blend) must not invert the backup/primary roles —
    backups would fire before primaries.  The controller clamps
    T_L >= T_S at every derivation, and releases the clamp once T_S
    falls again."""
    adversarial = OperatingTable(
        target_mean_latency_us=15.0, service_rate_mpps=29.76,
        points=(
            # low-load rung: huge T_S, tiny T_L — inverted on purpose
            OperatingPoint(rho=0.1, t_s_us=120.0, t_l_us=8.0, m=2,
                           mean_latency_us=12.0, cpu_fraction=0.1,
                           loss_fraction=0.0),
            OperatingPoint(rho=0.9, t_s_us=10.0, t_l_us=400.0, m=3,
                           mean_latency_us=9.0, cpu_fraction=0.9,
                           loss_fraction=0.0),
        ))
    cfg = MetronomeConfig(m=3, v_target_us=10.0, t_long_us=500.0)
    ctl = MetronomeController(cfg, feedforward=adversarial)
    # drive rho low: the table feeds T_S=120, T_L=8 — the clamp holds
    for _ in range(300):
        ctl.on_cycle_end(busy_us=0.5, vacation_us=100.0)
        assert ctl.t_long_us >= ctl.t_short_us
        assert (ctl.timeout_us(primary=False)
                >= ctl.timeout_us(primary=True))
    assert ctl.t_short_us > 100.0          # the inverted rung is active
    # back at high load the table is sane again and the clamp releases:
    # T_L returns to the table's 400us rung, well above T_S
    for _ in range(300):
        ctl.on_cycle_end(busy_us=40.0, vacation_us=10.0)
    assert ctl.rho > 0.75
    assert ctl.t_long_us > 4 * ctl.t_short_us
    assert ctl.t_long_us >= ctl.t_short_us
    # the clamp also guards the pure-Eq-12 path (no table): a config
    # with T_L below the Eq-12 T_S band cannot invert either
    ctl2 = MetronomeController(
        MetronomeConfig(m=3, v_target_us=200.0, t_long_us=50.0))
    for _ in range(50):
        ctl2.on_cycle_end(busy_us=0.5, vacation_us=300.0)
        assert ctl2.t_long_us >= ctl2.t_short_us


def test_feedforward_weight_blends_back_to_eq12():
    tbl = _hand_table()
    cfg0 = MetronomeConfig(m=3, v_target_us=10.0, feedforward_weight=0.0)
    ctl = MetronomeController(cfg0, feedforward=tbl)
    plain = MetronomeController(MetronomeConfig(m=3, v_target_us=10.0))
    for c in (ctl, plain):
        c.on_cycle_end(busy_us=20.0, vacation_us=10.0)
    assert ctl.t_short_us == pytest.approx(plain.t_short_us)
    assert ctl.t_long_us == pytest.approx(cfg0.t_long_us)


def test_policy_carries_table_across_resets():
    tbl = _hand_table()
    pol = MetronomePolicy(MetronomeConfig(m=3, v_target_us=10.0),
                          operating_table=tbl)
    pol.reset()
    assert pol.controller.feedforward is tbl
    ts_ff, _ = tbl.timeouts_us(pol.controller.rho)
    assert pol.t_short_us == pytest.approx(ts_ff)


def test_server_loads_operating_table_at_startup(tmp_path):
    from repro.serving import Server

    class _NullEngine:
        def submit(self, reqs):
            pass

        def pump(self):
            return False

    tbl = _hand_table()
    path = tmp_path / "table.json"
    tbl.save(path)
    pol = MetronomePolicy(MetronomeConfig(m=2, v_target_us=2_000.0,
                                          t_long_us=50_000.0))
    srv = Server(_NullEngine(), pol, operating_table=str(path))
    assert srv.operating_table == tbl
    assert pol.controller.feedforward == tbl
    ts_ff, _ = tbl.timeouts_us(pol.controller.rho)
    assert pol.controller.t_short_us == pytest.approx(ts_ff)
    # policies without a controller cannot take a table
    from repro.runtime import FixedPeriodPolicy
    with pytest.raises(ValueError, match="no .*controller"):
        Server(_NullEngine(), FixedPeriodPolicy(50.0), operating_table=tbl)


# ---------------------------------------------------------------------------
# acceptance verdict: calibrated beats (<=) the fixed-t_s baseline
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sweep_frontier_verdict_calibrated_beats_fixed():
    """The benchmark's verdict row: per load, the calibrated table meets
    the latency target at CPU <= the best fixed configuration."""
    from benchmarks.sweep_frontier import sweep_frontier

    rows = {name: (val, derived)
            for name, val, derived in sweep_frontier(quick=True)}
    ok, derived = rows["verdict/ok"]
    assert ok == 1.0, rows.get("verdict/calibrated_vs_fixed_ts")
    _, vd = rows["verdict/calibrated_vs_fixed_ts"]
    assert "calibrated_leq_fixed_at_every_load=True" in vd
