"""Tests for the unified repro.runtime API: policy/workload protocols,
multi-queue dispatch/assignment, sim/real parity, trace-replay math,
bounded stats, deprecation shims."""

import time
import warnings

import numpy as np
import pytest

from repro.core import MetronomeConfig
from repro.runtime import (
    BoundedQueue,
    BusyPollPolicy,
    CBRWorkload,
    DedicatedAssignment,
    Dispatcher,
    EqualTimeoutsPolicy,
    FixedPeriodPolicy,
    FlowHashDispatch,
    LeastLoadedDispatch,
    MetronomePolicy,
    OnOffBurstyWorkload,
    PoissonWorkload,
    Reservoir,
    RetrievalPolicy,
    RoundRobinDispatch,
    RunStats,
    Runtime,
    SharedAssignment,
    SimRunConfig,
    StealingAssignment,
    TraceReplayWorkload,
    Workload,
    simulate_run,
)
from repro.core.hr_sleep import naive_sleep


# ---------------------------------------------------------------------------
# protocols
# ---------------------------------------------------------------------------

def test_policies_and_workloads_satisfy_protocols():
    policies = [BusyPollPolicy(), MetronomePolicy(),
                FixedPeriodPolicy(50.0), EqualTimeoutsPolicy()]
    workloads = [PoissonWorkload(1.0), CBRWorkload(1.0),
                 OnOffBurstyWorkload(4.0),
                 TraceReplayWorkload([0.0, 1.0, 2.0])]
    for p in policies:
        assert isinstance(p, RetrievalPolicy), p
    for w in workloads:
        assert isinstance(w, Workload), w


def test_every_policy_runs_against_every_workload_in_sim():
    """The acceptance grid: 4 policies x 4 workloads, one engine."""
    trace = np.cumsum(np.full(50_000, 0.5))          # 2 Mpps CBR-ish trace
    mk_workloads = [
        lambda: PoissonWorkload(2.0),
        lambda: CBRWorkload(2.0),
        lambda: OnOffBurstyWorkload(8.0, on_mean_us=2_000.0,
                                    off_mean_us=6_000.0),
        lambda: TraceReplayWorkload(trace, speedup=2.0, jitter=0.1, loop=True),
    ]
    mk_policies = [
        lambda: BusyPollPolicy(),
        lambda: MetronomePolicy(MetronomeConfig(m=3)),
        lambda: FixedPeriodPolicy(50.0),
        lambda: EqualTimeoutsPolicy(MetronomeConfig(m=3, v_target_us=10.0)),
    ]
    for mw in mk_workloads:
        for mp in mk_policies:
            p, w = mp(), mw()
            rs = simulate_run(p, w, SimRunConfig(duration_us=20_000.0, seed=1))
            assert rs.serviced > 0, (p, w)
            assert rs.offered >= rs.serviced
            assert 0.0 < rs.cpu_fraction <= max(p.threads, 1) + 0.1
            if getattr(p, "spin", False):
                assert rs.cpu_fraction == pytest.approx(1.0)


def test_policy_instance_reusable_across_backends():
    """The same policy object runs in the simulator, then on real threads."""
    policy = MetronomePolicy(MetronomeConfig(m=2, v_target_us=500.0,
                                             t_long_us=5_000.0))
    rs_sim = simulate_run(policy, PoissonWorkload(1.0),
                          SimRunConfig(duration_us=50_000.0, seed=2))
    assert rs_sim.serviced > 0

    q = BoundedQueue(4096)
    seen = []
    rt = Runtime([q], process=seen.extend, policy=policy)
    rt.start()
    for i in range(50):
        q.push(i)
        time.sleep(0.001)
    time.sleep(0.05)
    rs_real = rt.stop()
    assert sorted(seen) == list(range(50))
    assert rs_real.items == 50
    assert rs_real.cpu_fraction < 1.0


# ---------------------------------------------------------------------------
# multi-queue ingress: dispatchers, assignments, conservation
# ---------------------------------------------------------------------------

# Pinned pre-refactor outputs: simulate_run with n_queues=1 and the default
# round-robin dispatcher must reproduce the original single-queue event
# sequence bit for bit (same seed => same wakeups/cycles/drops/vacations).
# awake_ns values pin round()-based us->ns conversion (not truncation).
_SINGLE_QUEUE_GOLDENS = [
    (
        lambda: MetronomePolicy(MetronomeConfig(m=3, v_target_us=10.0,
                                                t_long_us=500.0)),
        lambda: PoissonWorkload(14.88),
        lambda: SimRunConfig(duration_us=200_000.0, seed=7),
        dict(wakeups=6031, cycles=5276, busy_tries=755, serviced=2975499,
             offered=2975499, dropped=0, awake_ns=106014165,
             mean_vac=18.95650064499486, mean_busy=18.950562039912935),
    ),
    (
        lambda: FixedPeriodPolicy(50.0, threads=2),
        lambda: OnOffBurstyWorkload(20.0, on_mean_us=2_000.0,
                                    off_mean_us=5_000.0),
        lambda: SimRunConfig(duration_us=150_000.0, seed=11,
                             queue_capacity=512),
        dict(wakeups=4764, cycles=4069, busy_tries=695, serviced=1196066,
             offered=1308145, dropped=112079, awake_ns=44954390,
             mean_vac=26.98560342251278, mean_busy=9.877215479220014),
    ),
    (
        lambda: EqualTimeoutsPolicy(MetronomeConfig(m=3, v_target_us=10.0)),
        lambda: PoissonWorkload(2.0),
        lambda: SimRunConfig(duration_us=100_000.0, seed=3,
                             interference_prob=0.05,
                             interference_mean_us=50.0,
                             stall_rate_per_us=0.0001, stall_mean_us=100.0),
        dict(wakeups=18231, cycles=13610, busy_tries=4621, serviced=200139,
             offered=200139, dropped=0, awake_ns=24956101,
             mean_vac=6.85276468234786, mean_busy=0.49412937593325584),
    ),
]


@pytest.mark.parametrize("case", range(len(_SINGLE_QUEUE_GOLDENS)))
def test_single_queue_reduction_is_exact(case):
    mk_p, mk_w, mk_c, gold = _SINGLE_QUEUE_GOLDENS[case]
    rs = simulate_run(mk_p(), mk_w(), mk_c(), dispatcher=RoundRobinDispatch())
    assert rs.wakeups == gold["wakeups"]
    assert rs.cycles == gold["cycles"]
    assert rs.busy_tries == gold["busy_tries"]
    assert rs.items == gold["serviced"]
    assert rs.offered == gold["offered"]
    assert rs.dropped == gold["dropped"]
    assert rs.awake_ns == gold["awake_ns"]
    assert float(np.mean(rs.vacations_us)) == pytest.approx(
        gold["mean_vac"], rel=1e-12)
    assert float(np.mean(rs.busies_us)) == pytest.approx(
        gold["mean_busy"], rel=1e-12)


def _assert_per_queue_conserves(rs, n_queues):
    assert len(rs.per_queue) == n_queues
    assert sum(q.offered for q in rs.per_queue) == rs.offered
    assert sum(q.dropped for q in rs.per_queue) == rs.dropped
    assert sum(q.serviced for q in rs.per_queue) == rs.items
    assert sum(q.busy_tries for q in rs.per_queue) == rs.busy_tries


@pytest.mark.parametrize("mk_dispatch", [
    RoundRobinDispatch, FlowHashDispatch, LeastLoadedDispatch])
@pytest.mark.parametrize("mk_assign", [
    SharedAssignment, DedicatedAssignment, StealingAssignment])
def test_sim_per_queue_conservation(mk_dispatch, mk_assign):
    policy = MetronomePolicy(MetronomeConfig(m=4, v_target_us=10.0,
                                             t_long_us=500.0))
    rs = simulate_run(policy, PoissonWorkload(10.0),
                      SimRunConfig(duration_us=30_000.0, seed=5, n_queues=4),
                      dispatcher=mk_dispatch(), assignment=mk_assign())
    assert rs.items > 0
    _assert_per_queue_conserves(rs, 4)


@pytest.mark.parametrize("mk_assign", [
    SharedAssignment, DedicatedAssignment, StealingAssignment])
def test_threads_per_queue_conservation(mk_assign):
    qs = [BoundedQueue(4096) for _ in range(3)]
    seen = []
    rt = Runtime(qs, process=seen.extend,
                 policy=MetronomePolicy(MetronomeConfig(
                     m=3, v_target_us=500.0, t_long_us=5_000.0)),
                 assignment=mk_assign())
    rt.start()
    for i in range(300):
        qs[i % 3].push(i)
        if i % 50 == 0:
            time.sleep(0.002)
    deadline = time.monotonic() + 5.0
    while any(len(q) for q in qs) and time.monotonic() < deadline:
        time.sleep(0.005)
    st = rt.stop()
    assert sorted(seen) == list(range(300))
    _assert_per_queue_conserves(st, 3)


@pytest.mark.parametrize("mk_dispatch", [
    RoundRobinDispatch, FlowHashDispatch, LeastLoadedDispatch])
def test_dispatch_split_sums_and_respects_pick_range(mk_dispatch):
    d = mk_dispatch()
    assert isinstance(d, Dispatcher)
    rng = np.random.default_rng(0)
    d.reset(5, rng)
    backlogs = np.array([3.0, 0.0, 10.0, 1.0, 7.0])
    for n in (0, 1, 7, 1234):
        parts = d.split(n, backlogs)
        assert parts.sum() == n
        assert parts.min() >= 0
        assert len(parts) == 5
    for seq in range(50):
        assert 0 <= d.pick(seq, backlogs) < 5


def test_flow_hash_dispatch_affinity_and_skew():
    d = FlowHashDispatch(n_flows=32, zipf_s=1.5)
    d.reset(4, np.random.default_rng(3))
    # same key always lands in the same queue
    for key in ("sess-a", 17, ("user", 4)):
        picks = {d.pick(i, [0, 0, 0, 0], key=key) for i in range(10)}
        assert len(picks) == 1
    # Zipf weights are genuinely skewed: top queue well above fair share
    w = d.queue_weights
    assert w.sum() == pytest.approx(1.0)
    assert w.max() > 1.5 / 4


def test_least_loaded_dispatch_water_fills():
    d = LeastLoadedDispatch()
    d.reset(3, np.random.default_rng(0))
    parts = d.split(6, np.array([10.0, 0.0, 2.0]))
    # all 6 go to the two shortest queues, leveling them below the longest
    assert parts[0] == 0
    assert parts.sum() == 6
    assert parts[1] >= parts[2]
    assert d.pick(0, [5, 1, 3]) == 1


def test_sim_threads_parity_multi_queue_skewed():
    """The same MetronomePolicy config under the same Zipf-skewed Poisson
    load runs on both backends with 3 queues: per-queue accounting
    conserves on both, and the skew shows up in the same ordering."""
    def mk_policy():
        return MetronomePolicy(MetronomeConfig(m=3, v_target_us=1_000.0,
                                               t_long_us=20_000.0))

    rs_sim = simulate_run(
        mk_policy(), PoissonWorkload(0.002),
        SimRunConfig(duration_us=300_000.0, service_rate_mpps=0.02,
                     seed=13, n_queues=3),
        dispatcher=FlowHashDispatch(n_flows=16, zipf_s=2.0),
        assignment=StealingAssignment())
    _assert_per_queue_conserves(rs_sim, 3)
    assert rs_sim.items > 0

    qs = [BoundedQueue(65_536) for _ in range(3)]
    rt = Runtime(qs, process=lambda b: None, policy=mk_policy(),
                 sleep_fn=naive_sleep, assignment=StealingAssignment())
    rs_real = rt.run(PoissonWorkload(0.002), duration_us=300_000.0, seed=13,
                     dispatcher=FlowHashDispatch(n_flows=16, zipf_s=2.0))
    _assert_per_queue_conserves(rs_real, 3)
    assert rs_real.items > 0
    # both backends drew the same flow->queue table (same seed), so the
    # busiest queue index agrees between sim and threads
    busiest_sim = max(rs_sim.per_queue, key=lambda q: q.offered).queue
    busiest_real = max(rs_real.per_queue, key=lambda q: q.offered).queue
    assert busiest_sim == busiest_real
    # and both backends sleep most of the time at this light load
    assert rs_sim.cpu_fraction < 0.9
    assert rs_real.cpu_fraction < 0.9


def test_dedicated_assignment_clones_controllers():
    policy = MetronomePolicy(MetronomeConfig(m=2, v_target_us=10.0))
    slots = DedicatedAssignment().slots(policy, 3)
    assert len(slots) == 6                       # 2 threads x 3 queues
    pols = {id(s.policy) for s in slots}
    assert len(pols) == 3                        # one clone per queue
    assert all(id(s.policy) != id(policy) for s in slots)
    # single queue: no cloning, caller's policy object stays observable
    slots1 = DedicatedAssignment().slots(policy, 1)
    assert all(s.policy is policy for s in slots1)


def test_stealing_demotes_only_redundant_home_pollers():
    """A ring's sole home poller keeps its primary cadence on a missed
    trylock; only redundant homes take the paper's backup role."""
    policy = FixedPeriodPolicy(50.0, threads=5)
    slots = StealingAssignment().slots(policy, 4)
    assert [s.queues[0] for s in slots] == [0, 1, 2, 3, 0]
    # queue 0 has two home pollers -> they demote; queues 1-3 do not
    assert [s.demote_on_miss for s in slots] == [True, False, False, False,
                                                 True]
    assert all(s.steal for s in slots)


def test_drain_truncation_counted_and_warned():
    """A saturated run (offered rate > service rate) hits the 64-round
    drain cap; the truncation must be counted, not silently eaten."""
    rs = simulate_run(
        FixedPeriodPolicy(20.0, threads=1), PoissonWorkload(2.0),
        SimRunConfig(duration_us=30_000.0, service_rate_mpps=1.0,
                     queue_capacity=100_000, seed=0))
    assert rs.drain_truncations > 0
    with pytest.warns(RuntimeWarning, match="drain round cap"):
        s = rs.summary()
    assert s["drain_truncations"] == rs.drain_truncations


def test_runtime_rearms_vacation_clock_on_start():
    """BoundedQueue stamps last_busy_end_ns at construction; a Runtime
    started later must not report the queue's pre-start age as the first
    vacation."""
    q = BoundedQueue(64)
    vacs = []

    class Recording(FixedPeriodPolicy):
        def on_cycle_end(self, busy_us, vacation_us):
            vacs.append(vacation_us)

    rt = Runtime([q], process=lambda b: None,
                 policy=Recording(200.0, threads=1))
    time.sleep(0.25)                 # queue ages before the runtime starts
    rt.start()
    q.push(1)
    time.sleep(0.05)
    rt.stop()
    assert vacs, "no cycle observed"
    assert vacs[0] < 200_000         # << the 250ms pre-start age


def test_runtime_stop_reraises_the_first_poller_exception():
    """A poller that dies in ``process`` stops the others; ``stop()``
    re-raises its exception instead of returning as if all were well."""
    q = BoundedQueue(64)

    def process(batch):
        raise ValueError(f"bad batch {batch}")

    rt = Runtime([q], process=process,
                 policy=FixedPeriodPolicy(200.0, threads=2))
    rt.start()
    assert rt.error is None
    q.push(1)
    deadline = time.monotonic() + 5.0
    while rt.error is None and time.monotonic() < deadline:
        time.sleep(0.005)
    assert isinstance(rt.error, ValueError)
    with pytest.raises(ValueError, match="bad batch"):
        rt.stop()
    # a restart clears the recorded failure
    rt.process = lambda batch: None
    rt.start()
    assert rt.error is None
    rt.stop()


# ---------------------------------------------------------------------------
# sim/real parity
# ---------------------------------------------------------------------------

def _spin_us(us: float) -> None:
    end = time.perf_counter_ns() + int(us * 1_000)
    while time.perf_counter_ns() < end:
        pass


def _parity_pair(rate_per_us: float, service_us: float, duration_us: float,
                 seed: int = 3):
    """Run the same policy config under the same Poisson workload in the
    simulator and on real threads; return (sim_stats, real_stats, policies)."""
    def mk_policy():
        return MetronomePolicy(MetronomeConfig(m=2, v_target_us=1_000.0,
                                               t_long_us=20_000.0))

    p_sim = mk_policy()
    rs_sim = simulate_run(
        p_sim, PoissonWorkload(rate_per_us),
        SimRunConfig(duration_us=duration_us,
                     service_rate_mpps=1.0 / service_us, seed=seed))

    p_real = mk_policy()

    def process(items):
        for _ in items:
            _spin_us(service_us)

    rt = Runtime([BoundedQueue(65_536)], process=process, policy=p_real,
                 sleep_fn=naive_sleep)
    rs_real = rt.run(PoissonWorkload(rate_per_us), duration_us=duration_us,
                     seed=seed)
    return rs_sim, rs_real, p_sim, p_real


@pytest.mark.slow
def test_sim_real_parity_metronome_poisson():
    """The same MetronomePolicy configuration converges to similar rho /
    T_S and the same CPU-fraction trend in the discrete-event simulator
    and on real threads (loose bands: the real backend rides a noisy
    shared host; one retry absorbs scheduling-noise outliers)."""
    for attempt in range(2):
        try:
            _check_parity_metronome_poisson()
            return
        except AssertionError:
            if attempt == 1:
                raise


def _check_parity_metronome_poisson():
    lo = _parity_pair(rate_per_us=0.001, service_us=100.0,
                      duration_us=1_200_000.0)
    hi = _parity_pair(rate_per_us=0.004, service_us=100.0,
                      duration_us=1_200_000.0)

    for rs_sim, rs_real, p_sim, p_real in (lo, hi):
        assert rs_real.items > 0 and rs_sim.items > 0
        # rho estimates land in the same band (true rho: 0.1 / 0.4)
        assert abs(p_sim.rho - p_real.rho) < 0.25, (p_sim.rho, p_real.rho)
        # adaptive T_S within a small factor of each other
        ratio = p_sim.t_short_us / p_real.t_short_us
        assert 0.4 < ratio < 2.5, (p_sim.t_short_us, p_real.t_short_us)
        # both backends sleep most of the time at these loads
        assert rs_sim.cpu_fraction < 0.9
        assert rs_real.cpu_fraction < 0.9

    # trend parity: 4x the load raises rho in both backends.  The real
    # backend's EWMA rides empty-win cycles (a second primary waking just
    # after a busy period drags B/(B+V) toward 0) plus host scheduling
    # noise, so it only gets a directional margin (gaps of +0.03 with the
    # old 0.04 margin were observed flaking on busy hosts).
    assert hi[2].rho > lo[2].rho + 0.1          # sim
    assert hi[3].rho > lo[3].rho + 0.01         # real
    # and raises CPU in both backends
    assert hi[0].cpu_fraction > lo[0].cpu_fraction
    assert hi[1].cpu_fraction > lo[1].cpu_fraction


# ---------------------------------------------------------------------------
# trace replay math
# ---------------------------------------------------------------------------

def test_trace_replay_speedup_exact_without_jitter():
    ts = [100.0, 300.0, 500.0, 900.0]
    wl = TraceReplayWorkload(ts, speedup=2.0, jitter=0.0)
    wl.reset(np.random.default_rng(0))
    np.testing.assert_allclose(wl._times, [0.0, 100.0, 200.0, 400.0])
    assert wl.counts_in(0.0, 150.0) == 2          # arrivals at 0 and 100
    assert wl.counts_in(150.0, 400.0) == 1        # arrival at 200 ([t0, t1))
    assert wl.counts_in(400.0, 1e9) == 1          # arrival at 400
    # mean rate scales with speedup: 4 pkts over (900-100)/2 us
    assert wl.mean_rate_mpps == pytest.approx(4 / 400.0)


def test_trace_replay_jitter_bounds_and_determinism():
    ts = np.cumsum(np.full(2_000, 10.0))
    wl = TraceReplayWorkload(ts, speedup=1.0, jitter=0.25)
    wl.reset(np.random.default_rng(7))
    gaps = np.diff(wl._times)
    assert gaps.min() >= 10.0 * 0.75 - 1e-9
    assert gaps.max() <= 10.0 * 1.25 + 1e-9
    assert gaps.std() > 0.1                        # jitter actually applied
    # unbiased in expectation
    assert np.mean(gaps) == pytest.approx(10.0, rel=0.05)
    # same seed -> same replay; different seed -> different replay
    wl2 = TraceReplayWorkload(ts, speedup=1.0, jitter=0.25)
    wl2.reset(np.random.default_rng(7))
    np.testing.assert_array_equal(wl._times, wl2._times)
    wl3 = TraceReplayWorkload(ts, speedup=1.0, jitter=0.25)
    wl3.reset(np.random.default_rng(8))
    assert not np.array_equal(wl._times, wl3._times)


def test_trace_replay_loop_extends_monotonically():
    wl = TraceReplayWorkload([0.0, 10.0, 20.0], jitter=0.0, loop=True)
    wl.reset(np.random.default_rng(0))
    n = wl.counts_in(0.0, 200.0)
    assert n > 3                                   # looped past one lap
    assert np.all(np.diff(wl._times) >= 0)
    arr = list(wl.iter_arrivals(95.0, np.random.default_rng(0)))
    assert arr == sorted(arr)
    assert all(t < 95.0 for t in arr)


def test_trace_replay_validation():
    with pytest.raises(ValueError):
        TraceReplayWorkload([])
    with pytest.raises(ValueError):
        TraceReplayWorkload([1.0], speedup=0.0)
    with pytest.raises(ValueError):
        TraceReplayWorkload([1.0], jitter=1.5)
    # zero-span looped trace would never advance a lap: rejected upfront
    with pytest.raises(ValueError, match="nonzero span"):
        TraceReplayWorkload([5.0, 5.0], loop=True)
    # single-timestamp looped trace still terminates (floored restart gap)
    wl = TraceReplayWorkload([5.0], loop=True)
    wl.reset(np.random.default_rng(0))
    assert wl.counts_in(0.0, 1.0) >= 1


# ---------------------------------------------------------------------------
# workload accounting
# ---------------------------------------------------------------------------

def test_cbr_counts_are_deterministic_and_exact():
    wl = CBRWorkload(0.5)                          # one packet every 2us
    wl.reset(np.random.default_rng(0))
    total = sum(wl.counts_in(t, t + 7.0) for t in np.arange(0.0, 700.0, 7.0))
    assert total == 350
    assert wl.counts_in(10.0, 10.0) == 0


def test_onoff_counts_match_duty_cycle():
    wl = OnOffBurstyWorkload(10.0, on_mean_us=1_000.0, off_mean_us=3_000.0)
    wl.reset(np.random.default_rng(11))
    dur = 2_000_000.0
    total = sum(wl.counts_in(t, t + 50.0) for t in np.arange(0.0, dur, 50.0))
    expected = 10.0 * wl.duty_cycle * dur
    assert total == pytest.approx(expected, rel=0.2)


# ---------------------------------------------------------------------------
# bounded stats
# ---------------------------------------------------------------------------

def test_reservoir_is_bounded_and_uniform_ish():
    r = Reservoir(capacity=1_000, seed=0)
    r.extend(float(i) for i in range(100_000))
    assert len(r) == 1_000
    assert r.count == 100_000
    med = float(np.median(r))
    assert 30_000 < med < 70_000                   # uniform sample, not a head
    assert np.median(np.asarray(r)) == med         # numpy interop


def test_reservoir_vectorized_extend_matches_algorithm_r():
    """Array-like inputs take the bulk numpy path; the Algorithm-R
    invariant (bounded, uniform over everything seen) must survive."""
    r = Reservoir(capacity=1_000, seed=1)
    r.extend(np.arange(0, 40_000, dtype=np.float64))        # ndarray
    r.extend(list(range(40_000, 80_000)))                   # list
    r.extend(float(x) for x in range(80_000, 100_000))      # generator tail
    assert len(r) == 1_000
    assert r.count == 100_000
    med = float(np.median(r))
    assert 30_000 < med < 70_000
    # mixed-path chunk sizes seen in the simulator (tiny lists) still work
    r2 = Reservoir(capacity=8, seed=2)
    for i in range(100):
        r2.extend([float(i)] * 3)
    assert len(r2) == 8
    assert r2.count == 300
    # empty batches are a no-op
    r2.extend([])
    r2.extend(np.empty(0))
    assert r2.count == 300


def test_reservoir_merge_lossless_then_weighted():
    """merge() is exact concatenation while both sides are lossless and
    a count-weighted union (still bounded, still uniform-ish) after."""
    a = Reservoir(capacity=100, seed=0)
    b = Reservoir(capacity=100, seed=1)
    a.extend([1.0, 2.0, 3.0])
    b.extend([4.0, 5.0])
    a.merge(b)
    assert sorted(a) == [1.0, 2.0, 3.0, 4.0, 5.0]
    assert a.count == 5
    # weighted regime: one side saw 9x the data; the merged sample's
    # composition must reflect the 9:1 stream weights, not the 1:1
    # buffer sizes
    big = Reservoir(capacity=500, seed=2)
    small = Reservoir(capacity=500, seed=3)
    big.extend(np.zeros(45_000))
    small.extend(np.ones(5_000))
    big.merge(small)
    assert len(big) == 500
    assert big.count == 50_000
    ones = float(np.sum(np.asarray(big)))
    assert 20 <= ones <= 90                       # ~10% +- sampling noise
    # merging an empty reservoir is a no-op
    before = list(big)
    big.merge(Reservoir(capacity=10, seed=4))
    assert list(big) == before


def test_run_stats_merge_combines_shards():
    """Two equal-window sim shards merge into one run: counters add,
    per-queue slices add by index, reservoirs pool, and cpu_fraction
    becomes total cores burned over the shared window."""
    def run(seed):
        return simulate_run(
            MetronomePolicy(MetronomeConfig(m=3, v_target_us=10.0,
                                            t_long_us=500.0)),
            PoissonWorkload(10.0),
            SimRunConfig(duration_us=30_000.0, seed=seed, n_queues=2))

    a, b, fresh_a = run(1), run(2), run(1)
    merged = a.merge(b)
    assert merged is a
    for f in ("wakeups", "cycles", "busy_tries", "items", "offered",
              "dropped", "awake_ns"):
        assert getattr(merged, f) == getattr(fresh_a, f) + getattr(b, f), f
    assert merged.duration_ns == fresh_a.duration_ns      # same window
    assert merged.cpu_fraction == pytest.approx(
        fresh_a.cpu_fraction + b.cpu_fraction, rel=1e-9)
    _assert_per_queue_conserves(merged, 2)
    assert merged.latency_us.count == (fresh_a.latency_us.count
                                       + b.latency_us.count)
    lo = min(fresh_a.mean_latency_us, b.mean_latency_us)
    hi = max(fresh_a.mean_latency_us, b.mean_latency_us)
    assert lo - 1e-9 <= merged.mean_latency_us <= hi + 1e-9
    # Little-law integrals add too
    assert merged.latency_area_us == pytest.approx(
        fresh_a.latency_area_us + b.latency_area_us)
    assert merged.vacations_us.size == (fresh_a.vacations_us.size
                                        + b.vacations_us.size)
    # same-policy labels survive; mixed ones collapse
    assert merged.policy == fresh_a.policy
    c = run(3)
    c.policy = "other"
    merged.merge(c)
    assert merged.policy == "mixed"


def test_run_stats_merge_single_queue_no_reservoir_double_count():
    """Regression: with n_queues=1 the run-level and per-queue[0]
    reservoirs must not alias — merge() pools run-level and per-queue
    independently, and aliasing double-counted the donor's samples
    (count came out A + 2B)."""
    def run(seed):
        return simulate_run(
            MetronomePolicy(MetronomeConfig(m=2, v_target_us=10.0,
                                            t_long_us=500.0)),
            PoissonWorkload(8.0),
            SimRunConfig(duration_us=20_000.0, seed=seed))

    a, b, fresh_a = run(1), run(2), run(1)
    assert a.latency_us is not a.per_queue[0].latency_us
    b_count = b.latency_us.count
    b_buf = list(b.latency_us)
    a.merge(b)
    assert a.latency_us.count == fresh_a.latency_us.count + b_count
    assert a.per_queue[0].latency_us.count == a.latency_us.count
    # the donor is untouched by the merge...
    assert b.latency_us.count == b_count
    assert list(b.latency_us) == b_buf
    # ...even after the adopting side merges again (no adopted aliases)
    empty = RunStats(backend="sim", policy=a.policy, workload=a.workload)
    empty.merge(b)
    b_q0 = b.per_queue[0]
    before = (b_q0.offered, b_q0.serviced, b_q0.latency_us.count)
    empty.merge(run(3))
    assert (b_q0.offered, b_q0.serviced,
            b_q0.latency_us.count) == before


def test_per_queue_reservoirs_decorrelated_and_merge_to_total():
    """Each queue carries its own latency reservoir (decorrelated
    seeds), and the run-level reservoir is their weighted union."""
    rs = simulate_run(
        MetronomePolicy(MetronomeConfig(m=4, v_target_us=10.0,
                                        t_long_us=500.0)),
        PoissonWorkload(12.0),
        SimRunConfig(duration_us=40_000.0, seed=5, n_queues=4))
    per_q = [q.latency_us for q in rs.per_queue]
    assert all(r is not None for r in per_q)
    assert sum(r.count for r in per_q) == rs.latency_us.count
    # distinct eviction rngs: spawned seeds differ across queues
    states = {id(r._np_rng) for r in per_q}
    assert len(states) == 4
    seeds_differ = {r._rng.random() for r in per_q}
    assert len(seeds_differ) == 4


def test_runtime_restart_does_not_double_count():
    """Queue/lock counters are cumulative; a restarted Runtime must report
    only its own run's arrivals."""
    q = BoundedQueue(4096)
    rt = Runtime([q], process=lambda b: None,
                 policy=FixedPeriodPolicy(200.0, threads=1))
    for _ in range(2):
        rt.start()
        for i in range(100):
            q.push(i)
        deadline = time.monotonic() + 5.0
        while len(q) and time.monotonic() < deadline:
            time.sleep(0.005)
        st = rt.stop()
        assert st.offered == 100
        assert st.items == 100
        assert st.dropped == 0


def test_runtime_latency_samples_bounded():
    q = BoundedQueue(100_000)
    rt = Runtime([q], process=lambda b: None,
                 policy=FixedPeriodPolicy(200.0, threads=1),
                 latency_sample_every=1, latency_reservoir=256)
    rt.start()
    for i in range(3_000):
        q.push(i)
    deadline = time.monotonic() + 5.0
    while len(q) and time.monotonic() < deadline:
        time.sleep(0.01)
    st = rt.stop()
    assert st.items == 3_000
    assert len(st.latency_samples_us) <= 256       # capped despite the flood


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------

def test_core_shims_still_resolve_and_warn():
    from repro.core import (
        BoundedQueue as BQ,
        BusyPollLoop,
        MetronomePollers,
        PollerStats,
        SimConfig,
        simulate,
    )
    from repro.runtime import RunStats

    assert BQ is BoundedQueue
    assert PollerStats is RunStats

    with pytest.warns(DeprecationWarning):
        mp = MetronomePollers([BoundedQueue(16)], process=lambda b: None)
    assert isinstance(mp, Runtime)
    assert mp.controller is mp.policy.controller
    with pytest.warns(DeprecationWarning):
        bp = BusyPollLoop([BoundedQueue(16)], process=lambda b: None)
    assert isinstance(bp.policy, BusyPollPolicy)

    res = simulate(SimConfig(duration_us=20_000.0, seed=5))
    assert res.serviced > 0


def test_serving_shims_still_resolve_and_warn():
    from repro.serving import BusyPollServer, MetronomeServer, Server, ServerStats
    from repro.runtime import RunStats

    assert ServerStats is RunStats
    assert issubclass(MetronomeServer, Server)
    assert issubclass(BusyPollServer, Server)

    class _NullEngine:
        def submit(self, reqs):
            pass

        def pump(self):
            return False

    with pytest.warns(DeprecationWarning):
        srv = MetronomeServer(_NullEngine())
    assert isinstance(srv.policy, MetronomePolicy)
    assert srv.controller is srv.policy.controller
    with pytest.warns(DeprecationWarning):
        bsrv = BusyPollServer(_NullEngine())
    assert isinstance(bsrv.policy, BusyPollPolicy)


def test_old_import_surface_unchanged():
    """Everything the old repro.core exported still imports cleanly."""
    import repro.core as core

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for name in core.__all__:
            assert getattr(core, name) is not None, name


# ---------------------------------------------------------------------------
# dynamic concurrency sanitizer over real threaded runs (CI: -k threaded)
# ---------------------------------------------------------------------------

def _metronome_policy():
    return MetronomePolicy(MetronomeConfig(m=2, v_target_us=500.0,
                                           t_long_us=5_000.0))


def test_threaded_runtime_sanitizer_confirms_no_races():
    """The tier-1 race gate: a real instrumented Runtime run with the
    Eraser state machine watching every queue/stats attribute access
    must end with zero confirmed races, and the traced locks must have
    recorded real hold-time telemetry."""
    from repro.analysis.sanitizer import Sanitizer

    q = BoundedQueue(4096)
    seen = []
    rt = Runtime([q], process=seen.extend, policy=_metronome_policy())
    with Sanitizer() as san:
        san.instrument_runtime(rt)
        rt.start()
        for i in range(50):
            q.push(i)
            time.sleep(0.001)
        time.sleep(0.05)
        rs = rt.stop()
    assert rs.items == 50 and sorted(seen) == list(range(50))
    assert san.confirmed_races() == []
    locks = san.lock_report()
    assert locks["_stats_lock"]["acquisitions"] > 0
    assert locks["queue.lock"]["acquisitions"] > 0
    assert sum(locks["_stats_lock"]["hold_ns_hist"].values()) > 0


def test_threaded_server_sanitizer_confirms_no_races():
    """Same gate through the serving layer: sharded ingress, the engine
    lock's blocking/try-acquire split, and the runtime underneath."""
    from repro.analysis.sanitizer import Sanitizer
    from repro.serving import Server

    class _NullEngine:
        def submit(self, reqs):
            pass

        def pump(self):
            return False

    srv = Server(_NullEngine(), _metronome_policy(), n_queues=2)
    with Sanitizer() as san:
        san.instrument_server(srv)
        srv.start()
        for i in range(30):
            srv.submit([i])
            time.sleep(0.001)
        time.sleep(0.05)
        srv.stop()
    assert san.confirmed_races() == []
    locks = san.lock_report()
    assert {"_engine_lock", "_submit_lock", "_stats_lock",
            "queue.lock"} <= set(locks)


def test_threaded_sanitizer_catches_seeded_race():
    """The gate must be able to fail: an intentionally unguarded
    two-thread counter bump is reported, and validate() maps a static
    finding quoting the same class/attribute to CONFIRMED."""
    import threading

    from repro.analysis.sanitizer import Sanitizer

    class Buggy:
        def __init__(self):
            self.hits = 0

        def worker(self):
            for _ in range(20_000):
                self.hits += 1

    b = Buggy()
    with Sanitizer() as san:
        san.trace(b)
        ts = [threading.Thread(target=b.worker) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    races = san.confirmed_races()
    assert [(r["class"], r["attr"]) for r in races] == [("Buggy", "hits")]

    static = [{"rule": "RACE002", "fingerprint": "x", "path": "p",
               "message": ("unsynchronized read-modify-write of "
                           "'self.hits' in 'worker': no lock held, "
                           "concurrent threads can lose updates")}]
    (verdict,) = san.validate(static)
    assert verdict["status"] == "CONFIRMED"


def test_threaded_sanitizer_validates_static_fixture_findings(tmp_path):
    """PLAUSIBLE -> UNOBSERVED plumbing: the static RACE findings from
    the fixture suite stay UNOBSERVED against a clean run, and the
    saved JSON report carries races + lock histograms + verdicts."""
    import json as _json
    from pathlib import Path

    from repro.analysis import run_analysis
    from repro.analysis.sanitizer import Sanitizer

    repo = Path(__file__).resolve().parents[1]
    fixtures = repo / "tests" / "analysis_fixtures"
    static = run_analysis(
        [fixtures / "race_write_bad.py", fixtures / "race_rmw_bad.py"],
        root=repo).findings
    assert static, "fixture findings expected"

    q = BoundedQueue(1024)
    rt = Runtime([q], process=lambda b: None, policy=_metronome_policy())
    with Sanitizer() as san:
        san.instrument_runtime(rt)
        rt.start()
        for i in range(10):
            q.push(i)
            time.sleep(0.001)
        rt.stop()
    report_path = tmp_path / "sanitizer_report.json"
    san.save(report_path, static)
    payload = _json.loads(report_path.read_text())
    assert payload["schema"] == "repro-sanitizer/1"
    assert payload["races"] == []
    assert {v["status"] for v in payload["validated"]} == {"UNOBSERVED"}
    assert payload["locks"]["queue.lock"]["acquisitions"] > 0


def test_threaded_sanitizer_uninstrument_restores_classes():
    """Tracing patches type(obj); leaving the context must restore the
    class so later tests see pristine Runtime/queue behavior."""
    from repro.analysis.sanitizer import Sanitizer

    orig_set = BoundedQueue.__setattr__
    orig_get = BoundedQueue.__getattribute__
    q = BoundedQueue(16)
    with Sanitizer() as san:
        san.trace(q)
        assert BoundedQueue.__setattr__ is not orig_set
        q.push(1)
    assert BoundedQueue.__setattr__ is orig_set
    assert BoundedQueue.__getattribute__ is orig_get
