"""Tests for hr_sleep, trylock, controller, and the real-thread pollers."""

import threading
import time

import numpy as np
import pytest

from repro.core import (
    BoundedQueue,
    BusyPollLoop,
    MetronomeConfig,
    MetronomeController,
    MetronomePollers,
    TryLock,
    hr_sleep,
    naive_sleep,
)


# ---------------------------------------------------------------------------
# hr_sleep
# ---------------------------------------------------------------------------

def test_hr_sleep_never_undershoots():
    for tgt in (5_000, 50_000, 200_000):
        t0 = time.perf_counter_ns()
        hr_sleep(tgt)
        assert time.perf_counter_ns() - t0 >= tgt


def test_hr_sleep_more_precise_than_naive():
    """Table 1 structure: mean overshoot of hr_sleep < naive at us scale.

    A busy machine preempts a thread for a whole scheduler tick (about
    4 ms) now and then, in bursts, and the spin at the end of hr_sleep
    is what a tick catches.  Measured one arm after the other, a burst
    can land on the hr_sleep arm alone, so the two arms alternate sample
    by sample and see the same load.  They take ``measure_precision``'s
    300 samples each: a single tick moves a mean of 300 by 13 us, less
    than the 50 us of timer slack Linux adds to every naive sleep."""
    samples = 300
    for t in (20_000, 100_000):
        over = np.zeros(2)
        for _ in range(samples):
            for arm, sleep in enumerate((hr_sleep, naive_sleep)):
                t0 = time.perf_counter_ns()
                sleep(t)
                over[arm] += time.perf_counter_ns() - t0 - t
        hr_over, nv_over = over / samples
        assert hr_over < nv_over, (t, hr_over, nv_over)


def test_hr_sleep_sub_us_immediate():
    t0 = time.perf_counter_ns()
    hr_sleep(500, sub_us_immediate=True)
    assert time.perf_counter_ns() - t0 < 1_000_000  # returned ~immediately


# ---------------------------------------------------------------------------
# trylock
# ---------------------------------------------------------------------------

def test_trylock_single_winner():
    lock = TryLock()
    winners = []
    barrier = threading.Barrier(8)

    def race(i):
        barrier.wait()
        if lock.try_acquire():
            winners.append(i)

    ts = [threading.Thread(target=race, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(winners) == 1
    assert lock.acquisitions == 1
    assert lock.busy_tries == 7
    lock.release()
    assert lock.try_acquire()


# ---------------------------------------------------------------------------
# controller
# ---------------------------------------------------------------------------

def test_controller_converges_and_respects_roles():
    cfg = MetronomeConfig(m=3, v_target_us=10.0, t_long_us=500.0, alpha=0.2)
    ctrl = MetronomeController(cfg)
    for _ in range(200):
        ctrl.on_cycle_end(busy_us=30.0, vacation_us=10.0)   # rho -> 0.75
    assert ctrl.rho == pytest.approx(0.75, abs=0.01)
    ts = ctrl.timeout_us(primary=True)
    expected = 3 * 10.0 * (1 - 0.75) / (1 - 0.75**3)
    assert ts == pytest.approx(expected, rel=0.02)
    assert ctrl.timeout_us(primary=False) == 500.0
    assert ctrl.timeout_ns(primary=False) == 500_000


def test_controller_clamps():
    cfg = MetronomeConfig(m=4, v_target_us=10.0, ts_min_us=2.0)
    ctrl = MetronomeController(cfg)
    for _ in range(100):
        ctrl.on_cycle_end(busy_us=1000.0, vacation_us=0.001)  # rho -> 1
    assert ctrl.t_short_us >= 2.0
    for _ in range(300):
        ctrl.on_cycle_end(busy_us=0.0, vacation_us=100.0)     # rho -> 0
    assert ctrl.t_short_us <= 4 * 10.0 + 1e-9


# ---------------------------------------------------------------------------
# pollers (integration, real threads)
# ---------------------------------------------------------------------------

def _feed(q: BoundedQueue, n: int, rate_hz: float):
    period = 1.0 / rate_hz
    for i in range(n):
        q.push(i)
        time.sleep(period)


def test_metronome_pollers_drain_everything():
    q = BoundedQueue(capacity=4096)
    seen = []
    pollers = MetronomePollers([q], process=seen.extend,
                               cfg=MetronomeConfig(m=3, v_target_us=200.0,
                                                   t_long_us=2000.0))
    pollers.start()
    _feed(q, 300, rate_hz=3000.0)
    time.sleep(0.2)
    stats = pollers.stop()
    assert len(seen) == 300
    assert sorted(seen) == list(range(300))          # no loss, no duplication
    assert stats.cycles > 0
    assert q.dropped == 0
    assert stats.cpu_fraction < 1.0                  # it actually slept


def test_metronome_cpu_below_busy_poll():
    def run(cls, **kw):
        q = BoundedQueue(capacity=4096)
        sink = []
        loop = cls([q], process=sink.extend, **kw)
        loop.start()
        _feed(q, 200, rate_hz=2000.0)
        deadline = time.monotonic() + 3.0
        while len(sink) < 200 and time.monotonic() < deadline:
            time.sleep(0.01)                 # let the pollers drain the tail
        st = loop.stop()
        return st, len(sink)

    m_stats, m_n = run(MetronomePollers,
                       cfg=MetronomeConfig(m=2, v_target_us=500.0, t_long_us=5000.0))
    b_stats, b_n = run(BusyPollLoop)
    assert m_n == b_n == 200
    assert m_stats.cpu_fraction < 0.8 * b_stats.cpu_fraction


def test_bounded_queue_drops_on_overflow():
    q = BoundedQueue(capacity=8)
    for i in range(20):
        q.push(i)
    assert len(q) == 8
    assert q.dropped == 12
    assert q.offered == 20


def test_pollers_latency_bounded_by_vacation_target():
    q = BoundedQueue(capacity=4096)
    pollers = MetronomePollers([q], process=lambda b: None,
                               cfg=MetronomeConfig(m=3, v_target_us=300.0,
                                                   t_long_us=3000.0),
                               latency_sample_every=1)
    pollers.start()
    _feed(q, 150, rate_hz=1500.0)
    time.sleep(0.1)
    stats = pollers.stop()
    assert stats.latency_samples_us, "no latency samples collected"
    med = float(np.median(stats.latency_samples_us))
    # Retrieval latency should be on the order of the vacation target, far
    # below the backup timeout (which would indicate a dead primary).
    assert med < 3000.0, med
