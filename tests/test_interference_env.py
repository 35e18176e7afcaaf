"""Dedicated coverage for the CPU-sharing environment model: the event
engine's per-wake interference and correlated-stall paths
(repro.runtime.sim) and SleepModel tail sampling (repro.runtime.simcore)
— golden-pinned directional effects at fixed seed."""

import numpy as np
import pytest

from repro.core import MetronomeConfig
from repro.runtime import (
    MetronomePolicy,
    PoissonWorkload,
    SimRunConfig,
    simulate_run,
)
from repro.runtime.simcore import (
    HR_SLEEP_MODEL,
    NANOSLEEP_MODEL,
    PERFECT_SLEEP_MODEL,
    SleepModel,
)


def _run(cfg):
    policy = MetronomePolicy(
        MetronomeConfig(m=3, v_target_us=10.0, t_long_us=500.0),
        adaptive=False)
    return simulate_run(policy, PoissonWorkload(0.7 * 29.76), cfg)


def _cfg(**kw):
    base = dict(duration_us=200_000.0, queue_capacity=256, seed=11,
                sleep_model=HR_SLEEP_MODEL)
    base.update(kw)
    return SimRunConfig(**base)


def test_per_wake_interference_strictly_raises_vacation_and_loss():
    """sim.py's interference branch: Bernoulli x Exp per-wake delays
    strictly increase mean vacation AND loss over the quiet baseline at
    the same seed (the queue sized so the delays actually overflow)."""
    quiet = _run(_cfg())
    noisy = _run(_cfg(interference_prob=0.3, interference_mean_us=120.0))
    assert noisy.mean_vacation_us > quiet.mean_vacation_us
    assert noisy.loss_fraction > quiet.loss_fraction
    assert noisy.mean_sojourn_us > quiet.mean_sojourn_us


def test_correlated_stalls_strictly_raise_vacation_and_loss():
    """sim.py's stall-window branch: system-wide freeze windows defer
    every wake inside them — vacations stretch and the ring overflows,
    strictly above the quiet baseline at the same seed."""
    quiet = _run(_cfg())
    stalled = _run(_cfg(stall_rate_per_us=1.0 / 4_000.0,
                        stall_mean_us=300.0))
    assert stalled.mean_vacation_us > quiet.mean_vacation_us
    assert stalled.loss_fraction > quiet.loss_fraction
    # deferred wakes are not charged: the stalled run wakes *less*
    assert stalled.wakeups < quiet.wakeups


def test_interference_and_stalls_compose():
    """Both injections together are worse than either alone (same seed,
    same workload) — the noisy-shared-host worst case."""
    intf = _run(_cfg(interference_prob=0.3, interference_mean_us=120.0))
    stall = _run(_cfg(stall_rate_per_us=1.0 / 4_000.0, stall_mean_us=300.0))
    both = _run(_cfg(interference_prob=0.3, interference_mean_us=120.0,
                     stall_rate_per_us=1.0 / 4_000.0, stall_mean_us=300.0))
    assert both.loss_fraction > max(intf.loss_fraction, stall.loss_fraction)
    assert both.mean_vacation_us > max(intf.mean_vacation_us,
                                       stall.mean_vacation_us)


# ---------------------------------------------------------------------------
# SleepModel tail sampling (simcore.py)
# ---------------------------------------------------------------------------

def _both_forms(model, target_us, n, seed):
    """``n`` overshoots at one target from each form of the model: the
    array ``sample`` and the scalar ``sampler`` the event engine runs."""
    draw = model.sampler(np.random.default_rng(seed))
    return (model.sample(np.full(n, target_us), np.random.default_rng(seed)),
            np.array([draw(target_us) for _ in range(n)]))


def test_sleep_model_tail_adds_exp_mass():
    """Golden-pinned at fixed rng: the Bernoulli x Exp tail arm adds
    ~tail_prob * tail_mean to the mean overshoot and produces samples
    far beyond the Gaussian arm's reach, in both forms of the model."""
    base = SleepModel(base_us=2.8, slope=0.027, sigma_us=0.5)
    tailed = SleepModel(base_us=2.8, slope=0.027, sigma_us=0.5,
                        tail_prob=0.05, tail_mean_us=400.0)
    for plain, heavy in zip(_both_forms(base, 50.0, 200_000, 3),
                            _both_forms(tailed, 50.0, 200_000, 3)):
        extra = float(np.mean(heavy) - np.mean(plain))
        assert extra == pytest.approx(0.05 * 400.0, rel=0.1)
        # the tail reaches multi-hundred-us; the Gaussian arm never does
        assert float(np.max(heavy)) > 1_000.0
        assert float(np.max(plain)) < 50.0 * 1.1 + 2.8 + 10 * 0.5


def test_sleep_model_certain_tail_mean_is_pinned():
    """tail_prob=1: every sample carries one Exp(tail_mean) draw, so the
    mean overshoot is base + slope*t + E|N| + tail_mean."""
    m = SleepModel(base_us=5.0, slope=0.0, sigma_us=0.0,
                   tail_prob=1.0, tail_mean_us=200.0)
    for s in _both_forms(m, 10.0, 100_000, 9):
        assert float(np.mean(s)) == pytest.approx(10.0 + 5.0 + 200.0,
                                                  rel=0.02)
        assert float(np.min(s)) >= 15.0


def test_sleep_model_no_tail_is_affine_plus_halfnormal():
    m = SleepModel(base_us=2.0, slope=0.1, sigma_us=1.0)
    # mean = t + base + slope*t + sigma*sqrt(2/pi)
    expect = 20.0 + 2.0 + 2.0 + 1.0 * np.sqrt(2.0 / np.pi)
    for s in _both_forms(m, 20.0, 100_000, 4):
        assert float(np.mean(s)) == pytest.approx(expect, rel=0.01)
        assert float(np.min(s)) >= 24.0


@pytest.mark.parametrize("model", [
    HR_SLEEP_MODEL, NANOSLEEP_MODEL, PERFECT_SLEEP_MODEL,
    SleepModel(base_us=5.0, slope=0.0, sigma_us=0.0, tail_prob=0.3,
               tail_mean_us=200.0)])
def test_sleep_model_sampler_draws_what_sample_draws(model):
    """The event engine's scalar sampler consumes the stream as
    ``sample`` does for one target (the tail's exponential drawn hit or
    miss) and gives the same values, bit for bit, and the stream is
    left in the same state."""
    targets = np.random.default_rng(0).uniform(1.0, 900.0, 2_000).tolist()
    a, b = np.random.default_rng(21), np.random.default_rng(21)
    draw = model.sampler(b)
    for t in targets:
        want = float(model.sample(t, a))
        got = draw(t)
        assert type(got) is float and got == want
    assert a.random() == b.random()
