"""Plain reference for one sleep&wake packet-retrieval host.

An event-driven simulation of Metronome's static-timeout retrieval on
one Rx ring, written from the paper's model and independent of the code
under test (it imports nothing of ``repro``):

  - ``m`` poller threads start within one short timeout of each other;
  - a waking thread that finds the ring unlocked takes the lock, ends
    the ring's vacation and drains it at ``service_rate_mpps``, taking
    in what arrives meanwhile (busy-period recursion, at most 64
    rounds); it then sleeps the short timeout T_S;
  - a waking thread that finds the ring locked sleeps the long
    timeout T_L;
  - arrivals are Poisson, counted per interval, and dropped beyond
    ``queue_capacity`` (Rx-ring semantics);
  - each sleep overshoots its target by ``base + slope*target +
    |N(0, sigma)|`` (the HR-sleep timer model);
  - each wake costs ``wake_cost_us`` of CPU, each drained packet
    ``1/mu``;
  - optional correlated stall windows: a Poisson process of
    Exp(``stall_mean_us``)-long freezes; a timer expiring inside one
    fires at its end plus U(0, 1) us and is not counted as a wake.

Mean sojourn is Little's law: the area under the backlog curve over
the packets served.

``arith`` sets the precision every computed value is rounded to: the
reference computes in float64 (``None``); ``bf16_round`` rounds every
value to bfloat16, which is the benchmark's control (a precision below
the float32 that the configuration states).  ``max_events`` bounds the
loop, because a clock held in bfloat16 can stop advancing.
"""

from __future__ import annotations

import math

import numpy as np


def bf16_round(x: float) -> float:
    """Round ``x`` to the nearest bfloat16 value (8 significant bits,
    ties to even)."""
    if x == 0.0 or not math.isfinite(x):
        return x
    mant, exp = math.frexp(x)
    return math.ldexp(round(mant * 256.0) / 256.0, exp)


def simulate_host(*, t_s_us: float, t_l_us: float, m: int,
                  rate_mpps: float, duration_us: float,
                  service_rate_mpps: float, queue_capacity: float,
                  wake_cost_us: float, sleep_base_us: float,
                  sleep_slope: float, sleep_sigma_us: float,
                  stall_rate_per_us: float = 0.0,
                  stall_mean_us: float = 0.0, seed: int = 0,
                  keep_latencies: int = 0, arith=None,
                  max_events: int | None = None) -> dict:
    """Run one host; returns its counters and derived statistics.

    ``keep_latencies > 0`` also returns up to that many per-packet
    sojourn samples (ages of the packets found at each busy start plus
    their drain position), for tail estimates."""
    r = arith or float
    rng = np.random.default_rng(seed)
    mu = r(service_rate_mpps)
    lam = r(rate_mpps)
    cap = float(queue_capacity)

    def sleep(target: float) -> float:
        over = sleep_base_us + sleep_slope * target + abs(
            rng.normal(0.0, sleep_sigma_us))
        return r(target + r(over))

    wake_at = [r(x) for x in rng.uniform(0.0, max(t_s_us, 1e-3), size=m)]
    backlog = 0.0
    last_adv = 0.0
    busy_until = 0.0
    last_busy_end = 0.0
    offered = dropped = served = 0.0
    wakeups = busy_tries = cycles = 0.0
    awake = 0.0
    lat_area = 0.0
    samples: list[float] = []
    next_stall = (rng.exponential(1.0 / stall_rate_per_us)
                  if stall_rate_per_us else math.inf)
    stall_end = -1.0
    events = 0
    limit = max_events if max_events is not None else math.inf

    while events < limit:
        events += 1
        i = min(range(m), key=wake_at.__getitem__)
        t = wake_at[i]
        if t >= duration_us:
            break
        if stall_rate_per_us:
            while next_stall <= t:
                stall_end = max(stall_end, r(
                    next_stall + rng.exponential(stall_mean_us)))
                next_stall += rng.exponential(1.0 / stall_rate_per_us)
            if t < stall_end:
                wake_at[i] = r(stall_end + rng.uniform(0.0, 1.0))
                continue
        wakeups = r(wakeups + 1.0)
        awake = r(awake + wake_cost_us)
        if t > last_adv:
            n = float(rng.poisson(lam * (t - last_adv)))
            offered = r(offered + n)
            room = max(cap - backlog, 0.0)
            if n > room:
                dropped = r(dropped + (n - room))
                n = room
            backlog = r(backlog + n)
            last_adv = t
        if t < busy_until:
            busy_tries = r(busy_tries + 1.0)
            wake_at[i] = r(t + sleep(t_l_us))
            continue
        # lock won: the vacation ends, the n_v packets found arrived
        # about uniformly over it
        v = max(r(t - last_busy_end), 0.0)
        n_v = backlog
        lat_area = r(lat_area + r(n_v * v / 2.0))
        if keep_latencies and n_v >= 1.0 and len(samples) < keep_latencies:
            k = min(int(n_v), 8)
            age = rng.uniform(0.0, max(v, 1e-9), size=k)
            pos = np.sort(rng.uniform(0.0, n_v, size=k)) / service_rate_mpps
            samples.extend((max(v, 1e-9) - age + pos).tolist())
        busy = 0.0
        cursor = t
        rounds = 0
        while backlog >= 1.0 and rounds < 64:
            dt = r(backlog / mu)
            b_r = backlog
            served = r(served + b_r)
            busy = r(busy + dt)
            own = float(rng.poisson(lam * dt))
            cursor = r(cursor + dt)
            offered = r(offered + own)
            if own > cap:
                dropped = r(dropped + (own - cap))
                own = cap
            backlog = own
            lat_area = r(lat_area + r(dt * (b_r + own) / 2.0))
            rounds += 1
        last_adv = max(last_adv, cursor)
        cycles = r(cycles + 1.0)
        busy_until = r(t + busy)
        last_busy_end = busy_until
        awake = r(awake + busy)
        wake_at[i] = r(busy_until + sleep(t_s_us))

    out = {
        "offered": offered, "dropped": dropped, "served": served,
        "wakeups": wakeups, "busy_tries": busy_tries, "cycles": cycles,
        "awake_us": awake, "lat_area": lat_area,
        "mean_latency_us": lat_area / max(served, 1.0),
        "cpu_fraction": awake / duration_us,
        "loss_fraction": dropped / max(offered, 1.0),
        "events": events,
    }
    if keep_latencies:
        out["latencies_us"] = np.asarray(samples[:keep_latencies])
    return out
