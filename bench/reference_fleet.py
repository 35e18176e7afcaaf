"""Plain reference for a fleet of sleep&wake hosts with hedged requests.

Written from the deployment's description and independent of the code
under test (it imports nothing of ``repro``):

  - each host is the host of ``bench.reference``: ``m`` poller threads
    on one Rx ring, static timeouts T_S/T_L, Poisson arrivals counted
    per interval and dropped beyond ``queue_capacity``, the HR-sleep
    overshoot, busy-period recursion at ``service_rate_mpps``, and
    Exp(``stall_mean_us``) stall windows that delay timers (a thread
    already draining keeps draining);
  - the fleet is ``n_hosts`` such hosts behind a uniform balancer, with
    stalls independent across hosts; a seeded sample of them is
    simulated;
  - every packet's sojourn is measured exactly, FIFO: the loop records
    the ring's cumulative admission curve and its cumulative departure
    curve (arrivals spread evenly over each counting interval, the
    earliest of them admitted when the ring is short of room), and a
    packet's sojourn is its departure time minus its admission time at
    the same rank;
  - hedging after D, first completion wins: a request still queued at
    age D is duplicated to a random other replica (uniform over the
    other hosts); a duplicate that finds that ring full is lost; the
    request completes at ``min(x, D + y)``, with ``x`` its own sojourn
    and ``y`` its duplicate's.  A duplicate is never hedged again, and
    is served even after its original completed;
  - duplicates load the partners: one fixed-point pass simulates the
    hosts again with their rate raised by the fraction S(D) of packets
    that the first pass finds still queued at age D.

``arith`` sets the precision every computed value is rounded to, as in
``bench.reference``: ``None`` is float64, ``bf16_round`` is the control.
"""

from __future__ import annotations

import math

import numpy as np

RANKS = 65536            # stratified packet ranks sampled per host


def _segments_time(segs: list, ranks: np.ndarray) -> np.ndarray:
    """Time at which a cumulative curve reaches each rank.  ``segs`` are
    (c0, c1, t0, t1): the curve rises linearly from c0 at t0 to c1 at t1,
    and the segments follow each other in rank."""
    s = np.asarray(segs, dtype=np.float64).reshape(-1, 4)
    i = np.minimum(np.searchsorted(s[:, 1], ranks, side="left"),
                   len(s) - 1)
    c0, c1, t0, t1 = s[i, 0], s[i, 1], s[i, 2], s[i, 3]
    frac = np.clip((ranks - c0) / np.maximum(c1 - c0, 1e-12), 0.0, 1.0)
    return t0 + frac * (t1 - t0)


def simulate_host(*, t_s_us: float, t_l_us: float, m: int,
                  rate_mpps: float, duration_us: float,
                  service_rate_mpps: float, queue_capacity: float,
                  wake_cost_us: float, sleep_base_us: float,
                  sleep_slope: float, sleep_sigma_us: float,
                  stall_rate_per_us: float = 0.0,
                  stall_mean_us: float = 0.0, seed: int = 0,
                  ranks: int = RANKS, arith=None,
                  max_events: int | None = None) -> dict:
    """Run one host; returns its counters, derived statistics and
    ``sojourns_us``: the sorted sojourns of ``ranks`` packets drawn
    stratified over every packet served."""
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
    offered = dropped = served = 0.0
    wakeups = awake = lat_area = 0.0
    last_busy_end = 0.0
    adm_segs: list = []       # (c0, c1, t0, t1) of the admission curve
    dep_segs: list = []       # the same of the departure curve
    cum_a = cum_d = 0.0
    next_stall = (rng.exponential(1.0 / stall_rate_per_us)
                  if stall_rate_per_us else math.inf)
    stall_end = -1.0
    events = 0
    limit = max_events if max_events is not None else math.inf

    def admit(n: float, room: float, t0: float, t1: float) -> float:
        """Admit the earliest ``min(n, room)`` of ``n`` arrivals spread
        over [t0, t1]; returns how many were admitted."""
        nonlocal cum_a
        k = min(n, max(room, 0.0))
        if k > 0.0:
            adm_segs.append((cum_a, cum_a + k, t0,
                             r(t0 + r((t1 - t0) * k / n))))
            cum_a = r(cum_a + k)
        return k

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
            k = admit(n, cap - backlog, last_adv, t)
            dropped = r(dropped + (n - k))
            backlog = r(backlog + k)
            last_adv = t
        if t < busy_until:
            wake_at[i] = r(t + sleep(t_l_us))
            continue
        v = max(r(t - last_busy_end), 0.0)
        lat_area = r(lat_area + r(backlog * v / 2.0))
        busy = 0.0
        cursor = t
        rounds = 0
        while backlog >= 1.0 and rounds < 64:
            dt = r(backlog / mu)
            b_r = backlog
            dep_segs.append((cum_d, cum_d + b_r, cursor, r(cursor + dt)))
            cum_d = r(cum_d + b_r)
            served = r(served + b_r)
            busy = r(busy + dt)
            own = float(rng.poisson(lam * dt))
            offered = r(offered + own)
            k = admit(own, cap, cursor, r(cursor + dt))
            dropped = r(dropped + (own - k))
            cursor = r(cursor + dt)
            backlog = k
            lat_area = r(lat_area + r(dt * (b_r + k) / 2.0))
            rounds += 1
        last_adv = max(last_adv, cursor)
        busy_until = r(t + busy)
        last_busy_end = busy_until
        awake = r(awake + busy)
        wake_at[i] = r(busy_until + sleep(t_s_us))

    out = {
        "offered": offered, "dropped": dropped, "served": served,
        "wakeups": wakeups, "awake_us": awake, "lat_area": lat_area,
        "mean_latency_us": lat_area / max(served, 1.0),
        "cpu_fraction": awake / duration_us,
        "loss_fraction": dropped / max(offered, 1.0),
        "events": events,
    }
    if served >= 1.0 and adm_segs:
        u = (np.arange(ranks) + rng.random(ranks)) / ranks
        rk = u * served
        soj = (_segments_time(dep_segs, rk) - _segments_time(adm_segs, rk))
        out["sojourns_us"] = np.sort(np.maximum(soj, 0.0))
    else:
        out["sojourns_us"] = np.zeros(1)
    return out


def host_kwargs(config: dict, duration_us: float, *, t_s_us: float,
                t_l_us: float, m: int) -> dict:
    """Keyword arguments of ``simulate_host`` for a configuration file's
    host at one operating point (the rate is given per call)."""
    sm = config["sleep_model"]
    return dict(t_s_us=float(t_s_us), t_l_us=float(t_l_us), m=int(m),
                duration_us=float(duration_us),
                service_rate_mpps=float(config["service_rate_mpps"]),
                queue_capacity=float(config["queue_capacity"]),
                wake_cost_us=float(config["wake_cost_us"]),
                sleep_base_us=float(sm["base_us"]),
                sleep_slope=float(sm["slope"]),
                sleep_sigma_us=float(sm["sigma_us"]),
                stall_rate_per_us=float(config["stall_rate_per_us"]),
                stall_mean_us=float(config["stall_mean_us"]))


def sample_hosts(kw: dict, rate_mpps: float, seeds, *, arith=None) -> list:
    """``simulate_host`` of every sampled host at ``rate_mpps``.  In
    reduced precision the loop is bounded at a few times the events a
    float64 run needs (a clock held in bfloat16 can stop advancing)."""
    cap = None
    if arith is not None:
        cap = int(8 * kw["m"] * kw["duration_us"] / kw["t_s_us"]) + 10_000
    return [simulate_host(**kw, rate_mpps=rate_mpps, seed=int(s),
                          arith=arith, max_events=cap) for s in seeds]


def still_queued_at(hosts: list, d: float) -> float:
    """Share of the packets served that were still queued at age ``d``."""
    n = sum(h["served"] for h in hosts)
    late = sum(h["served"] * float(np.mean(h["sojourns_us"] > d))
               for h in hosts)
    return late / max(n, 1.0)


def first_completion(hosts: list, d: float, n_hosts: int, *,
                     requests: int, seed: int) -> np.ndarray:
    """Latencies of ``requests`` requests: each lands on a sampled host
    in proportion to what it served; one still queued at age ``d`` is
    duplicated to a random other replica (a sampled host standing for
    it), lost there with that host's loss share; first completion wins.
    ``d <= 0`` or one host: no hedging."""
    rng = np.random.default_rng(seed)
    served = np.asarray([h["served"] for h in hosts], dtype=np.float64)
    own = rng.choice(len(hosts), size=requests, p=served / served.sum())
    x = np.empty(requests)
    for h, rec in enumerate(hosts):
        sel = own == h
        pool = rec["sojourns_us"]
        x[sel] = pool[rng.integers(pool.size, size=int(sel.sum()))]
    if d <= 0.0 or n_hosts < 2:
        return x
    late = np.flatnonzero(x > d)
    # a random other replica: uniform over the sample without the host
    # itself (the sample stands for the n_hosts - 1 others)
    other = rng.integers(len(hosts) - 1, size=late.size)
    other = other + (other >= own[late])
    y = np.empty(late.size)
    lost = np.zeros(late.size, dtype=bool)
    for h, rec in enumerate(hosts):
        sel = other == h
        k = int(sel.sum())
        pool = rec["sojourns_us"]
        y[sel] = pool[rng.integers(pool.size, size=k)]
        lost[sel] = rng.random(k) < rec["loss_fraction"]
    x[late] = np.where(lost, x[late], np.minimum(x[late], d + y))
    return x


def fleet_point(first: list, d: float, n_hosts: int, *, rerun,
                rate_mpps: float, seed: int,
                requests: int = 1_000_000) -> dict:
    """The reference at one operating point.  ``first`` are the sampled
    hosts at the balancer's rate ``rate_mpps``; with hedging, ``rerun``
    simulates them again at a given rate: the one fixed-point pass, at
    the rate raised by the share S(D) of packets still queued at age D.
    Returns the p99.9 and p99 of first completion, the share of served
    packets duplicated (``dup_share``), and the sample's mean per-host
    statistics."""
    hosts = first
    dup_share = 0.0
    if d > 0.0 and n_hosts > 1:
        hosts = rerun(rate_mpps * (1.0 + still_queued_at(first, d)))
        dup_share = still_queued_at(hosts, d)
    lat = first_completion(hosts, d, n_hosts, requests=requests,
                           seed=int(seed) ^ 0x5A5A)
    mean = lambda k: float(np.mean([h[k] for h in hosts]))
    return {"p999_us": float(np.quantile(lat, 0.999)),
            "p99_us": float(np.quantile(lat, 0.99)),
            "dup_share": dup_share,
            "mean_latency_us": mean("mean_latency_us"),
            "cpu_fraction": mean("cpu_fraction"),
            "wakeups": mean("wakeups"),
            "loss_fraction": mean("loss_fraction")}
