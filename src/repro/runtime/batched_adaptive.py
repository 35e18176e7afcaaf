"""Adaptive macro-slot (event-jump) variant of the batched JAX engine.

The fixed-slot kernel in ``batched.py`` spends compute proportional to
*simulated time*: at the paper's own operating points (T_S ~ 50us,
threads asleep most of the time) ~99% of its 0.5us scan steps advance a
slot in which no thread wakes and no queue drains.  This module applies
Metronome's thesis to the simulator itself — skip the sleep: each scan
step advances a *variable* ``dt`` equal to the distance to the next
"interesting" boundary, all computed in-scan with pure array ops:

  - the earliest pending thread wake (``min`` over sleeping timers);
  - the predicted drain-out of any locked queue
    (``backlog / (mu - lam_q)`` — a thread-release point), and the
    predicted fill-to-capacity of any queue whose net inflow is
    positive (the point where drops start and the backlog path stops
    being linear);
  - the current load-schedule segment's end (the arrival rate is
    constant inside a macro-slot by construction);
  - the next ``window_us`` edge (each macro-slot lands in one window);
  - the next correlated-stall start (the Poisson stall process is
    sampled event-style: the next start time is an Exp(1/rate)
    inter-arrival carried in the scan state, instead of a per-slot
    Bernoulli — same process, exact inter-arrival law);
  - the end of the run.

The per-slot update generalizes to closed-form multi-slot aggregates:

  - *arrivals*: the residual-carried Gaussian fluid scales exactly —
    one draw of variance ``lam*dt`` has the same law as the sum of
    ``dt/slot_us`` per-slot draws, so the process (and the PRNG *rate*
    contract: one fresh draw per macro-slot, overshoot/interference
    charged only on re-arm events) is preserved;
  - *drain*: ``min(backlog + admitted, mu*dt)`` per locked queue —
    closed-form-exact because drain-out times are themselves jump
    boundaries, so no locked queue empties strictly inside a macro-slot
    (admission concurrently frees ring room: ``room = capacity -
    backlog + mu*dt`` on locked queues, the continuous-admission
    semantics the event engine has and fixed slots approximate);
  - *latency area*: the trapezoid ``(B0 + B1)/2 * dt`` — exact for the
    piecewise-linear fluid backlog path between boundaries (the
    fixed-slot engine's end-of-slot rectangle is the dt -> 0 limit);
  - *vacations*: unlocked queues accumulate the full ``dt`` before
    claims are processed at the macro-slot's end boundary, so a
    harvested vacation includes its final partial interval (exact,
    where the fixed-slot engine loses the claim slot's fraction).

Scan length is ``O(#wakes + #busy periods + #schedule segments +
#windows + #stalls)`` instead of ``duration_us / slot_us`` — bounded
ahead of time by ``estimate_adaptive_steps`` (a conservative per-point
wake budget), bucketed to the same geometric ladder as the fixed
engine's slot count, and never above the fixed engine's own scan
length.  Two safety valves make the static bound safe rather than
truncating:

  - ``dt`` is floored at ``slot_us`` (adaptive rarely steps finer than
    fixed) — but the floor yields to the nearest wake or drain-out
    boundary: stepping past a wake coalesces two claims into one and
    turns the loser into a T_L-parked busy try, and stretching a
    sub-slot residual drain holds the queue lock past its true empty
    time; either bias feeds the busy-try/parking loop and suppresses
    the wake rate ~25% at m > 1.  Inside the reserved tail (last
    eighth) of the step budget ``remaining / steps_left`` pacing
    guarantees every point reaches ``duration_us`` exactly (the final
    live step takes ``dt = remaining``, so "sum of dt == duration" is
    exact, not approximate), degrading to coarser-than-boundary jumps
    instead of ending early if the budget was underestimated;
  - every step where that floor overrode the boundary distance by more
    than the wake epsilon is counted in ``forced_steps``
    (``BatchStats.forced_steps``), so budget pressure is measurable
    instead of silent.

Where adaptive is approximate where fixed-slot is not: a forced jump
can cross a wake / segment / stall boundary (the wake still happens —
late, with the timer residual carried into the next sleep, so long-run
wake *rates* stay unbiased, the same bias correction the fixed engine
applies to slot quantization); at most one stall window opens per
macro-slot; a queue whose drain-out boundary lands inside the step
takes its arrivals deterministically for that step (a noisy draw there
is one-sided — a positive residual extends the busy period while a
negative one cannot shorten it, since release happens at the boundary
either way — so suppressing it keeps busy-period lengths, and through
them the wake rate, unbiased); and a queue whose stochastic arrivals
deviate from the fluid prediction mid-jump has its trapezoid latency
area off by the deviation (drops themselves stay exact: admission is
capacity-clamped every step).  Parity is the acceptance bar, pinned in
tests/test_batched_engine.py: adaptive-vs-event within the same
documented bands the fixed engine holds (max(1.5us, 12%) latency /
0.02 + 5% CPU quiet; max(4.5us, 22%) / 0.025 + 6% / loss 0.03 noisy),
and adaptive-vs-fixed within those bands on the same 24 + 16 random
configurations.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from .simcore import SimRunConfig

__all__ = ["estimate_adaptive_steps", "adaptive_kernel"]

# SimRunConfig fields this engine deliberately does NOT read — the same
# declarations batched.py makes, proven complete by the engine-parity
# static check (repro.analysis PARITY001/002, which treats this module
# exactly like batched.py):
#   - grid-supplied: seed / n_queues come per-point from the SweepGrid
#     row (the grid axis IS the sweep surface);
#   - event-engine-only: binned time series stay with simulate_run
#     (validate_batched_config rejects them before this module runs);
#   - sample-path detail: no latency reservoir exists in a fluid engine.
_GRID_SUPPLIED_FIELDS = ("seed", "n_queues")
_EVENT_ENGINE_ONLY_FIELDS = ("timeseries_bin_us",)
_NO_SAMPLE_PATH_FIELDS = ("latency_reservoir",)

_WAKE_EPS_US = 1e-6      # timer-expiry threshold after a macro-jump
_RATE_EPS = 1e-9         # net-rate floor for drain/fill boundaries
_FILL_SLACK_PKTS = 1.0   # stop chasing the fill boundary this close to cap


class _AdaptiveStats(NamedTuple):
    offered: jnp.ndarray
    dropped: jnp.ndarray
    serviced: jnp.ndarray
    wakeups: jnp.ndarray
    busy_tries: jnp.ndarray
    cycles: jnp.ndarray
    awake_us: jnp.ndarray
    lat_area: jnp.ndarray
    vac_sum: jnp.ndarray
    nv_sum: jnp.ndarray
    ts_arms: jnp.ndarray       # T_S-class sleeps armed (empty + release)
    energy_uj: jnp.ndarray     # EnergyModel charge (active + arms)
    n_steps: jnp.ndarray
    forced_steps: jnp.ndarray


def estimate_adaptive_steps(grid, cfg: SimRunConfig, slot_us: float,
                            n_windows: int) -> int:
    """Conservative scan-length budget for the adaptive kernel.

    Boundaries per point: each thread wake is one step, and each busy
    period ends in exactly one drain-out step (the drain-boundary step
    takes its arrivals deterministically, so the prediction lands on
    empty with no corrective jumps), so ``3x`` the wake budget
    ``m * duration / primary_cycle`` covers wake + drain + fill + claim
    slack; schedule segments, window edges and the expected stall-start
    count add linearly.  Clamped at the fixed engine's own slot count —
    adaptive never scans more than fixed would have."""
    sm = cfg.sleep_model
    ts_us = np.maximum(np.asarray(grid.t_s_us, dtype=np.float64),
                       2.0 * slot_us)
    cycle = ts_us * (1.0 + sm.slope) + sm.base_us
    wake_budget = (np.asarray(grid.m, dtype=np.float64)
                   * cfg.duration_us / cycle)
    n_bound = 0
    scheds = list(grid.schedules) if grid.schedules else []
    if cfg.schedule is not None:
        scheds.append(cfg.schedule)
    for s in scheds:
        if s is not None:
            n_bound = max(n_bound, len(s.jump_boundaries(cfg.duration_us)))
    extras = (n_bound + n_windows
              + cfg.stall_rate_per_us * cfg.duration_us + 64)
    n_slots = max(int(math.ceil(cfg.duration_us / slot_us)), 1)
    return int(min(math.ceil(3.0 * float(wake_budget.max()) + extras),
                   n_slots))


def _build_adaptive_sweep(max_steps: int, slot_us: float, m_max: int,
                          q_max: int, mu: float, capacity: float,
                          wake_cost_us: float, sleep_params: tuple,
                          interference_params: tuple,
                          energy_params: tuple, n_seg: int = 0,
                          n_windows: int = 0, window_us: float = 0.0):
    """Build + jit the vmapped event-jump kernel for one static shape.

    Same static surface as ``batched._build_sweep`` (the two engines
    compile from the same grid preparation), except the scan length is
    the adaptive step *budget* rather than the slot count, and
    ``duration`` is a traced per-point input: steps after a point
    reaches its duration are dt=0 no-ops (carry held via a live mask),
    which is also what lets one step budget be shared across a vmapped
    batch and across the bucketing ladder."""
    from .batched import energy_arm_cost
    base_us, slope, sigma_us, tail_prob, tail_mean_us = sleep_params
    intf_prob, intf_mean_us, stall_rate, stall_mean_us = interference_params
    active_power_w, _dvfs_scale, e_states = energy_params
    t_idx = jnp.arange(m_max)
    q_idx = jnp.arange(q_max)
    floor_us = slot_us

    def one_point(t_s, t_l, m, nq, lam, seed_lo, seed_hi, duration,
                  sched_edges, sched_scales):
        tmask = t_idx < m
        qmask = q_idx < nq
        # per-arm C-state charges are point constants (the target, not
        # the realized vacancy, selects the state — see EnergyModel)
        e_arm_s = energy_arm_cost(t_s, e_states)
        e_arm_l = energy_arm_cost(t_l, e_states)

        # both 32-bit halves of the 64-bit seed are folded in, so seeds
        # differing only in their high bits stay independent
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), seed_lo), seed_hi)
        key, k0 = jax.random.split(key)
        # active launch (event-engine convention): first wakes land
        # uniformly inside one primary timeout
        sleep0 = jax.random.uniform(k0, (m_max,)) * t_s
        sleep0 = jnp.where(tmask, jnp.maximum(sleep0, floor_us), jnp.inf)
        if stall_rate > 0.0:
            key, kst = jax.random.split(key)
            next_stall0 = jax.random.exponential(kst, ()) / stall_rate
        else:
            next_stall0 = jnp.float32(jnp.inf)

        def step(carry, t):
            prev = carry
            (sleep_rem, attached, backlog, vac_timer, arr_res, stall_end,
             next_stall, rem_t, A, win_acc) = carry
            now = duration - rem_t
            live = rem_t > 0.0
            with jax.named_scope("rng"):
                kt_step = jax.random.fold_in(key, t)
                if tail_prob > 0.0:
                    kt_step, kp, ku = jax.random.split(kt_step, 3)
                if intf_prob > 0.0:
                    kt_step, kip, kie = jax.random.split(kt_step, 3)
                if stall_rate > 0.0:
                    kt_step, kse, ksg, ksu = jax.random.split(kt_step, 4)
                # one fused normal draw covers arrivals + sleep noise
                zs = jax.random.normal(kt_step, (q_max + m_max,))

            # ---- the jump: distance to the next interesting boundary
            with jax.named_scope("jump"):
                sleeping = tmask & (attached < 0)
                occ = (jax.nn.one_hot(attached, q_max).sum(axis=0) > 0)
                wake_dt = jnp.min(jnp.where(
                    sleeping, jnp.maximum(sleep_rem, 0.0), jnp.inf))
                if n_seg > 0:
                    si = jnp.clip(
                        jnp.searchsorted(sched_edges, now, side="right") - 1,
                        0, n_seg - 1)
                    scale_t = sched_scales[si]
                    nxt_si = jnp.clip(si + 1, 0, n_seg - 1)
                    seg_dt = jnp.where(si + 1 < n_seg,
                                       sched_edges[nxt_si] - now, jnp.inf)
                else:
                    scale_t = jnp.float32(1.0)
                    seg_dt = jnp.float32(jnp.inf)
                lam_q = jnp.where(qmask, lam * scale_t / nq, 0.0)
                # locked queues: expected time to drain out (thread release)
                net_out = jnp.where(occ, mu - lam_q, 0.0)
                drain_q = jnp.where(
                    occ & (net_out > _RATE_EPS),
                    jnp.maximum(backlog, 0.0)
                    / jnp.maximum(net_out, _RATE_EPS), jnp.inf)
                drain_dt = jnp.min(drain_q)
                # filling queues: expected time to reach capacity (drops
                # start; within one packet of capacity the path is flat at
                # cap and no further fill chasing is needed)
                net_in = lam_q - jnp.where(occ, mu, 0.0)
                fill_dt = jnp.min(jnp.where(
                    qmask & (net_in > _RATE_EPS)
                    & (backlog < capacity - _FILL_SLACK_PKTS),
                    (capacity - backlog) / jnp.maximum(net_in, _RATE_EPS),
                    jnp.inf))
                if n_windows > 0:
                    win_dt = ((jnp.floor(now / window_us) + 1.0) * window_us
                              - now)
                else:
                    win_dt = jnp.float32(jnp.inf)
                stall_dt = next_stall - now
                dt_b = jnp.minimum(
                    jnp.minimum(jnp.minimum(wake_dt, drain_dt),
                                jnp.minimum(fill_dt, seg_dt)),
                    jnp.minimum(jnp.minimum(win_dt, stall_dt), rem_t))
                # completion guard: only inside the reserved tail (the last
                # eighth of the budget) is the remaining-average pace
                # enforced — with an adequate budget rem hits 0 long before
                # the tail and no jump is ever distorted; with a short one
                # the tail sweeps to the end at the average pace instead of
                # truncating.  (Enforcing rem/steps_left from step 0 would
                # coarsen every boundary finer than the whole-run average.)
                steps_left = jnp.float32(max_steps) - t.astype(jnp.float32)
                in_tail = steps_left <= jnp.float32(max(max_steps // 8, 2))
                pace = jnp.where(in_tail, rem_t / steps_left, 0.0)
                # the floor never steps PAST a wake or a drain-out: stepping
                # past a wake coalesces two nearby wakes into one boundary
                # and turns the second thread's claim into a busy try (it
                # sees the first claimant's lock); stretching a sub-floor
                # residual drain holds the queue lock past its true empty
                # time.  Either way P(wake lands in a busy period) inflates
                # and the T_L parking feedback amplifies it ~4x at m > 1.
                # Sub-floor steps stay budget-safe: their count is bounded
                # by wakes + drain-outs, both inside the step estimate
                floor_eff = jnp.minimum(
                    floor_us,
                    jnp.maximum(jnp.minimum(wake_dt, drain_dt),
                                _WAKE_EPS_US))
                dt = jnp.minimum(
                    jnp.maximum(dt_b, jnp.maximum(floor_eff, pace)), rem_t)
                # a floor_us clamp over a sub-slot boundary is expected
                # (adaptive never steps finer than fixed); "forced" counts
                # only genuine budget pressure from the tail pace
                forced = (dt > jnp.maximum(dt_b, floor_us) + _WAKE_EPS_US) \
                    & live
                t_new = now + dt

            # 1. arrivals over dt: residual-carried Gaussian fluid ~
            # Poisson — one draw of variance lam*dt per queue; admission
            # is concurrent with drain on locked queues (ring frees as
            # it is polled), so room grows by mu*dt there.  A queue
            # whose drain boundary lands inside this step takes its
            # arrivals deterministically: a noisy draw there is one-
            # sided (a positive residual extends the busy period, a
            # negative one cannot shorten it — release happens at the
            # boundary either way), which stretches busy periods and
            # biases the wake rate down through the parking feedback
            with jax.named_scope("arrivals"):
                drain_now = occ & (drain_q <= dt + _WAKE_EPS_US)
                mu_a = lam_q * dt
                z_q = jnp.where(drain_now, 0.0, zs[:q_max])
                raw = arr_res + mu_a + jnp.sqrt(mu_a) * z_q
                a = jnp.maximum(raw, 0.0)
                arr_res = jnp.minimum(raw, 0.0)      # deficit carried forward
                room = jnp.maximum(capacity - backlog, 0.0) \
                    + jnp.where(occ, mu * dt, 0.0)
                adm = jnp.minimum(a, room)
                offered = a.sum()
                dropped = (a - adm).sum()

            # 2. drain: no locked queue empties strictly inside the
            # macro-slot (drain-out is a boundary), so min(B0 + adm,
            # mu*dt) is the exact aggregate of the per-slot updates
            with jax.named_scope("drain"):
                serve = jnp.where(occ,
                                  jnp.minimum(backlog + adm, mu * dt), 0.0)
                b_new = jnp.minimum(jnp.maximum(backlog + adm - serve, 0.0),
                                    capacity)
                served = serve.sum()

            # 3. Little integral: trapezoid over the linear fluid path;
            # vacations accrue on old occupancy BEFORE end-boundary
            # claims, so a harvested vacation includes its final
            # partial interval and a queue released at this boundary
            # starts its vacation next step — both event-exact
            with jax.named_scope("accumulate"):
                lat_area = 0.5 * (backlog.sum() + b_new.sum()) * dt
                vac_timer = vac_timer + jnp.where(qmask & ~occ, dt, 0.0)
                backlog = b_new

            # 4. stall process at the boundary: the carried Exp
            # inter-arrival fires when the jump reaches it; overlapping
            # windows extend (max), the event engine's lazy merge.  At
            # most one window opens per macro-slot (a forced jump past
            # two starts opens the second one step late).
            with jax.named_scope("stall"):
                if stall_rate > 0.0:
                    fire = (next_stall <= t_new) & live
                    w_end = next_stall \
                        + stall_mean_us * jax.random.exponential(kse, ())
                    stall_end = jnp.where(fire,
                                          jnp.maximum(stall_end, w_end),
                                          stall_end)
                    gap = jax.random.exponential(ksg, ()) / stall_rate
                    next_stall = jnp.where(fire, next_stall + gap, next_stall)

            # sleep overshoot + per-wake OS interference draws — one per
            # thread per macro-slot, charged only on re-arm events, the
            # same per-sleep rate contract as the fixed engine
            with jax.named_scope("overshoot"):
                over = jnp.full((m_max,), base_us)
                if sigma_us > 0.0:
                    over = over + sigma_us * jnp.abs(zs[q_max:])
                if tail_prob > 0.0:
                    hit = jax.random.uniform(kp, (m_max,)) < tail_prob
                    over = over + hit * tail_mean_us * jax.random.exponential(
                        ku, (m_max,))
                if intf_prob > 0.0:
                    ihit = jax.random.uniform(kip, (m_max,)) < intf_prob
                    over = over + ihit * intf_mean_us * jax.random.exponential(
                        kie, (m_max,))
                slp_s = t_s * (1.0 + slope) + over
                slp_l = t_l * (1.0 + slope) + over

            # 5. wakes at the boundary: timers land exactly on it when
            # the wake governed the jump; the (negative) residual carry
            # keeps wake rates unbiased when a forced jump overshot
            with jax.named_scope("wake_claim"):
                sleep_rem = jnp.where(sleeping, sleep_rem - dt, sleep_rem)
                woken = sleeping & (sleep_rem <= _WAKE_EPS_US) & live
                if stall_rate > 0.0:
                    # timers expiring inside an open stall window defer to
                    # its end (+U(0,1)us re-arm jitter), not counted as wakes
                    push = woken & (t_new < stall_end)
                    woken = woken & ~push
                    sleep_rem = jnp.where(
                        push,
                        stall_end - t_new + jax.random.uniform(ksu, (m_max,)),
                        sleep_rem)
                n_wake = woken.sum().astype(jnp.float32)

            # 6. queues drained out by the boundary release their
            # thread (fresh T_S sleep, no residual) BEFORE boundary
            # wakes classify: drain-out precedes the boundary in true
            # time, so a thread waking at the boundary must see the
            # queue free — release-after-claim would misclassify it as
            # a busy try and park it on T_L
            with jax.named_scope("release"):
                q_done = occ & (backlog <= 1e-6)
                # one-hot over the queue axis: a per-point gather here
                # costs most of a scan step on a TPU; a detached
                # thread (-1) matches no queue
                t_done = ((attached[:, None] == q_idx) & q_done).any(axis=1)
                sleep_rem = jnp.where(t_done, slp_s, sleep_rem)
                attached = jnp.where(t_done, -1, attached)
                occ = occ & ~q_done

            # 7. claim loop — identical to the fixed-slot kernel's
            with jax.named_scope("wake_claim"):
                busy_tries = jnp.float32(0.0)
                cycles = jnp.float32(0.0)
                vac_sum = jnp.float32(0.0)
                nv_sum = jnp.float32(0.0)
                ts_arm = t_done.sum().astype(jnp.float32)
                for i in range(m_max):            # static unroll, m_max small
                    w = woken[i]
                    free_q = qmask & ~occ
                    claimable = free_q & (backlog >= 1.0)
                    qi = jnp.argmax(jnp.where(claimable, backlog, -1.0))
                    do_attach = w & claimable.any()
                    empty_claim = w & ~claimable.any() & free_q.any()
                    eqi = jnp.argmax(free_q)      # first free (empty) queue
                    blocked = w & ~free_q.any()

                    claim_hot = do_attach & (q_idx == qi)
                    claim_any = claim_hot | (empty_claim & (q_idx == eqi))
                    vac_sum = vac_sum + (vac_timer * claim_any).sum()
                    # one nonzero term at most, so the sum is exact
                    nv_sum = nv_sum + jnp.where(claim_hot, backlog, 0.0).sum()
                    vac_timer = jnp.where(claim_any, 0.0, vac_timer)
                    cycles = cycles + (do_attach | empty_claim)
                    busy_tries = busy_tries + blocked
                    ts_arm = ts_arm + empty_claim
                    attached = attached.at[i].set(
                        jnp.where(do_attach, qi, attached[i]))
                    occ = occ | claim_hot
                    # re-sleep adds onto the expired-timer residual
                    sleep_rem = sleep_rem.at[i].add(
                        jnp.where(empty_claim, slp_s[i],
                                  jnp.where(blocked, slp_l[i], 0.0)))

            with jax.named_scope("accumulate"):
                rem_t = rem_t - dt
                # energy: active power over this step's awake time plus the
                # per-arm C-state charges (blocked wakes re-arm T_L)
                awake_step = n_wake * wake_cost_us + served / mu
                energy_step = (active_power_w * awake_step
                               + ts_arm * e_arm_s + busy_tries * e_arm_l)
                A = _AdaptiveStats(
                    offered=A.offered + offered,
                    dropped=A.dropped + dropped,
                    serviced=A.serviced + served,
                    wakeups=A.wakeups + n_wake,
                    busy_tries=A.busy_tries + busy_tries,
                    cycles=A.cycles + cycles,
                    awake_us=A.awake_us + awake_step,
                    lat_area=A.lat_area + lat_area,
                    vac_sum=A.vac_sum + vac_sum,
                    nv_sum=A.nv_sum + nv_sum,
                    ts_arms=A.ts_arms + ts_arm,
                    energy_uj=A.energy_uj + energy_step,
                    n_steps=A.n_steps + 1.0,
                    forced_steps=A.forced_steps + forced.astype(jnp.float32),
                )
                if n_windows > 0:
                    # window edges are jump boundaries, so a macro-slot
                    # never spans windows (except forced jumps, attributed
                    # to the window containing their start)
                    wi = jnp.clip((now / window_us).astype(jnp.int32),
                                  0, n_windows - 1)
                    win_acc = win_acc.at[wi].add(jnp.stack([
                        offered, served, lat_area, awake_step, energy_step]))
            with jax.named_scope("gate"):
                nxt = (sleep_rem, attached, backlog, vac_timer, arr_res,
                       stall_end, next_stall, rem_t, A, win_acc)
                # finished points hold their carry: every later step is a
                # no-op, so one compiled budget serves the whole batch
                gated = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(live, new, old), nxt, prev)
            return gated, None

        z0 = jnp.float32(0.0)
        init = (sleep0,
                jnp.full((m_max,), -1, jnp.int32),
                jnp.zeros(q_max, jnp.float32),
                jnp.zeros(q_max, jnp.float32),
                jnp.zeros(q_max, jnp.float32),
                jnp.float32(-1.0),          # stall_end: no window open
                next_stall0,
                jnp.asarray(duration, jnp.float32),
                _AdaptiveStats(z0, z0, z0, z0, z0, z0, z0, z0, z0, z0,
                               z0, z0, z0, z0),
                jnp.zeros((max(n_windows, 1), 5), jnp.float32))
        (_, _, backlog_f, _, _, _, _, rem_f, A, win_acc), _ = \
            jax.lax.scan(step, init,
                         jnp.arange(max_steps, dtype=jnp.int32))
        # duration - rem_f is the *exact* simulated time: rem is carried
        # by subtraction and the final live step takes dt = rem, so a
        # completed point has rem_f == 0.0 exactly (not approximately)
        return A, win_acc, backlog_f.sum(), duration - rem_f

    def adaptive_sweep(*points):
        return jax.vmap(one_point)(*points)

    # the function's name is the compiled module's: jit_adaptive_sweep
    return jax.jit(adaptive_sweep)


# created on first use so importing this module has no registry side
# effects; the instance lives in batched.CompileCache._registry like
# every other kernel cache
_compiled_adaptive_sweep = None


def adaptive_kernel(grid, cfg: SimRunConfig, slot_us: float, n_seg: int,
                    n_windows: int):
    """The jitted event-jump kernel for ``grid``'s static shape, and its
    scan length (the bucketed step budget).  It takes the fixed
    kernel's inputs and returns ``(stats, windows, final_backlog,
    sim_time_us)``; ``batched.simulate_batch`` stages, runs and packages
    it like the fixed kernel."""
    # batched.py imports this module lazily inside simulate_batch and
    # this import runs only at call time, so the two modules stay
    # import-order independent
    from .batched import CompileCache, bucket_steps

    global _compiled_adaptive_sweep
    if _compiled_adaptive_sweep is None:
        _compiled_adaptive_sweep = CompileCache(
            _build_adaptive_sweep, maxsize=64,
            name="batched_adaptive._compiled_adaptive_sweep")

    max_steps = bucket_steps(
        estimate_adaptive_steps(grid, cfg, slot_us, n_windows))
    sm = cfg.sleep_model
    fn = _compiled_adaptive_sweep(
        max_steps, float(slot_us), int(grid.m.max()),
        int(grid.n_queues.max()),
        float(cfg.service_rate_mpps), float(cfg.queue_capacity),
        float(cfg.wake_cost_us),
        (float(sm.base_us), float(sm.slope), float(sm.sigma_us),
         float(sm.tail_prob), float(sm.tail_mean_us)),
        (float(cfg.interference_prob), float(cfg.interference_mean_us),
         float(cfg.stall_rate_per_us), float(cfg.stall_mean_us)),
        cfg.energy_model.params(),
        n_seg, n_windows, float(cfg.window_us))
    return fn, max_steps
