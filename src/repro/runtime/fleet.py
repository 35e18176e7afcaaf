"""Fleet-scale batched simulation: N Metronome hosts in one jit call.

The batched engine (``repro.runtime.batched``) vmaps the fixed-slot
kernel over operating points; this module adds the *host* axis on top:
a ``FleetGrid`` stacks ``n_hosts`` replica hosts per sweep point and
runs the single-host slot dynamics under a second ``vmap``
(point x host), with three fleet-level stages around the per-host body:

  1. **Load balancer.**  Each slot, the shared (schedule-modulated)
     arrival stream splits across hosts by ``FleetConfig.lb``:
     ``uniform`` (1/H), ``weighted`` (static shares), or
     ``least-loaded`` — a softmin over a *stale* backlog snapshot that
     refreshes only every ``lb_stale_us`` (the finite-polling-rate
     balancer whose stale signal herds load onto a replica that *was*
     idle).
  2. **Topology.**  The first ``round(far_fraction*H)`` hosts sit in a
     far rack: every admitted packet pays its rack's constant cost, and
     far packets additionally queue on a shared bottleneck link modeled
     M/M/1-style (wait ``1/(link_rate - far_rate)``, clamped near
     saturation).  Network delay accumulates in a separate per-host
     ``topo_area`` — it is real end-to-end latency but NOT host queue
     depth, so host-level parity vs the single-host engines is
     untouched.
  3. **Hedged requests.**  A per-point hedge deadline D duplicates each
     request still queued at age D, and only those, once: a duplicate
     is never hedged again.  The partner is a random other replica, so
     in the fluid kernel host ``h`` receives ``(sum(dup) - dup_h) /
     (H - 1)`` each slot, spread over its queues.  A duplicate that
     finds the partner's ring full is dropped and counted in
     ``hedge_dup_dropped``; one admitted is served in full (there is no
     cancellation, so fleet CPU counts the duplicates' work).  Issued
     duplicates are ``hedge_dup``, not ``offered``.

**Sojourn accounting.**  The kernel measures each host's packet
sojourns.  Per (host, queue) it keeps a ring of ``RING`` samples of the
cumulative admissions (all of them, and the balancer's originals apart),
one at the end of every cohort of ``cohort_slots`` slots (``COHORT_US``
of simulated time, a horizon of ``RING * COHORT_US``).  At each cohort's
end the ring gives the admission time of the rank just served, by a
compare-and-count over the ring with linear interpolation inside a
cohort (FIFO: a packet leaves at the rank it arrived at), and the
cohort's served mass goes into a per-host log-spaced histogram of
sojourns (``sojourn_edges``), whose last bin counts what the horizon
cannot see.  The same ring gives, each slot, the mass of packets that
turn D old while still queued: the hedge trigger.  ``FleetStats.
quantile`` combines the measured per-host survivals by first completion
wins; nothing in it is assumed.  With D <= 0 every other counter is
bit-identical to a kernel without hedging.

**Per-host parity contract.**  Host ``h`` of a fleet row with seed
``s`` draws exactly the PRNG stream of a single-host batched run seeded
``s + h`` (the per-host key is ``fold_in(fold_in(PRNGKey(0), lo + h),
hi)`` on the split 64-bit seed; the carry across the low word is
ignored, so keep fleet seeds below ``2**32 - n_hosts``).  Under uniform
round-robin with topology and hedging off, host ``h`` at fleet rate
``lam`` is the single-host kernel at rate ``lam/H`` — which is what the
fleet-vs-merged-single-host parity test pins against the *event*
engine within the existing quiet bands.

**Device sharding.**  ``simulate_fleet(..., shard=True)`` splits the
point axis across local devices via ``jax.shard_map`` (each
device vmaps its slice of points over all hosts); ``shard=None`` auto-
enables when more than one device is visible, and ``shard=False``
forces the pure-vmap path.  CI exercises the sharded path with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  Both paths go
through one ``CompileCache``d jit per static shape — a 1000-host x
8-point sweep is ONE jit call, no Python loop over hosts.

Cluster rollups go through the existing ``RunStats`` machinery:
``FleetStats.host_run_stats(i)`` yields one ``RunStats`` per host and
``to_run_stats(i)`` n-way-merges them (``RunStats.merge_all``).

Spans (``repro.runtime.tracing``): ``fleet.sweep`` around
``simulate_fleet`` (counts ``points``, ``hosts``, ``scan_steps``,
``hedge_dup``, ``hedge_dup_dropped``, ``hist_overflow``, ``built``),
with children ``fleet.scan`` (the jitted call until its outputs are
ready) and ``fleet.fetch`` (copies and ``FleetStats``); ``fleet.tail``
around ``FleetStats.quantiles`` and ``fleet.rollup`` around
``FleetStats.rollups``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from .batched import (
    CompileCache,
    SweepGrid,
    _schedule_rows,
    bucket_steps,
    energy_arm_cost,
    validate_batched_config,
)
from . import tracing
from .simcore import _LINK_UTIL_CLAMP, FleetConfig, SimRunConfig
from .stats import Reservoir, RunStats

__all__ = ["FleetGrid", "FleetStats", "simulate_fleet", "sojourn_edges"]

_LB_CODE = {"uniform": 0, "weighted": 1, "least-loaded": 2}

# sojourn accounting: cohorts of about COHORT_US, a ring of RING cohort
# samples (the horizon), a histogram of HIST_BINS log-spaced bins from
# HIST_LO_US to the horizon, with an underflow and an overflow bin
COHORT_US = 4.0
RING = 384
HIST_BINS = 128
HIST_LO_US = 0.5


def cohort_slots(slot_us: float) -> int:
    """Slots per cohort of the sojourn ring at this slot width."""
    return max(int(round(COHORT_US / slot_us)), 1)


def sojourn_edges(slot_us: float) -> np.ndarray:
    """The ``HIST_BINS + 1`` inner edges (us) of the sojourn histogram:
    log-spaced from ``HIST_LO_US`` to the ring's horizon.  Bin 0 is
    [0, edges[0]) and the last bin [edges[-1], inf), the overflow."""
    horizon = RING * cohort_slots(slot_us) * slot_us
    return np.geomspace(HIST_LO_US, horizon, HIST_BINS + 1)


@dataclass(frozen=True)
class FleetGrid:
    """A flat batch of fleet operating points.

    ``grid`` holds the per-host knobs (T_S, T_L, M, n_queues, seed) and
    the FLEET-AGGREGATE offered rate per point (``rate_mpps`` is what
    the balancer receives; each host sees its share).  ``fleet`` is the
    shared environment (host count, LB policy, topology) and
    ``hedge_deadline_us`` is a per-point operating knob — it is a
    *traced* kernel input, so one compilation sweeps hedge deadlines
    next to (T_S, T_L, M) without re-tracing.
    """

    grid: SweepGrid
    fleet: FleetConfig
    hedge_deadline_us: np.ndarray     # (len(grid),); <= 0 disables
    shape: tuple = ()

    @classmethod
    def product(cls, *, fleet: FleetConfig, t_s_us, t_l_us, rate_mpps,
                m=(3,), n_queues=(1,), seeds=(0,),
                hedge_deadline_us=(0.0,), schedules=None) -> "FleetGrid":
        """Dense cartesian grid with a trailing hedge-deadline axis on
        top of ``SweepGrid.product``'s axes (``rate_mpps`` entries are
        fleet aggregates)."""
        fleet.validate()
        base = SweepGrid.product(t_s_us=t_s_us, t_l_us=t_l_us,
                                 rate_mpps=rate_mpps, m=m,
                                 n_queues=n_queues, seeds=seeds,
                                 schedules=schedules)
        hedge = np.atleast_1d(np.asarray(hedge_deadline_us,
                                         dtype=np.float64))
        nh = hedge.size
        shape = base.shape + (nh,)
        grid = SweepGrid(
            t_s_us=np.repeat(base.t_s_us, nh),
            t_l_us=np.repeat(base.t_l_us, nh),
            m=np.repeat(base.m, nh),
            n_queues=np.repeat(base.n_queues, nh),
            rate_mpps=np.repeat(base.rate_mpps, nh),
            seed=np.repeat(base.seed, nh),
            shape=shape,
            schedules=(tuple(s for s in base.schedules
                             for _ in range(nh))
                       if base.schedules else ()))
        return cls(grid=grid, fleet=fleet,
                   hedge_deadline_us=np.tile(hedge, len(base)),
                   shape=shape)

    @classmethod
    def of_points(cls, points, *, fleet: FleetConfig) -> "FleetGrid":
        """Arbitrary point list; each dict takes ``SweepGrid`` keys plus
        an optional ``hedge_deadline_us`` (default 0 = no hedging)."""
        fleet.validate()
        pts = list(points)
        base = SweepGrid.of_points(pts)
        hedge = np.asarray([p.get("hedge_deadline_us", 0.0) for p in pts],
                           dtype=np.float64)
        return cls(grid=base, fleet=fleet, hedge_deadline_us=hedge,
                   shape=(len(pts),))

    def __len__(self) -> int:
        return len(self.grid)

    def point(self, i: int) -> dict:
        d = self.grid.point(i)
        d["hedge_deadline_us"] = float(self.hedge_deadline_us[i])
        d["n_hosts"] = self.fleet.n_hosts
        d["lb"] = self.fleet.lb
        return d


class _FleetSlotStats(NamedTuple):
    offered: jnp.ndarray       # all fields (n_hosts,) per point
    dropped: jnp.ndarray
    serviced: jnp.ndarray
    wakeups: jnp.ndarray
    busy_tries: jnp.ndarray
    cycles: jnp.ndarray
    awake_us: jnp.ndarray
    lat_area: jnp.ndarray      # host queue-depth integral (packet*us)
    vac_sum: jnp.ndarray
    nv_sum: jnp.ndarray
    ts_arms: jnp.ndarray       # T_S-class sleeps armed (empty + release)
    energy_uj: jnp.ndarray     # EnergyModel charge (active + arms)
    topo_area: jnp.ndarray     # network delay integral (packet*us)
    hedge_dup: jnp.ndarray     # duplicate requests issued by this host
    hedge_dup_in: jnp.ndarray  # duplicates admitted into this host's ring
    hedge_dup_dropped: jnp.ndarray  # duplicates its full ring dropped


class _Cohort(NamedTuple):
    """Per (host, queue) counts since the last cohort's close (admissions
    relative to the ranks served by then) and the open cohort's served
    mass and first/last serving slot ends (us)."""

    cum_all: jnp.ndarray       # admissions, duplicates included
    cum_orig: jnp.ndarray      # admissions from the balancer
    cum_served: jnp.ndarray
    served: jnp.ndarray
    first_us: jnp.ndarray
    last_us: jnp.ndarray


def _build_fleet_sweep(n_slots: int, slot_us: float, m_max: int,
                       q_max: int, n_hosts: int, mu: float,
                       capacity: float, wake_cost_us: float,
                       sleep_params: tuple, interference_params: tuple,
                       energy_params: tuple,
                       n_seg: int, lb_code: int, lb_weights: tuple,
                       lb_softness_pkts: float, stale_every_slots: int,
                       far_count: int, near_cost_us: float,
                       far_cost_us: float, link_rate_mpps: float,
                       n_shards: int):
    """Build + jit the (point x host) fleet kernel for one static shape.

    The per-host slot body is the single-host kernel's, line for line
    (same PRNG key discipline per host — the parity contract), wrapped
    in an inner host vmap; the load-balancer split, the topology delay,
    and the hedge-duplicate exchange are the only cross-host stages.
    ``n_shards > 1`` wraps the point-axis vmap in ``shard_map`` over the
    first ``n_shards`` local devices.

    The kernel scans ``n_slots`` constant ``slot_us`` slots
    (``duration`` is traced and steps past it are carry-held no-ops, so
    one bucketed scan length serves nearby durations bit-identically),
    in cohorts of ``cohort_slots(slot_us)`` slots: each scan step runs
    that many slots one after another, then closes the cohort (ring
    sample, served-mass histogram); see the module docstring.
    """
    base_us, slope, sigma_us, tail_prob, tail_mean_us = sleep_params
    intf_prob, intf_mean_us, stall_rate, stall_mean_us = interference_params
    active_power_w, _dvfs_scale, e_states = energy_params
    stall_p = 1.0 - math.exp(-stall_rate * slot_us) if stall_rate else 0.0
    dt = slot_us
    t_idx = jnp.arange(m_max)
    q_idx = jnp.arange(q_max)
    h_idx = jnp.arange(n_hosts)
    far_mask = (h_idx < far_count)
    rack_cost_us = jnp.where(far_mask, far_cost_us, near_cost_us)
    topo_on = (near_cost_us > 0.0 or far_cost_us > 0.0
               or link_rate_mpps > 0.0)
    w_static = (jnp.asarray(lb_weights, jnp.float32) if lb_code == 1
                else jnp.full((n_hosts,), 1.0 / n_hosts, jnp.float32))
    g = cohort_slots(slot_us)
    cohort_us = g * dt
    k_idx = jnp.arange(RING)
    edges = jnp.asarray(sojourn_edges(slot_us), jnp.float32)

    def one_fleet(t_s, t_l, m, nq, lam, seed_lo, seed_hi, hedge_d,
                  duration, sched_edges, sched_scales):
        tmask = t_idx < m
        qmask = q_idx < nq
        # per-arm C-state charges are point constants shared by every
        # host (the target, not the realized vacancy, picks the state)
        e_arm_s = energy_arm_cost(t_s, e_states)
        e_arm_l = energy_arm_cost(t_l, e_states)

        # per-host keys: host h draws the stream of a single-host run
        # seeded (seed + h) — the fleet<->single-host parity contract
        host_lo = seed_lo + h_idx.astype(jnp.uint32)

        def init_host(lo):
            k = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(0), lo), seed_hi)
            k, k0 = jax.random.split(k)
            s0 = jax.random.uniform(k0, (m_max,)) * t_s
            return k, s0

        keys, sleep0_h = jax.vmap(init_host)(host_lo)
        sleep0_h = jnp.where(tmask[None, :],
                             jnp.maximum(sleep0_h, dt), jnp.inf)

        def host_step(key_h, t, scale_t, lam_h, sleep_rem, attached,
                      backlog, vac_timer, arr_res, stall_end):
            """One host, one slot — the single-host kernel body."""
            now = t.astype(jnp.float32) * dt
            lam_q = jnp.where(qmask, lam_h / nq, 0.0)
            with jax.named_scope("rng"):
                kt_step = jax.random.fold_in(key_h, t)
                if tail_prob > 0.0:
                    kt_step, kp, ku = jax.random.split(kt_step, 3)
                if intf_prob > 0.0:
                    kt_step, kip, kie = jax.random.split(kt_step, 3)
                if stall_p > 0.0:
                    kt_step, ksp, kse, ksu = jax.random.split(kt_step, 4)
                zs = jax.random.normal(kt_step, (q_max + m_max,))

            with jax.named_scope("stall"):
                if stall_p > 0.0:
                    hit_s = jax.random.uniform(ksp, ()) < stall_p
                    win = now + stall_mean_us * jax.random.exponential(kse, ())
                    stall_end = jnp.where(hit_s,
                                          jnp.maximum(stall_end, win),
                                          stall_end)

            with jax.named_scope("arrivals"):
                if n_seg > 0:
                    mu_a = lam_q * scale_t * dt
                else:
                    mu_a = lam_q * dt
                raw = arr_res + mu_a + jnp.sqrt(mu_a) * zs[:q_max]
                a = jnp.maximum(raw, 0.0)
                arr_res = jnp.minimum(raw, 0.0)
                room = jnp.maximum(capacity - backlog, 0.0)
                adm = jnp.minimum(a, room)
                backlog = backlog + adm
                offered = a.sum()
                dropped = (a - adm).sum()

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

            with jax.named_scope("wake_claim"):
                sleeping = tmask & (attached < 0)
                sleep_rem = jnp.where(sleeping, sleep_rem - dt, sleep_rem)
                woken = sleeping & (sleep_rem <= 0.0)
                if stall_p > 0.0:
                    push = woken & (now < stall_end)
                    woken = woken & ~push
                    sleep_rem = jnp.where(
                        push,
                        stall_end - now + jax.random.uniform(ksu, (m_max,)),
                        sleep_rem)
                n_wake = woken.sum().astype(jnp.float32)

                occ = (jax.nn.one_hot(attached, q_max).sum(axis=0) > 0)
                busy_tries = jnp.float32(0.0)
                cycles = jnp.float32(0.0)
                vac_sum = jnp.float32(0.0)
                nv_sum = jnp.float32(0.0)
                ts_arm = jnp.float32(0.0)
                for i in range(m_max):          # static unroll, m_max small
                    w = woken[i]
                    free_q = qmask & ~occ
                    claimable = free_q & (backlog >= 1.0)
                    qi = jnp.argmax(jnp.where(claimable, backlog, -1.0))
                    do_attach = w & claimable.any()
                    empty_claim = w & ~claimable.any() & free_q.any()
                    eqi = jnp.argmax(free_q)
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
                    sleep_rem = sleep_rem.at[i].add(
                        jnp.where(empty_claim, slp_s[i],
                                  jnp.where(blocked, slp_l[i], 0.0)))

            with jax.named_scope("drain"):
                serve = jnp.where(occ, jnp.minimum(backlog, mu * dt), 0.0)
                backlog = backlog - serve
                served = serve.sum()

            with jax.named_scope("release"):
                q_done = occ & (backlog <= 1e-6)
                # one-hot over the queue axis: a per-point gather here
                # costs most of a scan step on a TPU; a detached
                # thread (-1) matches no queue
                t_done = ((attached[:, None] == q_idx) & q_done).any(axis=1)
                ts_arm = ts_arm + t_done.sum()
                sleep_rem = jnp.where(t_done, slp_s, sleep_rem)
                attached = jnp.where(t_done, -1, attached)
                occ = occ & ~q_done

            with jax.named_scope("accumulate"):
                vac_timer = vac_timer + jnp.where(qmask & ~occ, dt, 0.0)
                lat_area = backlog.sum() * dt

                awake_step = n_wake * wake_cost_us + served / mu
                energy_step = (active_power_w * awake_step
                               + ts_arm * e_arm_s + busy_tries * e_arm_l)
                out = (offered, dropped, served, n_wake, busy_tries, cycles,
                       vac_sum, nv_sum, adm.sum(), lat_area, ts_arm,
                       energy_step, adm, serve)
            return (sleep_rem, attached, backlog, vac_timer, arr_res,
                    stall_end), out

        def fleet_step(carry, t, aged_prev, aged_now, orig_share):
            """One slot of the fleet.  ``aged_prev``/``aged_now``: per
            (host, queue), how many admissions are at least D old at the
            end of the previous slot and of this one; ``orig_share``: the
            balancer's share of those that turn D old in this slot."""
            prev = carry
            (f_sleep, f_att, f_back, f_vac, f_res, f_stall, stale_b,
             S, C) = carry
            now = t.astype(jnp.float32) * dt
            live = now < duration
            with jax.named_scope("balancer"):
                if n_seg > 0:
                    si = jnp.clip(
                        jnp.searchsorted(sched_edges, now, side="right") - 1,
                        0, n_seg - 1)
                    scale_t = sched_scales[si]
                else:
                    scale_t = jnp.float32(1.0)

                # 1. load balancer: split the fleet stream across hosts
                if lb_code == 2:
                    # least-loaded on a stale snapshot, refreshed every
                    # stale_every_slots (the lag IS the policy's weakness)
                    refresh = (t % stale_every_slots) == 0
                    stale_b = jnp.where(refresh, f_back.sum(axis=1), stale_b)
                    shares = jax.nn.softmax(-stale_b / lb_softness_pkts)
                else:
                    shares = w_static
                lam_h = lam * shares                       # (H,) mpps

            new_carry, outs = jax.vmap(
                host_step, in_axes=(0, None, None, 0, 0, 0, 0, 0, 0, 0)
            )(keys, t, scale_t, lam_h, f_sleep, f_att, f_back, f_vac,
              f_res, f_stall)
            (f_sleep, f_att, f_back, f_vac, f_res, f_stall) = new_carry
            (offered_h, dropped_h, served_h, n_wake_h, busy_h, cycles_h,
             vac_h, nv_h, adm_h, lat_area_h, ts_arm_h, energy_h, adm_hq,
             serve_hq) = outs

            # 2. topology: admitted packets pay rack cost; far packets
            # also queue on the shared bottleneck link (M/M/1-style
            # wait at the CURRENT far-rack arrival rate, clamped)
            with jax.named_scope("topology"):
                if topo_on:
                    topo_delay_us = rack_cost_us
                    if link_rate_mpps > 0.0 and far_count > 0:
                        far_rate = jnp.where(far_mask, adm_h, 0.0).sum() / dt
                        gap = jnp.maximum(
                            link_rate_mpps - far_rate,
                            (1.0 - _LINK_UTIL_CLAMP) * link_rate_mpps)
                        topo_delay_us = topo_delay_us + far_mask / gap
                    topo_area_h = adm_h * topo_delay_us
                else:
                    topo_area_h = jnp.zeros((n_hosts,))

            # 3. hedging: the balancer's packets that turn D old in this
            # slot while still queued (FIFO: ranks above those served)
            # are duplicated, each to a random other replica; in the
            # fluid kernel every other host takes 1/(H-1) of each host's
            # duplicates.  orig_share is 0 unless D > 0, so the backlog
            # then stays bit-identical.
            with jax.named_scope("hedge"):
                cum_served = C.cum_served + serve_hq
                late = jnp.maximum(
                    aged_now - jnp.maximum(aged_prev, cum_served), 0.0)
                dup_h = (late * orig_share).sum(axis=1)    # (H,) issued
                if n_hosts > 1:
                    inject = (dup_h.sum() - dup_h) / (n_hosts - 1)
                else:
                    inject = jnp.zeros((n_hosts,))
                inject = inject[:, None] * (qmask / nq)    # (H, q_max)
                inj_room = jnp.maximum(capacity - f_back, 0.0)
                dup_in = jnp.minimum(inject, inj_room)
                f_back = f_back + dup_in

            with jax.named_scope("sojourn"):
                slot_end = (t + 1).astype(jnp.float32) * dt
                served_q = serve_hq > 0.0
                C = _Cohort(
                    cum_all=C.cum_all + adm_hq + dup_in,
                    cum_orig=C.cum_orig + adm_hq,
                    cum_served=cum_served,
                    served=C.served + serve_hq,
                    first_us=jnp.where(served_q,
                                       jnp.minimum(C.first_us, slot_end),
                                       C.first_us),
                    last_us=jnp.where(served_q, slot_end, C.last_us))

            with jax.named_scope("accumulate"):
                S = _FleetSlotStats(
                    offered=S.offered + offered_h,
                    dropped=S.dropped + dropped_h,
                    serviced=S.serviced + served_h,
                    wakeups=S.wakeups + n_wake_h,
                    busy_tries=S.busy_tries + busy_h,
                    cycles=S.cycles + cycles_h,
                    awake_us=S.awake_us + n_wake_h * wake_cost_us
                             + served_h / mu,
                    lat_area=S.lat_area + lat_area_h,
                    vac_sum=S.vac_sum + vac_h,
                    nv_sum=S.nv_sum + nv_h,
                    ts_arms=S.ts_arms + ts_arm_h,
                    energy_uj=S.energy_uj + energy_h,
                    topo_area=S.topo_area + topo_area_h,
                    hedge_dup=S.hedge_dup + dup_h,
                    hedge_dup_in=S.hedge_dup_in + dup_in.sum(axis=1),
                    hedge_dup_dropped=S.hedge_dup_dropped
                    + (inject - dup_in).sum(axis=1),
                )
            with jax.named_scope("gate"):
                nxt = (f_sleep, f_att, f_back, f_vac, f_res, f_stall,
                       stale_b, S, C)
                # steps past this point's duration hold the carry — the
                # bucketed scan length pads with no-ops, live steps stay
                # bit-identical to the unpadded scan
                gated = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(live, new, old), nxt, prev)
            return gated

        def cohort_step(carry, b):
            """Cohort ``b``: its ``g`` slots, then its close.  At the
            start, the ring gives the admissions at least D old at the
            cohort's two ends, interpolated linearly in between; at the
            close, the ring takes the cohort's sample and gives the
            admission times of the first and the last rank served, and
            the cohort's served mass goes into the histogram, its
            sojourns spread evenly between those two ranks'."""
            slots, ring_all, ring_orig, hist = carry
            with jax.named_scope("hedge"):
                # age of ring entry k, in cohorts: 0 = newest (cohort b-1)
                age = ((b - 1 - k_idx) % RING).astype(jnp.float32)
                lag = jnp.maximum(hedge_d, cohort_us) / cohort_us
                w_lo = jnp.maximum(1.0 - jnp.abs(age - lag), 0.0)
                w_d = jnp.maximum(1.0 - jnp.abs(age - lag + 1.0), 0.0) - w_lo
                aged_lo = (ring_all * w_lo).sum(axis=-1)   # (H, q_max)
                d_all = (ring_all * w_d).sum(axis=-1)
                d_orig = (ring_orig * w_d).sum(axis=-1)
                share = jnp.where(d_all > 1e-6,
                                  d_orig / jnp.maximum(d_all, 1e-6), 0.0)
                share = share * (hedge_d > 0.0)

            def slot(carry, j):
                k = j.astype(jnp.float32)
                return fleet_step(carry, b * g + j,
                                  aged_lo + (k / g) * d_all,
                                  aged_lo + ((k + 1.0) / g) * d_all,
                                  share), None

            slots, _ = jax.lax.scan(slot, slots,
                                    jnp.arange(g, dtype=jnp.int32))
            C = slots[-1]
            with jax.named_scope("sojourn"):
                hot = k_idx == b % RING
                ring_all = jnp.where(hot, C.cum_all[..., None], ring_all)
                ring_orig = jnp.where(hot, C.cum_orig[..., None], ring_orig)
                # when each served rank was admitted: the ring's samples
                # above it are the newest, the newest one not above it
                # and the oldest above it bracket its admission (FIFO)
                t_close = (b + 1).astype(jnp.float32) * cohort_us
                rank = jnp.stack([jnp.zeros_like(C.cum_served),
                                  C.cum_served], axis=-1)[..., None]
                above = ring_all[..., None, :] > rank     # (H, q, 2, RING)
                n_above = above.sum(axis=-1).astype(jnp.float32)
                a_hi = jnp.where(above, ring_all[..., None, :],
                                 jnp.inf).min(axis=-1)
                seen = n_above < RING
                a_lo = jnp.where(seen, jnp.where(
                    above, -jnp.inf, ring_all[..., None, :]).max(axis=-1),
                    0.0)
                t_adm = jnp.where(
                    seen,
                    t_close - n_above * cohort_us
                    + cohort_us * (rank[..., 0] - a_lo)
                    / jnp.maximum(a_hi - a_lo, 1e-6),
                    t_close - (RING + 1) * cohort_us)
                s_first = C.first_us - t_adm[..., 0]
                s_last = C.last_us - t_adm[..., 1]
                has = C.served > 0.0
                lo = jnp.where(has, jnp.maximum(
                    jnp.minimum(s_first, s_last), 0.0), 0.0)
                hi = jnp.where(has, jnp.maximum(s_first, s_last), 0.0)
                cdf = jnp.clip((edges - lo[..., None])
                               / jnp.maximum(hi - lo, 1e-6)[..., None],
                               0.0, 1.0)
                ones = jnp.ones(lo.shape + (1,))
                frac = (jnp.concatenate([cdf, ones], axis=-1)
                        - jnp.concatenate([0.0 * ones, cdf], axis=-1))
                hist = hist + (jnp.where(has, C.served, 0.0)[..., None]
                               * frac).sum(axis=1)
                # rebase the counts on the ranks served: what the lookups
                # compare stays near zero, where float32 is exact enough
                ring_all = ring_all - C.cum_served[..., None]
                ring_orig = ring_orig - C.cum_orig[..., None]
                C = _Cohort(cum_all=C.cum_all - C.cum_served,
                            cum_orig=jnp.zeros_like(C.cum_orig),
                            cum_served=jnp.zeros_like(C.cum_served),
                            served=jnp.zeros_like(C.served),
                            first_us=jnp.full_like(C.first_us, jnp.inf),
                            last_us=jnp.full_like(C.last_us, -jnp.inf))
            return (slots[:-1] + (C,), ring_all, ring_orig, hist), None

        zh = jnp.zeros((n_hosts,), jnp.float32)
        zq = jnp.zeros((n_hosts, q_max), jnp.float32)
        init = (sleep0_h,
                jnp.full((n_hosts, m_max), -1, jnp.int32),
                zq, zq, zq,
                jnp.full((n_hosts,), -1.0, jnp.float32),
                zh,                          # stale LB snapshot
                _FleetSlotStats(*([zh] * len(_FleetSlotStats._fields))),
                _Cohort(zq, zq, zq, zq, jnp.full_like(zq, jnp.inf),
                        jnp.full_like(zq, -jnp.inf)))
        ring0 = jnp.zeros((n_hosts, q_max, RING), jnp.float32)
        hist0 = jnp.zeros((n_hosts, HIST_BINS + 2), jnp.float32)
        n_cohorts = -(-n_slots // g)
        (slots, _, _, hist), _ = jax.lax.scan(
            cohort_step, (init, ring0, ring0, hist0),
            jnp.arange(n_cohorts, dtype=jnp.int32))
        n_live = jnp.minimum(jnp.ceil(duration / dt),
                             jnp.float32(n_cohorts * g))
        return ((slots[-2], hist), n_live * dt, n_live,
                jnp.zeros_like(duration))

    inner = jax.vmap(one_fleet)
    if n_shards > 1:
        from jax.sharding import Mesh, PartitionSpec

        mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("pts",))
        spec = PartitionSpec("pts")
        inner = jax.shard_map(inner, mesh=mesh, in_specs=(spec,) * 11,
                              out_specs=spec, check_vma=False)

    def fleet_sweep(*points):
        return inner(*points)

    # the function's name is the compiled module's: jit_fleet_sweep
    return jax.jit(fleet_sweep)


_compiled_fleet_sweep = CompileCache(_build_fleet_sweep, maxsize=64,
                                     name="fleet._compiled_fleet_sweep")


class _Survival:
    """Measured per-host survival P(sojourn > x) from the kernel's
    histograms ``hist`` (H, HIST_BINS + 2) with inner ``edges``: linear
    inside a bin (the kernel spreads each cohort's mass evenly in x),
    flat in the overflow bin.  ``shift_us`` (H,) delays each host's
    curve by its network delay."""

    def __init__(self, hist: np.ndarray, edges: np.ndarray,
                 shift_us: np.ndarray):
        tot = np.maximum(hist.sum(axis=1, keepdims=True), 1e-30)
        self.mass = hist / tot
        # survival at each bin's lower bound: 0, edges[0], ..., edges[-1]
        self.tail = np.cumsum(self.mass[:, ::-1], axis=1)[:, ::-1]
        self.bound = np.concatenate([[0.0], edges])
        self.shift = shift_us
        self.rows = np.arange(hist.shape[0])

    def __call__(self, x: float) -> np.ndarray:
        """(H,) survival of every host at ``x`` us."""
        xs = np.maximum(x - self.shift, 0.0)
        b = np.searchsorted(self.bound, xs, side="right") - 1
        inner = b < self.bound.size - 1
        bb = np.minimum(b, self.bound.size - 2)
        width = self.bound[bb + 1] - self.bound[bb]
        frac = np.where(inner, np.clip((xs - self.bound[bb]) / width,
                                       0.0, 1.0), 0.0)
        return self.tail[self.rows, b] - frac * self.mass[self.rows, bb]

    def host_quantiles(self, q: float) -> np.ndarray:
        """(H,) each host's own ``q`` quantile (inf past the horizon)."""
        p = 1.0 - q
        b = (self.tail > p).sum(axis=1) - 1
        inner = b < self.bound.size - 1
        bb = np.minimum(b, self.bound.size - 2)
        m = np.maximum(self.mass[self.rows, bb], 1e-30)
        x = self.bound[bb] + np.clip((self.tail[self.rows, bb] - p) / m,
                                     0.0, 1.0) * (self.bound[bb + 1]
                                                  - self.bound[bb])
        return np.where(inner, x + self.shift, np.inf)


@dataclass
class FleetStats:
    """Per-(point, host) results of one fleet sweep.

    All counter arrays are float64 of shape ``(len(fgrid), n_hosts)``;
    ``hist`` is ``(len(fgrid), n_hosts, HIST_BINS + 2)``: each host's
    served packets by sojourn, bins ``sojourn_edges(slot_us)``.
    Fleet-level metrics
    reduce over the host axis; tail quantiles combine the measured
    per-host survivals by first completion wins (``quantile``).
    ``reshaped(name)`` appends the host axis to the grid's logical
    shape.
    """

    fgrid: FleetGrid
    cfg: SimRunConfig
    slot_us: float
    backend: str = "vmap"           # "vmap" | "shard_map(n)"
    offered: np.ndarray = field(default_factory=lambda: np.empty(0))
    dropped: np.ndarray = field(default_factory=lambda: np.empty(0))
    serviced: np.ndarray = field(default_factory=lambda: np.empty(0))
    wakeups: np.ndarray = field(default_factory=lambda: np.empty(0))
    busy_tries: np.ndarray = field(default_factory=lambda: np.empty(0))
    cycles: np.ndarray = field(default_factory=lambda: np.empty(0))
    awake_us: np.ndarray = field(default_factory=lambda: np.empty(0))
    lat_area: np.ndarray = field(default_factory=lambda: np.empty(0))
    vac_sum: np.ndarray = field(default_factory=lambda: np.empty(0))
    nv_sum: np.ndarray = field(default_factory=lambda: np.empty(0))
    ts_arms: np.ndarray = field(default_factory=lambda: np.empty(0))
    energy_uj: np.ndarray = field(default_factory=lambda: np.empty(0))
    topo_area: np.ndarray = field(default_factory=lambda: np.empty(0))
    hedge_dup: np.ndarray = field(default_factory=lambda: np.empty(0))
    hedge_dup_in: np.ndarray = field(default_factory=lambda: np.empty(0))
    hedge_dup_dropped: np.ndarray = field(
        default_factory=lambda: np.empty(0))
    hist: np.ndarray = field(default_factory=lambda: np.empty(0))
    # the compiled scan length, and per-POINT live slots, simulated time
    # and forced steps (always 0: the kernel takes no step but a slot)
    scan_len: int = 0
    n_steps: np.ndarray = field(default_factory=lambda: np.empty(0))
    forced_steps: np.ndarray = field(default_factory=lambda: np.empty(0))
    sim_time_us: np.ndarray = field(default_factory=lambda: np.empty(0))

    # -- derived ---------------------------------------------------------------
    @property
    def n_hosts(self) -> int:
        return self.fgrid.fleet.n_hosts

    @property
    def host_mean_latency_us(self) -> np.ndarray:
        """(P, H) end-to-end mean sojourn per host: Little's-law host
        component plus the network delay charged to its packets."""
        return ((self.lat_area + self.topo_area)
                / np.maximum(self.serviced, 1.0))

    @property
    def host_weight(self) -> np.ndarray:
        """(P, H) share of the balancer's requests each host admitted
        (duplicates excluded): the weights of the fleet's mixture."""
        adm = np.maximum(self.offered - self.dropped, 0.0)
        return adm / np.maximum(adm.sum(axis=1, keepdims=True), 1.0)

    @property
    def host_cpu_fraction(self) -> np.ndarray:
        return self.awake_us / self.cfg.duration_us

    @property
    def total_cpu_cores(self) -> np.ndarray:
        """(P,) cores burned by the whole fleet (the verdict metric —
        a busy-poll fleet pins n_hosts cores)."""
        return self.awake_us.sum(axis=1) / self.cfg.duration_us

    @property
    def host_power_w(self) -> np.ndarray:
        """(P, H) mean package power per host."""
        return self.energy_uj / self.cfg.duration_us

    @property
    def total_energy_uj(self) -> np.ndarray:
        """(P,) cluster energy (the power half of the verdict metric)."""
        return self.energy_uj.sum(axis=1)

    @property
    def energy_per_packet_nj(self) -> np.ndarray:
        """(P,) cluster energy per served packet."""
        return (1e3 * self.energy_uj.sum(axis=1)
                / np.maximum(self.serviced.sum(axis=1), 1.0))

    @property
    def mean_latency_us(self) -> np.ndarray:
        """(P,) fleet mean end-to-end sojourn (served-weighted)."""
        return ((self.lat_area + self.topo_area).sum(axis=1)
                / np.maximum(self.serviced.sum(axis=1), 1.0))

    @property
    def loss_fraction(self) -> np.ndarray:
        return (self.dropped.sum(axis=1)
                / np.maximum(self.offered.sum(axis=1), 1.0))

    @property
    def offered_total(self) -> np.ndarray:
        return self.offered.sum(axis=1)

    @property
    def offered_with_hedges(self) -> np.ndarray:
        """(P,) offered load including the duplicates issued."""
        return (self.offered + self.hedge_dup).sum(axis=1)

    @property
    def hist_overflow(self) -> np.ndarray:
        """(P,) served packets whose sojourn the ring's horizon could not
        see (the histogram's last bin)."""
        return self.hist[..., -1:].sum(axis=(1, 2))

    @property
    def rho(self) -> np.ndarray:
        """(P,) per-host utilization at uniform split."""
        return (self.fgrid.grid.rate_mpps
                / (self.cfg.service_rate_mpps * self.n_hosts))

    def survival(self, i: int) -> _Survival:
        """Measured per-host survival of point ``i``, network delay
        included."""
        delay = self.topo_area[i] / np.maximum(self.serviced[i], 1.0)
        return _Survival(self.hist[i], sojourn_edges(self.slot_us), delay)

    def quantile(self, i: int, q: float = 0.999) -> float:
        """Fleet latency quantile of point ``i`` from the measured
        per-host survivals S_h, first completion wins.  A request lands
        on host h with the share of the balancer's traffic h admitted
        (w_h); one still queued at D is duplicated to a random other
        replica j, which loses it with j's duplicate-drop share l_j:

            S(x) = sum_h w_h S_h(x) P_-h(x - D)   for x > D,
            P_j(y) = l_j + (1 - l_j) S_j(y),  P_-h = mean of P over j != h

        and S(x) = sum_h w_h S_h(x) up to D (or with D <= 0).  Solved by
        bisection; inf when the quantile lies past the ring's horizon."""
        if not 0.0 < q < 1.0:
            raise ValueError("q must be in (0, 1)")
        surv = self.survival(i)
        w = self.host_weight[i]
        d = float(self.fgrid.hedge_deadline_us[i])
        hedged = d > 0.0 and self.n_hosts > 1
        got = self.hedge_dup_in[i] + self.hedge_dup_dropped[i]
        lost = self.hedge_dup_dropped[i] / np.maximum(got, 1e-30)

        def fleet_surv(x: float) -> float:
            s = surv(x)
            if hedged and x > d:
                p = lost + (1.0 - lost) * surv(x - d)
                s = s * (p.sum() - p) / (self.n_hosts - 1)
            return float(w @ s)

        target = 1.0 - q
        hi = float(surv.bound[-1] + surv.shift.max()) + max(d, 0.0)
        if fleet_surv(hi) > target:
            return math.inf
        lo = 0.0
        for _ in range(60):
            mid = 0.5 * (lo + hi)
            if fleet_surv(mid) > target:
                lo = mid
            else:
                hi = mid
        return 0.5 * (lo + hi)

    def quantiles(self, q: float = 0.999) -> np.ndarray:
        """(P,) ``quantile(i, q)`` of every point (span ``fleet.tail``)."""
        with tracing.span("fleet.tail", points=len(self)):
            return np.asarray([self.quantile(i, q)
                               for i in range(len(self))])

    @property
    def p999_latency_us(self) -> np.ndarray:
        return self.quantiles(0.999)

    def reshaped(self, name: str) -> np.ndarray:
        val = np.asarray(getattr(self, name))
        shape = self.fgrid.shape or (len(self),)
        if val.ndim == 2:
            return val.reshape(shape + (self.n_hosts,))
        return val.reshape(shape)

    # -- RunStats rollups ------------------------------------------------------
    def _labels(self, i: int) -> tuple[dict, float, dict]:
        """What every host's ``RunStats`` of point ``i`` shares: labels,
        the worst-case latency, and the point."""
        p = self.fgrid.point(i)
        cap = self.cfg.queue_capacity * max(int(p["n_queues"]), 1)
        return dict(
            backend="fleet",
            policy=(f"sleepwake(t_s={p['t_s_us']:g},"
                    f"t_l={p['t_l_us']:g},m={p['m']})"),
            workload=f"fleet-share({p['rate_mpps']:g}mpps/{self.n_hosts})",
            started_ns=0, stopped_ns=round(self.cfg.duration_us * 1e3),
        ), float(cap / self.cfg.service_rate_mpps + p["t_l_us"]), p

    def host_run_stats(self, i: int) -> list[RunStats]:
        """One ``RunStats`` per host for point ``i`` (host-level view;
        the mean includes the host's network share, the p99 is the
        host's own measured one: NaN where the sweep measured none)."""
        labels, worst, p = self._labels(i)
        p99 = (self.survival(i).host_quantiles(0.99) if self.hist.size
               else np.full(self.n_hosts, np.nan))
        means = self.host_mean_latency_us[i]
        return [RunStats(
            **labels,
            wakeups=int(self.wakeups[i, h]),
            cycles=int(self.cycles[i, h]),
            busy_tries=int(self.busy_tries[i, h]),
            items=int(self.serviced[i, h]),
            offered=int(self.offered[i, h]),
            dropped=int(self.dropped[i, h]),
            awake_ns=round(self.awake_us[i, h] * 1e3),
            latency_us=Reservoir(4, seed=int(p["seed"]) + h),
            latency_area_us=float(self.lat_area[i, h]
                                  + self.topo_area[i, h]),
            energy_uj=float(self.energy_uj[i, h]),
            latency_override={"mean": float(means[h]), "p99": float(p99[h]),
                              "worst": worst},
        ) for h in range(self.n_hosts)]

    def to_run_stats(self, i: int) -> RunStats:
        """Cluster rollup of point ``i``: what ``RunStats.merge_all`` over
        ``host_run_stats(i)`` gives, summed from the arrays, with the
        fleet's measured p99 (first completion wins) as its p99."""
        labels, worst, p = self._labels(i)

        def total(a):          # the hosts' int() counters, summed
            return int(np.floor(a[i]).astype(np.int64).sum())

        items = np.floor(self.serviced[i])
        return RunStats(
            **labels,
            wakeups=total(self.wakeups), cycles=total(self.cycles),
            busy_tries=total(self.busy_tries), items=total(self.serviced),
            offered=total(self.offered), dropped=total(self.dropped),
            awake_ns=int(np.round(self.awake_us[i] * 1e3)
                         .astype(np.int64).sum()),
            latency_us=Reservoir(4, seed=int(p["seed"])),
            latency_area_us=float((self.lat_area[i]
                                   + self.topo_area[i]).sum()),
            energy_uj=float(self.energy_uj[i].sum()),
            latency_override={
                "mean": float(self.host_mean_latency_us[i] @ items
                              / max(items.sum(), 1.0)),
                "p99": self.quantile(i, 0.99),
                "worst": worst,
            },
        )

    def rollups(self) -> list[RunStats]:
        """``to_run_stats`` of every point (span ``fleet.rollup``)."""
        with tracing.span("fleet.rollup", points=len(self)):
            return [self.to_run_stats(i) for i in range(len(self))]

    def __len__(self) -> int:
        return len(self.fgrid)


def simulate_fleet(fgrid: FleetGrid, cfg: SimRunConfig | None = None, *,
                   slot_us: float = 0.5, shard: bool | None = None
                   ) -> FleetStats:
    """Simulate every fleet operating point — ONE jit-compiled call over
    the whole (point x host) batch; no Python loop over hosts.

    ``shard=None`` (default) splits the point axis across local devices
    via ``shard_map`` whenever more than one device is visible and falls
    back to pure vmap on one device; ``True``/``False`` force the
    respective path.  Points are padded to a multiple of the device
    count and the padding is sliced off the results.
    """
    cfg = cfg or SimRunConfig()
    validate_batched_config(cfg)
    fleet = fgrid.fleet.validate()
    hedge = np.asarray(fgrid.hedge_deadline_us, dtype=np.float64)
    horizon = float(sojourn_edges(slot_us)[-1])
    if np.any(hedge >= horizon):
        raise ValueError(f"hedge deadlines must lie below the sojourn "
                         f"ring's horizon of {horizon:g} us")
    n_pts = len(fgrid)
    m_max = int(fgrid.grid.m.max())
    q_max = int(fgrid.grid.n_queues.max())
    n_seg, sched_edges, sched_scales = _schedule_rows(fgrid.grid, cfg)
    stale_every_slots = max(int(round(fleet.lb_stale_us / slot_us)), 1)

    n_slots_true = max(int(math.ceil(cfg.duration_us / slot_us)), 1)
    g = cohort_slots(slot_us)
    n_slots = -(-bucket_steps(n_slots_true) // g) * g

    n_dev = len(jax.devices())
    use_shard = (n_dev > 1) if shard is None else bool(shard)
    n_shards = max(min(n_dev, n_pts), 1) if use_shard else 1

    with tracing.span("fleet.sweep", points=n_pts, hosts=fleet.n_hosts,
                      scan_steps=n_slots, built=0) as sp:
        sm = cfg.sleep_model
        lb_weights = (tuple(float(w) for w in fleet.shares())
                      if fleet.lb == "weighted" else ())
        fn = _compiled_fleet_sweep(
            n_slots, float(slot_us), m_max, q_max, int(fleet.n_hosts),
            float(cfg.service_rate_mpps), float(cfg.queue_capacity),
            float(cfg.wake_cost_us),
            (float(sm.base_us), float(sm.slope), float(sm.sigma_us),
             float(sm.tail_prob), float(sm.tail_mean_us)),
            (float(cfg.interference_prob), float(cfg.interference_mean_us),
             float(cfg.stall_rate_per_us), float(cfg.stall_mean_us)),
            cfg.energy_model.params(),
            n_seg, _LB_CODE[fleet.lb], lb_weights,
            float(fleet.lb_softness_pkts), stale_every_slots,
            fleet.far_hosts(), float(fleet.near_cost_us),
            float(fleet.far_cost_us), float(fleet.link_rate_mpps),
            n_shards)

        pad = (-n_pts) % n_shards

        def row(a, dtype):
            arr = np.asarray(a)
            if pad:
                arr = np.concatenate([arr, np.repeat(arr[:1], pad, axis=0)])
            return jnp.asarray(arr, dtype)

        g = fgrid.grid
        seed64 = np.asarray(g.seed, dtype=np.uint64)
        args = (row(g.t_s_us, jnp.float32), row(g.t_l_us, jnp.float32),
                row(g.m, jnp.int32), row(g.n_queues, jnp.int32),
                row(g.rate_mpps, jnp.float32),
                row((seed64 & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                    jnp.uint32),
                row((seed64 >> np.uint64(32)).astype(np.uint32),
                    jnp.uint32),
                row(hedge, jnp.float32),
                row(np.full(n_pts, cfg.duration_us), jnp.float32),
                row(sched_edges, jnp.float32),
                row(sched_scales, jnp.float32))
        with tracing.span("fleet.scan"):
            (out, hist), simt, nst, fst = jax.block_until_ready(fn(*args))
        with tracing.span("fleet.fetch"):
            vals = {k: np.asarray(v, dtype=np.float64)[:n_pts]
                    for k, v in out._asdict().items()}
            fs = FleetStats(
                fgrid=fgrid, cfg=cfg, slot_us=float(slot_us),
                backend=(f"shard_map({n_shards})" if n_shards > 1
                         else "vmap"),
                scan_len=n_slots,
                hist=np.asarray(hist, dtype=np.float64)[:n_pts],
                n_steps=np.asarray(nst, dtype=np.float64)[:n_pts],
                forced_steps=np.asarray(fst, dtype=np.float64)[:n_pts],
                sim_time_us=np.asarray(simt, dtype=np.float64)[:n_pts],
                **vals)
        sp.counts.update(
            hedge_dup=round(float(fs.hedge_dup.sum())),
            hedge_dup_dropped=round(float(fs.hedge_dup_dropped.sum())),
            hist_overflow=round(float(fs.hist[..., -1:].sum())))
    return fs
