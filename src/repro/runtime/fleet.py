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
  3. **Hedged requests.**  A per-point hedge deadline D duplicates
     requests that are predicted to miss it: each slot, the fraction
     ``sigmoid((backlog/mu - D) / (D/4))`` of a host's admitted packets
     is re-injected into the currently least-loaded *other* host — a
     smooth fluid stand-in for "duplicate to a second replica after D;
     first completion wins".  Duplicates burn real CPU on the partner
     (cancellation is not modeled in-scan, so fleet CPU is a
     conservative upper bound) and are counted in ``hedge_dup``, not in
     ``offered``.  The *tail benefit* of hedging — both replicas must
     stall for a request to stay slow — is evaluated post-scan by the
     closed-form model ``repro.runtime.stats.hedged_latency_quantile``
     on the per-host measured means, and pinned against the exact
     first-completion-wins reference (``repro.runtime.sim.
     fleet_tail_reference``) in tests.

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
from .batched_adaptive import (
    _FILL_SLACK_PKTS,
    _RATE_EPS,
    _WAKE_EPS_US,
    estimate_adaptive_steps,
)
from .simcore import _LINK_UTIL_CLAMP, FleetConfig, SimRunConfig
from .stats import Reservoir, RunStats, hedged_latency_quantile

__all__ = ["FleetGrid", "FleetStats", "simulate_fleet"]

_LB_CODE = {"uniform": 0, "weighted": 1, "least-loaded": 2}


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


def _build_fleet_sweep(n_slots: int, slot_us: float, m_max: int,
                       q_max: int, n_hosts: int, mu: float,
                       capacity: float, wake_cost_us: float,
                       sleep_params: tuple, interference_params: tuple,
                       energy_params: tuple,
                       n_seg: int, lb_code: int, lb_weights: tuple,
                       lb_softness_pkts: float, stale_every_slots: int,
                       far_count: int, near_cost_us: float,
                       far_cost_us: float, link_rate_mpps: float,
                       n_shards: int, stepping: str = "fixed"):
    """Build + jit the (point x host) fleet kernel for one static shape.

    The per-host slot body is the single-host kernel's, line for line
    (same PRNG key discipline per host — the parity contract), wrapped
    in an inner host vmap; the load-balancer split, the topology delay,
    and the hedge-duplicate exchange are the only cross-host stages.
    ``n_shards > 1`` wraps the point-axis vmap in ``shard_map`` over the
    first ``n_shards`` local devices.

    ``stepping="fixed"`` scans ``n_slots`` constant ``slot_us`` slots
    (``duration`` is traced and steps past it are carry-held no-ops, so
    one bucketed scan length serves nearby durations bit-identically);
    ``stepping="adaptive"`` treats ``n_slots`` as the event-jump step
    *budget*: every scan step advances one shared variable ``dt`` per
    point — the min over all hosts' wake / drain-out / fill boundaries,
    the schedule segment end, each host's next correlated-stall start,
    and the LB stale-snapshot refresh lattice (the refresh is a jump
    boundary, so the stale signal updates exactly on its
    ``lb_stale_us`` grid) — and the per-host body applies the
    closed-form multi-slot aggregates of ``batched_adaptive``.  The
    cross-host stages (LB split, bottleneck-link M/M/1 wait at the
    macro-slot's admission rate, fluid hedge duplication) consume the
    same ``dt``.
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
            # the extra split exists only in adaptive builds, so the
            # fixed kernel's per-host streams stay bit-identical
            if stepping == "adaptive" and stall_rate > 0.0:
                k, kst = jax.random.split(k)
                ns0 = jax.random.exponential(kst, ()) / stall_rate
            else:
                ns0 = jnp.float32(jnp.inf)
            return k, s0, ns0

        keys, sleep0_h, next0_h = jax.vmap(init_host)(host_lo)
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
                    nv_sum = nv_sum + jnp.where(do_attach, backlog[qi], 0.0)
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
                att_q = jnp.clip(attached, 0, q_max - 1)
                t_done = (attached >= 0) & q_done[att_q]
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
                       energy_step)
            return (sleep_rem, attached, backlog, vac_timer, arr_res,
                    stall_end), out

        def fleet_step(carry, t):
            prev = carry
            (f_sleep, f_att, f_back, f_vac, f_res, f_stall, stale_b,
             S) = carry
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
             vac_h, nv_h, adm_h, lat_area_h, ts_arm_h,
             energy_h) = outs
            back_tot = f_back.sum(axis=1)              # (H,) packets

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

            # 3. hedging (fluid): the share of this slot's admissions
            # predicted to miss the deadline (drain-time proxy
            # backlog/mu vs D, smooth sigmoid gate) is duplicated onto
            # the least-loaded OTHER host.  hedge_d <= 0 disables and
            # leaves the backlog bit-identical.
            with jax.named_scope("hedge"):
                hedge_on = (hedge_d > 0.0).astype(jnp.float32)
                drain_us = back_tot / mu
                gate = jax.nn.sigmoid((drain_us - hedge_d)
                                      / (0.25 * hedge_d + 1e-6))
                dup_h = adm_h * gate * hedge_on            # (H,) duplicates
                b1 = jnp.argmin(back_tot)
                b2 = jnp.argmin(jnp.where(h_idx == b1, jnp.inf, back_tot))
                partner = jnp.where(h_idx == b1, b2, b1)   # (H,)
                dup_per_q = dup_h[:, None] * (qmask / nq)  # (H, q_max)
                inject = jnp.zeros((n_hosts, q_max)).at[partner].add(dup_per_q)
                inj_room = jnp.maximum(capacity - f_back, 0.0)
                f_back = f_back + jnp.minimum(inject, inj_room)

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
                )
            with jax.named_scope("gate"):
                nxt = (f_sleep, f_att, f_back, f_vac, f_res, f_stall,
                       stale_b, S)
                # steps past this point's duration hold the carry — the
                # bucketed scan length pads with no-ops, live steps stay
                # bit-identical to the unpadded scan
                gated = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(live, new, old), nxt, prev)
            return gated, None

        zh = jnp.zeros((n_hosts,), jnp.float32)
        if stepping == "fixed":
            init = (sleep0_h,
                    jnp.full((n_hosts, m_max), -1, jnp.int32),
                    jnp.zeros((n_hosts, q_max), jnp.float32),
                    jnp.zeros((n_hosts, q_max), jnp.float32),
                    jnp.zeros((n_hosts, q_max), jnp.float32),
                    jnp.full((n_hosts,), -1.0, jnp.float32),
                    zh,                          # stale LB snapshot
                    _FleetSlotStats(zh, zh, zh, zh, zh, zh, zh, zh, zh,
                                    zh, zh, zh, zh, zh))
            (*_, S), _ = jax.lax.scan(
                fleet_step, init, jnp.arange(n_slots, dtype=jnp.int32))
            n_live = jnp.minimum(jnp.ceil(duration / dt),
                                 jnp.float32(n_slots))
            return S, n_live * dt, n_live, jnp.zeros_like(duration)

        # ---- adaptive (event-jump): one shared variable dt per point —
        # the per-host boundary structure reduced with a fleet-wide min,
        # so all hosts advance in lock-step through the LB coupling
        floor_us = slot_us
        stale_us = float(stale_every_slots) * slot_us

        def fleet_step_a(carry, t):
            prev = carry
            (a_sleep, a_att, a_back, a_vac, a_res, a_stall, a_next,
             lb_snap, next_ref, rem_t, nst, fst, SA) = carry
            now = duration - rem_t
            live = rem_t > 0.0

            with jax.named_scope("balancer"):
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

                # LB stale refresh is a jump boundary: the snapshot updates
                # exactly on its lb_stale_us lattice (missed lattice points
                # after a forced jump are skipped, matching the fixed
                # kernel's modulo refresh)
                if lb_code == 2:
                    fire_ref = now + _WAKE_EPS_US >= next_ref
                    lb_snap = jnp.where(fire_ref, a_back.sum(axis=1), lb_snap)
                    next_ref = jnp.where(
                        fire_ref,
                        (jnp.floor(now / stale_us + _WAKE_EPS_US) + 1.0)
                        * stale_us,
                        next_ref)
                    shares = jax.nn.softmax(-lb_snap / lb_softness_pkts)
                    ref_dt = next_ref - now
                else:
                    shares = w_static
                    ref_dt = jnp.float32(jnp.inf)
                lam_h = lam * shares                       # (H,) mpps
                lam_hq = (lam_h * scale_t)[:, None] \
                    * jnp.where(qmask, 1.0 / nq, 0.0)[None, :]

            # ---- the jump: nearest boundary across the whole fleet
            with jax.named_scope("jump"):
                sleeping_h = tmask[None, :] & (a_att < 0)
                occ_h = (jax.nn.one_hot(a_att, q_max).sum(axis=1) > 0)
                wake_dt = jnp.min(jnp.where(
                    sleeping_h, jnp.maximum(a_sleep, 0.0), jnp.inf))
                net_out = jnp.where(occ_h, mu - lam_hq, 0.0)
                drain_hq = jnp.where(
                    occ_h & (net_out > _RATE_EPS),
                    jnp.maximum(a_back, 0.0)
                    / jnp.maximum(net_out, _RATE_EPS), jnp.inf)
                drain_dt = jnp.min(drain_hq)
                net_in = lam_hq - jnp.where(occ_h, mu, 0.0)
                fill_dt = jnp.min(jnp.where(
                    qmask[None, :] & (net_in > _RATE_EPS)
                    & (a_back < capacity - _FILL_SLACK_PKTS),
                    (capacity - a_back) / jnp.maximum(net_in, _RATE_EPS),
                    jnp.inf))
                stall_dt = jnp.min(a_next) - now
                dt_b = jnp.minimum(
                    jnp.minimum(jnp.minimum(wake_dt, drain_dt),
                                jnp.minimum(fill_dt, seg_dt)),
                    jnp.minimum(jnp.minimum(ref_dt, stall_dt), rem_t))
                # completion guard — tail-reserve pacing only, see
                # batched_adaptive (same scheme, n_slots is the budget here)
                steps_left = jnp.float32(n_slots) - t.astype(jnp.float32)
                in_tail = steps_left <= jnp.float32(max(n_slots // 8, 2))
                pace = jnp.where(in_tail, rem_t / steps_left, 0.0)
                # floor respects wakes and drain-outs fleet-wide (see
                # batched_adaptive: stepping past either stretches busy
                # periods / coalesces claims and biases the wake rate down
                # through the T_L parking feedback)
                floor_eff = jnp.minimum(
                    floor_us,
                    jnp.maximum(jnp.minimum(wake_dt, drain_dt),
                                _WAKE_EPS_US))
                dtv = jnp.minimum(
                    jnp.maximum(dt_b, jnp.maximum(floor_eff, pace)), rem_t)
                forced = (dtv > jnp.maximum(dt_b, floor_us) + _WAKE_EPS_US) \
                    & live
                t_new = now + dtv

            def host_step_a(key_h, lam_q, sleep_rem, attached, backlog,
                            vac_timer, arr_res, stall_end, next_stall):
                """One host, one macro-slot — the closed-form aggregates
                of ``batched_adaptive`` at the shared fleet ``dtv``."""
                with jax.named_scope("rng"):
                    kt_step = jax.random.fold_in(key_h, t)
                    if tail_prob > 0.0:
                        kt_step, kp, ku = jax.random.split(kt_step, 3)
                    if intf_prob > 0.0:
                        kt_step, kip, kie = jax.random.split(kt_step, 3)
                    if stall_rate > 0.0:
                        kt_step, kse, ksg, ksu = jax.random.split(kt_step, 4)
                    zs = jax.random.normal(kt_step, (q_max + m_max,))

                with jax.named_scope("arrivals"):
                    sleeping = tmask & (attached < 0)
                    occ = (jax.nn.one_hot(attached, q_max).sum(axis=0) > 0)

                    # drain-boundary steps are deterministic per queue: a
                    # noisy draw there is one-sided (positive residual
                    # extends the busy period, negative cannot shorten it)
                    # — see batched_adaptive for the full argument
                    net_out_l = jnp.where(occ, mu - lam_q, 0.0)
                    drain_ql = jnp.where(
                        occ & (net_out_l > _RATE_EPS),
                        jnp.maximum(backlog, 0.0)
                        / jnp.maximum(net_out_l, _RATE_EPS), jnp.inf)
                    drain_now = occ & (drain_ql <= dtv + _WAKE_EPS_US)
                    mu_a = lam_q * dtv
                    z_q = jnp.where(drain_now, 0.0, zs[:q_max])
                    raw = arr_res + mu_a + jnp.sqrt(mu_a) * z_q
                    a = jnp.maximum(raw, 0.0)
                    arr_res = jnp.minimum(raw, 0.0)
                    room = jnp.maximum(capacity - backlog, 0.0) \
                        + jnp.where(occ, mu * dtv, 0.0)
                    adm = jnp.minimum(a, room)
                    offered = a.sum()
                    dropped = (a - adm).sum()

                with jax.named_scope("drain"):
                    serve = jnp.where(
                        occ, jnp.minimum(backlog + adm, mu * dtv), 0.0)
                    b_new = jnp.minimum(
                        jnp.maximum(backlog + adm - serve, 0.0), capacity)
                    served = serve.sum()

                with jax.named_scope("accumulate"):
                    lat_area = 0.5 * (backlog.sum() + b_new.sum()) * dtv
                    vac_timer = vac_timer + jnp.where(qmask & ~occ, dtv, 0.0)
                    backlog = b_new

                with jax.named_scope("stall"):
                    if stall_rate > 0.0:
                        fire = (next_stall <= t_new) & live
                        w_end = next_stall + stall_mean_us \
                            * jax.random.exponential(kse, ())
                        stall_end = jnp.where(
                            fire, jnp.maximum(stall_end, w_end), stall_end)
                        gap = jax.random.exponential(ksg, ()) / stall_rate
                        next_stall = jnp.where(fire, next_stall + gap,
                                               next_stall)

                with jax.named_scope("overshoot"):
                    over = jnp.full((m_max,), base_us)
                    if sigma_us > 0.0:
                        over = over + sigma_us * jnp.abs(zs[q_max:])
                    if tail_prob > 0.0:
                        hit = jax.random.uniform(kp, (m_max,)) < tail_prob
                        over = over + hit * tail_mean_us \
                            * jax.random.exponential(ku, (m_max,))
                    if intf_prob > 0.0:
                        ihit = jax.random.uniform(kip, (m_max,)) < intf_prob
                        over = over + ihit * intf_mean_us \
                            * jax.random.exponential(kie, (m_max,))
                    slp_s = t_s * (1.0 + slope) + over
                    slp_l = t_l * (1.0 + slope) + over

                with jax.named_scope("wake_claim"):
                    sleep_rem = jnp.where(sleeping, sleep_rem - dtv,
                                          sleep_rem)
                    woken = sleeping & (sleep_rem <= _WAKE_EPS_US) & live
                    if stall_rate > 0.0:
                        push = woken & (t_new < stall_end)
                        woken = woken & ~push
                        sleep_rem = jnp.where(
                            push,
                            stall_end - t_new
                            + jax.random.uniform(ksu, (m_max,)),
                            sleep_rem)
                    n_wake = woken.sum().astype(jnp.float32)

                # queues drained out by the boundary release their
                # thread BEFORE boundary wakes classify — drain-out
                # precedes the boundary in true time, so a thread
                # waking at the boundary must see the queue free
                # (release-after-claim would park it on T_L)
                with jax.named_scope("release"):
                    q_done = occ & (backlog <= 1e-6)
                    att_q = jnp.clip(attached, 0, q_max - 1)
                    t_done = (attached >= 0) & q_done[att_q]
                    sleep_rem = jnp.where(t_done, slp_s, sleep_rem)
                    attached = jnp.where(t_done, -1, attached)
                    occ = occ & ~q_done

                with jax.named_scope("wake_claim"):
                    busy_tries = jnp.float32(0.0)
                    cycles = jnp.float32(0.0)
                    vac_sum = jnp.float32(0.0)
                    nv_sum = jnp.float32(0.0)
                    ts_arm = t_done.sum().astype(jnp.float32)
                    for i in range(m_max):      # static unroll, m_max small
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
                        nv_sum = nv_sum + jnp.where(do_attach, backlog[qi],
                                                    0.0)
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

                with jax.named_scope("accumulate"):
                    awake_step = n_wake * wake_cost_us + served / mu
                    energy_step = (active_power_w * awake_step
                                   + ts_arm * e_arm_s
                                   + busy_tries * e_arm_l)
                    out = (offered, dropped, served, n_wake, busy_tries,
                           cycles, vac_sum, nv_sum, adm.sum(), lat_area,
                           ts_arm, energy_step)
                return (sleep_rem, attached, backlog, vac_timer, arr_res,
                        stall_end, next_stall), out

            new_carry, outs = jax.vmap(host_step_a)(
                keys, lam_hq, a_sleep, a_att, a_back, a_vac, a_res,
                a_stall, a_next)
            (a_sleep, a_att, a_back, a_vac, a_res, a_stall,
             a_next) = new_carry
            (offered_h, dropped_h, served_h, n_wake_h, busy_h, cycles_h,
             vac_h, nv_h, adm_h, lat_area_h, ts_arm_h,
             energy_h) = outs
            back_tot = a_back.sum(axis=1)

            # topology — the macro-slot's admissions pay rack + link
            # cost at the slot's average far-rack arrival rate
            with jax.named_scope("topology"):
                if topo_on:
                    topo_delay_us = rack_cost_us
                    if link_rate_mpps > 0.0 and far_count > 0:
                        far_rate = jnp.where(far_mask, adm_h, 0.0).sum() / dtv
                        gap = jnp.maximum(
                            link_rate_mpps - far_rate,
                            (1.0 - _LINK_UTIL_CLAMP) * link_rate_mpps)
                        topo_delay_us = topo_delay_us + far_mask / gap
                    topo_area_h = adm_h * topo_delay_us
                else:
                    topo_area_h = jnp.zeros((n_hosts,))

            # hedging (fluid) — per macro-slot, same gate as fixed
            with jax.named_scope("hedge"):
                hedge_on = (hedge_d > 0.0).astype(jnp.float32)
                drain_us = back_tot / mu
                gate = jax.nn.sigmoid((drain_us - hedge_d)
                                      / (0.25 * hedge_d + 1e-6))
                dup_h = adm_h * gate * hedge_on
                b1 = jnp.argmin(back_tot)
                b2 = jnp.argmin(jnp.where(h_idx == b1, jnp.inf, back_tot))
                partner = jnp.where(h_idx == b1, b2, b1)
                dup_per_q = dup_h[:, None] * (qmask / nq)
                inject = jnp.zeros((n_hosts, q_max)).at[partner].add(
                    dup_per_q)
                inj_room = jnp.maximum(capacity - a_back, 0.0)
                a_back = a_back + jnp.minimum(inject, inj_room)

            with jax.named_scope("accumulate"):
                SA = _FleetSlotStats(
                    offered=SA.offered + offered_h,
                    dropped=SA.dropped + dropped_h,
                    serviced=SA.serviced + served_h,
                    wakeups=SA.wakeups + n_wake_h,
                    busy_tries=SA.busy_tries + busy_h,
                    cycles=SA.cycles + cycles_h,
                    awake_us=SA.awake_us + n_wake_h * wake_cost_us
                             + served_h / mu,
                    lat_area=SA.lat_area + lat_area_h,
                    vac_sum=SA.vac_sum + vac_h,
                    nv_sum=SA.nv_sum + nv_h,
                    ts_arms=SA.ts_arms + ts_arm_h,
                    energy_uj=SA.energy_uj + energy_h,
                    topo_area=SA.topo_area + topo_area_h,
                    hedge_dup=SA.hedge_dup + dup_h,
                )
                rem_t = rem_t - dtv
                nst = nst + 1.0
                fst = fst + forced.astype(jnp.float32)
            with jax.named_scope("gate"):
                nxt = (a_sleep, a_att, a_back, a_vac, a_res, a_stall, a_next,
                       lb_snap, next_ref, rem_t, nst, fst, SA)
                gated = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(live, new, old), nxt, prev)
            return gated, None

        z0 = jnp.float32(0.0)
        init_a = (sleep0_h,
                  jnp.full((n_hosts, m_max), -1, jnp.int32),
                  jnp.zeros((n_hosts, q_max), jnp.float32),
                  jnp.zeros((n_hosts, q_max), jnp.float32),
                  jnp.zeros((n_hosts, q_max), jnp.float32),
                  jnp.full((n_hosts,), -1.0, jnp.float32),
                  next0_h,
                  zh,                        # stale LB snapshot
                  z0,                        # next_ref: refresh at t=0
                  jnp.asarray(duration, jnp.float32),
                  z0, z0,                    # n_steps, forced_steps
                  _FleetSlotStats(zh, zh, zh, zh, zh, zh, zh, zh, zh,
                                  zh, zh, zh, zh, zh))
        (*_, rem_f, nst, fst, SA), _ = jax.lax.scan(
            fleet_step_a, init_a, jnp.arange(n_slots, dtype=jnp.int32))
        return SA, duration - rem_f, nst, fst

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


@dataclass
class FleetStats:
    """Per-(point, host) results of one fleet sweep.

    All arrays are float64 of shape ``(len(fgrid), n_hosts)``.  Fleet-
    level metrics reduce over the host axis; tail quantiles come from
    the hedged-tail closed form on the per-host measured means (the
    slot engine keeps no samples).  ``reshaped(name)`` appends the host
    axis to the grid's logical shape.
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
    # stepping diagnostics (see BatchStats): which kernel ran, its
    # compiled scan length, and per-POINT live/forced step counts and
    # exact simulated time (host axis shares one dt, so these are (P,))
    stepping: str = "fixed"
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
        """(P, H) served-traffic share per host (tail-mixture weights)."""
        tot = np.maximum(self.serviced.sum(axis=1, keepdims=True), 1.0)
        return self.serviced / tot

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
        """(P,) offered load including hedge duplicates — strictly
        increasing as the hedge deadline tightens (the cost side of the
        hedging sanity test)."""
        return (self.offered + self.hedge_dup).sum(axis=1)

    @property
    def rho(self) -> np.ndarray:
        """(P,) per-host utilization at uniform split."""
        return (self.fgrid.grid.rate_mpps
                / (self.cfg.service_rate_mpps * self.n_hosts))

    def quantile(self, i: int, q: float = 0.999) -> float:
        """Fleet latency quantile of point ``i`` from the hedged-tail
        closed form on the measured per-host means, with the config's
        correlated-stall environment as the tail component."""
        tail_prob = min(self.cfg.stall_rate_per_us
                        * self.cfg.stall_mean_us, 0.5)
        return hedged_latency_quantile(
            q, self.host_mean_latency_us[i], self.host_weight[i],
            hedge_deadline_us=float(self.fgrid.hedge_deadline_us[i]),
            tail_prob=tail_prob,
            tail_scale_us=self.cfg.stall_mean_us)

    @property
    def p999_latency_us(self) -> np.ndarray:
        return np.asarray([self.quantile(i, 0.999)
                           for i in range(len(self))])

    def reshaped(self, name: str) -> np.ndarray:
        val = np.asarray(getattr(self, name))
        shape = self.fgrid.shape or (len(self),)
        if val.ndim == 2:
            return val.reshape(shape + (self.n_hosts,))
        return val.reshape(shape)

    # -- RunStats rollups ------------------------------------------------------
    def host_run_stats(self, i: int) -> list[RunStats]:
        """One ``RunStats`` per host for point ``i`` (host-level view;
        latency override mean includes the host's network share)."""
        p = self.fgrid.point(i)
        out = []
        for h in range(self.n_hosts):
            mean = float(self.host_mean_latency_us[i, h])
            cap = self.cfg.queue_capacity * max(int(p["n_queues"]), 1)
            out.append(RunStats(
                backend="fleet",
                policy=(f"sleepwake(t_s={p['t_s_us']:g},"
                        f"t_l={p['t_l_us']:g},m={p['m']})"),
                workload=(f"fleet-share({p['rate_mpps']:g}mpps"
                          f"/{self.n_hosts})"),
                wakeups=int(self.wakeups[i, h]),
                cycles=int(self.cycles[i, h]),
                busy_tries=int(self.busy_tries[i, h]),
                items=int(self.serviced[i, h]),
                offered=int(self.offered[i, h]),
                dropped=int(self.dropped[i, h]),
                awake_ns=round(self.awake_us[i, h] * 1e3),
                started_ns=0,
                stopped_ns=round(self.cfg.duration_us * 1e3),
                latency_us=Reservoir(4, seed=int(p["seed"]) + h),
                latency_area_us=float(self.lat_area[i, h]
                                      + self.topo_area[i, h]),
                energy_uj=float(self.energy_uj[i, h]),
                latency_override={
                    "mean": mean,
                    "p99": mean * 3.0,
                    "worst": float(cap / self.cfg.service_rate_mpps
                                   + p["t_l_us"]),
                },
            ))
        return out

    def to_run_stats(self, i: int) -> RunStats:
        """Cluster rollup of point ``i``: n-way ``RunStats.merge_all``
        over the per-host stats, with the fleet-level hedged-tail p99
        replacing the per-host heuristic."""
        hosts = self.host_run_stats(i)
        head = hosts[0]
        head.merge_all(hosts[1:])
        head.latency_override["p99"] = self.quantile(i, 0.99)
        return head

    def __len__(self) -> int:
        return len(self.fgrid)


def simulate_fleet(fgrid: FleetGrid, cfg: SimRunConfig | None = None, *,
                   slot_us: float = 0.5, shard: bool | None = None,
                   stepping: str = "fixed") -> FleetStats:
    """Simulate every fleet operating point — ONE jit-compiled call over
    the whole (point x host) batch; no Python loop over hosts.

    ``shard=None`` (default) splits the point axis across local devices
    via ``shard_map`` whenever more than one device is visible and falls
    back to pure vmap on one device; ``True``/``False`` force the
    respective path.  Points are padded to a multiple of the device
    count and the padding is sliced off the results.

    ``stepping="adaptive"`` switches to the event-jump kernel: hosts
    advance in lock-step by a shared variable ``dt`` (nearest boundary
    across the fleet, incl. the LB stale-refresh lattice).  The step
    budget sums per-host boundary estimates — load-proportionality
    shrinks as ``n_hosts`` grows (a 1000-host fleet has a wake
    somewhere almost every slot), so the budget is clamped at the
    fixed scan length and adaptive never scans more than fixed.
    """
    if stepping not in ("fixed", "adaptive"):
        raise ValueError(
            f"stepping must be 'fixed' or 'adaptive', got {stepping!r}")
    cfg = cfg or SimRunConfig()
    validate_batched_config(cfg)
    fleet = fgrid.fleet.validate()
    n_pts = len(fgrid)
    m_max = int(fgrid.grid.m.max())
    q_max = int(fgrid.grid.n_queues.max())
    n_seg, sched_edges, sched_scales = _schedule_rows(fgrid.grid, cfg)
    stale_every_slots = max(int(round(fleet.lb_stale_us / slot_us)), 1)

    n_slots_true = max(int(math.ceil(cfg.duration_us / slot_us)), 1)
    if stepping == "adaptive":
        est = estimate_adaptive_steps(fgrid.grid, cfg, slot_us, 0)
        if fleet.lb == "least-loaded":
            est += int(math.ceil(
                cfg.duration_us / (stale_every_slots * slot_us)))
        n_slots = bucket_steps(
            min(fleet.n_hosts * est + 64, n_slots_true))
    else:
        n_slots = bucket_steps(n_slots_true)

    n_dev = len(jax.devices())
    use_shard = (n_dev > 1) if shard is None else bool(shard)
    n_shards = max(min(n_dev, n_pts), 1) if use_shard else 1

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
        n_shards, stepping)

    pad = (-n_pts) % n_shards
    def row(a, dtype):
        arr = np.asarray(a)
        if pad:
            arr = np.concatenate([arr, np.repeat(arr[:1], pad, axis=0)])
        return jnp.asarray(arr, dtype)

    g = fgrid.grid
    seed64 = np.asarray(g.seed, dtype=np.uint64)
    out, simt, nst, fst = fn(
        row(g.t_s_us, jnp.float32), row(g.t_l_us, jnp.float32),
        row(g.m, jnp.int32), row(g.n_queues, jnp.int32),
        row(g.rate_mpps, jnp.float32),
        row((seed64 & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            jnp.uint32),
        row((seed64 >> np.uint64(32)).astype(np.uint32), jnp.uint32),
        row(fgrid.hedge_deadline_us, jnp.float32),
        row(np.full(n_pts, cfg.duration_us), jnp.float32),
        row(sched_edges, jnp.float32),
        row(sched_scales, jnp.float32))
    vals = {k: np.asarray(v, dtype=np.float64)[:n_pts]
            for k, v in out._asdict().items()}
    return FleetStats(
        fgrid=fgrid, cfg=cfg, slot_us=float(slot_us),
        backend=(f"shard_map({n_shards})" if n_shards > 1 else "vmap"),
        stepping=stepping, scan_len=n_slots,
        n_steps=np.asarray(nst, dtype=np.float64)[:n_pts],
        forced_steps=np.asarray(fst, dtype=np.float64)[:n_pts],
        sim_time_us=np.asarray(simt, dtype=np.float64)[:n_pts],
        **vals)
