"""Batched fixed-slot JAX simulation engine: thousands of configs per call.

The event engine (``repro.runtime.sim``) is exact but serial — one
(policy, workload, environment) point per call, at interpreter speed.
This engine trades per-event exactness for *throughput*: it discretizes
time into fixed slots (``lax.scan``), advances every poller thread and
Rx queue one slot at a time with pure array ops, and ``vmap``s the whole
run over a ``SweepGrid`` of operating points — (T_S, T_L, M, n_queues,
offered load, seed) — so a dense parameter sweep is one JIT-compiled
call instead of thousands of Python simulations.

Model (per grid point, per slot of ``slot_us``):

  1. arrivals per queue follow a residual-carried Gaussian fluid
     approximation of Poisson(lambda/n_queues * dt): each slot draws
     ``mu + sqrt(mu)*z`` packets (continuous), negative excursions are
     carried forward as a deficit instead of clipped, so both the total
     count and its variance at vacation scale match the Poisson process
     (an exact per-slot Poisson sampler costs O(lambda*dt) rejection
     iterations *inside* the scan and dominated the runtime by ~50x).
     Arrivals are admitted up to ``queue_capacity`` (drops counted —
     Rx-ring semantics);
  2. sleeping threads count down; threads whose timer expires wake
     (wake cost charged).  Each woken thread, in index order, claims the
     free (unlocked) queue with the longest backlog: a claim ends that
     queue's vacation and starts a busy period; a wake that finds a free
     but empty queue is an "empty win" (primary re-sleeps T_S, like the
     event engine's zero-backlog lock win); a wake with every queue
     locked is a busy try (backup re-sleeps T_L);
  3. each locked queue drains at mu for the slot (CPU charged as
     served/mu, exactly the event engine's accounting);
  4. queues drained to zero release their thread, which re-sleeps a
     fresh T_S sample;
  5. the queue-depth integral accumulates: mean latency is recovered by
     Little's law (area under the backlog curve / packets served), the
     true all-packet mean sojourn.

Sleep overshoot uses the same ``SleepModel`` affine-plus-noise form as
the event engine.  Wake-timer quantization is bias-corrected by carrying
the (negative) residual of each expired timer into the next sleep, so
wakeup *rates* are unbiased even though individual wakes land on slot
boundaries.

CPU-sharing environments are modeled with the event engine's semantics
(paper Sec 5.6 — co-located CPU-intensive applications):

  - *per-wake OS interference*: every re-sleep is lengthened by
    Exp(``interference_mean_us``) with probability
    ``interference_prob`` — an independent Bernoulli x Exp draw per
    thread per slot, charged only on slots where that thread actually
    re-arms its timer (exactly the event engine's per-sleep draw);
  - *correlated stall windows*: a Poisson process (rate
    ``stall_rate_per_us``, Bernoulli per slot with the exact
    ``1 - exp(-rate*dt)`` hit probability) opens system-wide freeze
    windows of Exp(``stall_mean_us``) length; any timer that expires
    inside an open window is deferred to the window's end (+U(0,1)us,
    the event engine's re-arm jitter) without being counted as a wake.
    Overlapping windows extend (``max``), matching the event engine's
    lazy merge.

Approximations vs the event engine (documented tolerances; pinned in
tests/test_batched_engine.py):

  - timeouts are static per point — the grid *is* the adaptation space
    (the calibration layer, not the engine, closes the loop);
  - arrivals are Poisson only (the workload protocol's generality stays
    with the event engine), but the rate may be *nonstationary*: a
    ``repro.runtime.schedule.LoadSchedule`` — per point via
    ``SweepGrid.schedules`` or batch-wide via ``cfg.schedule`` — is
    evaluated as a piecewise-constant multiplier per slot, and
    ``cfg.window_us > 0`` emits the same per-window
    offered/served/latency/CPU accumulators the event engine keeps
    (``BatchStats.windows(i)`` / ``.tracking(i, ...)``);
  - busy-period boundaries are quantized to ``slot_us`` (keep
    ``slot_us`` a few times smaller than T_S and 1/mu ≪ slot);
  - multi-queue sweeps release a thread after its one claimed queue
    drains instead of continuing the sweep (single-queue runs have no
    such gap, and parity is pinned at ``n_queues=1``);
  - stall-window starts/ends are quantized to ``slot_us`` and at most
    one window opens per slot (exact-probability Bernoulli), so keep
    ``stall_rate_per_us * slot_us`` well below 1.

Documented parity tolerance at ``n_queues=1``, stable region (rho ≤
0.85, T_S ≥ 8·slot_us): all-packet mean sojourn (Little's law, the
event engine's ``RunStats.mean_sojourn_us``) within max(1.5us, 12%) and
CPU fraction within 0.02 + 5% of the event engine — pinned for 24
random configurations in tests/test_batched_engine.py (typical observed
agreement is ~2% / ~0.005).  Under interference (``interference_prob >
0`` *and* ``stall_rate_per_us > 0``) the band widens — heavy-tailed
stall windows leave finite-sample noise in both engines' means — to
mean sojourn within max(4.5us, 22%), CPU within 0.025 + 6%, and loss
fraction within 0.03 absolute — pinned for 16 random noisy-host
configurations in the same test module.

Stepping modes.  ``simulate_batch(..., stepping="fixed")`` (the
default) is the kernel described above; every quantization caveat in
this docstring is a statement about its *per-slot* update at
``slot_us`` resolution, with two scan-shape refinements:

  - the scan length is the slot count rounded *up* a geometric ladder
    (``bucket_steps``) and the run duration is a traced input, so
    nearby durations share one compiled kernel (slots past a point's
    duration are carry-preserving no-ops) — numerics are unchanged,
    only recompile churn is;
  - wake-timer / busy-period / stall-window quantization is always
    ``slot_us`` regardless of the padded scan length.

``stepping="adaptive"`` dispatches to the event-jump kernel in
``batched_adaptive.py``: variable ``dt`` per scan step (next wake /
drain-out / fill / schedule-segment / window / stall-start boundary),
closed-form multi-slot aggregates, scan length O(#events) instead of
O(duration/slot_us) — load-proportional simulation, ~10x+ fewer steps
at low load.  Its approximation surface (what stays exact, what moves)
is documented in that module; both modes hold the parity bands above.
"""

from __future__ import annotations

import logging
import math
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from . import tracing
from .simcore import SimRunConfig
from .stats import Reservoir, RunStats, WindowedSeries

__all__ = ["SweepGrid", "BatchStats", "simulate_batch", "bucket_steps",
           "unsupported_config_fields", "validate_batched_config",
           "CompileCache", "compile_cache_stats"]

_log = logging.getLogger(__name__)


class _CacheInfo(NamedTuple):
    hits: int
    misses: int
    maxsize: int
    currsize: int
    evictions: int


class CompileCache:
    """LRU cache for jitted kernel builders, keyed on static shapes.

    ``functools.lru_cache`` is silent: when single-host and fleet shapes
    alternate past the bound, every call re-traces and the only symptom
    is a mysteriously slow process.  This cache (a) has a bound sized
    for fleet + single-host sweeps coexisting, (b) exposes hit / miss /
    eviction counters (``cache_info()``, surfaced by
    ``benchmarks/run.py --json``), (c) logs every eviction with the
    evicted key, so a retrace storm is visible in logs instead of
    silent, and (d) counts each build as ``built`` on the open tracing
    span, so a profile says which call compiled.  Every instance
    self-registers for ``compile_cache_stats``.
    """

    _registry: list["CompileCache"] = []

    def __init__(self, build, *, maxsize: int = 64, name: str = ""):
        self._build = build
        self.maxsize = int(maxsize)
        self.name = name or getattr(build, "__name__", "kernel")
        self._entries: OrderedDict[tuple, object] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        CompileCache._registry.append(self)

    def __call__(self, *key):
        try:
            fn = self._entries[key]
        except KeyError:
            self.misses += 1
            tracing.count(built=1)
            fn = self._build(*key)
            self._entries[key] = fn
            if len(self._entries) > self.maxsize:
                evicted, _ = self._entries.popitem(last=False)
                self.evictions += 1
                _log.warning(
                    "%s: evicting compiled kernel for static key %r "
                    "(cache full at %d entries, %d evictions so far) — "
                    "alternating shapes will re-trace every call",
                    self.name, evicted, self.maxsize, self.evictions)
            return fn
        self.hits += 1
        self._entries.move_to_end(key)
        return fn

    def cache_info(self) -> _CacheInfo:
        return _CacheInfo(self.hits, self.misses, self.maxsize,
                          len(self._entries), self.evictions)

    def cache_clear(self) -> None:
        self._entries.clear()
        self.hits = self.misses = self.evictions = 0

    def stats(self) -> dict:
        return {"name": self.name, "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions,
                "currsize": len(self._entries), "maxsize": self.maxsize}


def compile_cache_stats() -> list[dict]:
    """Hit/miss/eviction counters of every registered kernel cache (the
    batched sweep kernel, the fleet kernel) — one dict per cache, in
    registration order.  Benchmarks surface these in their JSON rows so
    retrace behavior is part of the tracked perf trajectory."""
    return [c.stats() for c in CompileCache._registry]


def bucket_steps(n: int, *, base: int = 64, ratio: float = 1.25) -> int:
    """Round a scan length up to a small geometric ladder.

    Both engines key their ``CompileCache`` on the scan length; keying
    on the *exact* slot count means every distinct ``duration_us``
    recompiles (a multi-second retrace to save a padded no-op tail).
    Rounding up to ``base * ratio**k`` collapses all nearby durations
    onto one compiled kernel at the cost of at most ``ratio - 1``
    (25%) extra carry-preserving no-op steps — the run's true duration
    is a traced input, so results are unchanged."""
    n = max(int(n), 1)
    v = base
    # rungs are the iterates v -> ceil(v * ratio), which makes every
    # rung a fixed point: bucket_steps(bucket_steps(n)) == bucket_steps(n)
    while v < n:
        v = int(math.ceil(v * ratio))
    return v


_DIMS = ("t_s_us", "t_l_us", "m", "n_queues", "rate_mpps", "seed")

# fixed-width piecewise-constant schedule rows the kernel consumes; a
# schedule denser than this is resampled to its per-segment means
_MAX_SCHED_SEGMENTS = 256


@dataclass(frozen=True)
class SweepGrid:
    """A flat batch of operating points, one simulated run per row.

    ``product(...)`` builds the dense cartesian grid (and remembers its
    logical ``shape`` so results can be reshaped per axis);
    ``of_points(...)`` wraps an arbitrary list of points (parity tests,
    spot checks).  All arrays share one length ``len(grid)``.

    ``schedules`` (optional) carries one ``LoadSchedule`` — or ``None``
    for stationary — per point: the batched engine evaluates each row's
    schedule as a piecewise-constant rate multiplier inside the slot
    loop, so a single vmapped call can sweep step/ramp/sinusoid/MMPP
    trajectories next to stationary points.  ``product`` grows the
    logical ``shape`` by a trailing schedules axis only when a
    ``schedules`` axis is passed (existing stationary grids keep their
    shape contract).
    """

    t_s_us: np.ndarray
    t_l_us: np.ndarray
    m: np.ndarray
    n_queues: np.ndarray
    rate_mpps: np.ndarray
    seed: np.ndarray
    shape: tuple = ()            # cartesian shape in _DIMS order ("" = flat)
    dims: tuple = _DIMS
    schedules: tuple = ()        # per-point LoadSchedule | None ("" = none)

    @classmethod
    def product(cls, *, t_s_us, t_l_us, rate_mpps, m=(3,), n_queues=(1,),
                seeds=(0,), schedules=None) -> "SweepGrid":
        axes = [np.atleast_1d(np.asarray(a)) for a in
                (t_s_us, t_l_us, m, n_queues, rate_mpps, seeds)]
        sched_axis = (np.arange(len(schedules))
                      if schedules is not None else np.zeros(1, np.int64))
        mesh = np.meshgrid(*axes, sched_axis, indexing="ij")
        shape = tuple(a.size for a in axes)
        if schedules is not None:
            shape = shape + (len(schedules),)
        vals = [g.ravel() for g in mesh]
        scheds = (tuple(schedules[i] for i in vals[6])
                  if schedules is not None else ())
        return cls(t_s_us=vals[0].astype(np.float64),
                   t_l_us=vals[1].astype(np.float64),
                   m=vals[2].astype(np.int32),
                   n_queues=vals[3].astype(np.int32),
                   rate_mpps=vals[4].astype(np.float64),
                   seed=vals[5].astype(np.int64),
                   shape=shape,
                   schedules=scheds)

    @classmethod
    def of_points(cls, points) -> "SweepGrid":
        """``points``: iterable of dicts with keys from ``SweepGrid.dims``
        (missing keys take m=3, n_queues=1, seed=0) plus an optional
        ``schedule`` (a ``LoadSchedule``) per point."""
        pts = list(points)
        get = lambda k, d: np.asarray([p.get(k, d) for p in pts])  # noqa: E731
        scheds = tuple(p.get("schedule") for p in pts)
        return cls(t_s_us=get("t_s_us", 10.0).astype(np.float64),
                   t_l_us=get("t_l_us", 500.0).astype(np.float64),
                   m=get("m", 3).astype(np.int32),
                   n_queues=get("n_queues", 1).astype(np.int32),
                   rate_mpps=get("rate_mpps", 14.88).astype(np.float64),
                   seed=get("seed", 0).astype(np.int64),
                   shape=(len(pts),),
                   schedules=(scheds if any(s is not None for s in scheds)
                              else ()))

    def __len__(self) -> int:
        return int(self.t_s_us.size)

    def point(self, i: int) -> dict:
        d = {k: getattr(self, k)[i].item() for k in self.dims}
        if self.schedules:
            d["schedule"] = self.schedules[i]
        return d


class _SlotStats(NamedTuple):
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


def energy_arm_cost(target_us, sleep_states):
    """Per-arm sleep + transition energy (uJ) of a traced sleep target:
    the deepest C-state whose minimum residency fits pays
    ``power_w * target + transition_uj`` (the next-timer-event governor
    approximation — see ``simcore.EnergyModel``).  ``sleep_states`` is
    the static shallow-to-deep tuple from ``EnergyModel.params()``."""
    p_w = jnp.float32(sleep_states[0][0])
    t_uj = jnp.float32(sleep_states[0][1])
    for pw, tuj, thr_us in sleep_states[1:]:
        fits = target_us >= thr_us
        p_w = jnp.where(fits, jnp.float32(pw), p_w)
        t_uj = jnp.where(fits, jnp.float32(tuj), t_uj)
    return p_w * target_us + t_uj


@dataclass
class BatchStats:
    """Array-shaped results, one entry per ``SweepGrid`` row.

    Everything is a float64 numpy array of shape ``(len(grid),)``;
    derived metrics are properties.  ``reshaped(name)`` folds a metric
    back to the grid's cartesian ``shape``; ``to_run_stats(i)`` converts
    one point into the unified ``RunStats`` (latency beyond the mean is
    an analytic estimate — the batched engine does not keep samples).
    """

    grid: SweepGrid
    cfg: SimRunConfig
    slot_us: float
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
    # energy accounting (cfg.energy_model): T_S-class arm count and the
    # total charge — active_power*awake + per-arm C-state residency +
    # transition energy (T_L-class arms == busy_tries)
    ts_arms: np.ndarray = field(default_factory=lambda: np.empty(0))
    energy_uj: np.ndarray = field(default_factory=lambda: np.empty(0))
    # cfg.window_us > 0: per-point windowed accumulators of shape
    # (len(grid), n_windows, 5) — [offered, served, lat_area, awake,
    # energy] — the same raw sums the event engine's WindowAccum keeps
    win: np.ndarray = field(default_factory=lambda: np.empty(0))
    # stepping diagnostics: which kernel produced this batch, its
    # compiled scan length, and per-point live-step / forced-step
    # counts, exact simulated time, and end-of-run total backlog (the
    # missing term of the offered = served + dropped + backlog law)
    stepping: str = "fixed"
    scan_len: int = 0
    n_steps: np.ndarray = field(default_factory=lambda: np.empty(0))
    forced_steps: np.ndarray = field(default_factory=lambda: np.empty(0))
    sim_time_us: np.ndarray = field(default_factory=lambda: np.empty(0))
    final_backlog: np.ndarray = field(default_factory=lambda: np.empty(0))

    # -- derived ---------------------------------------------------------------
    @property
    def cpu_fraction(self) -> np.ndarray:
        return self.awake_us / self.cfg.duration_us

    @property
    def loss_fraction(self) -> np.ndarray:
        return self.dropped / np.maximum(self.offered, 1.0)

    @property
    def mean_latency_us(self) -> np.ndarray:
        """Little's-law mean sojourn: queue-depth integral over departures."""
        return self.lat_area / np.maximum(self.serviced, 1.0)

    @property
    def mean_vacation_us(self) -> np.ndarray:
        return self.vac_sum / np.maximum(self.cycles, 1.0)

    @property
    def mean_nv(self) -> np.ndarray:
        return self.nv_sum / np.maximum(self.cycles, 1.0)

    @property
    def energy_per_packet_nj(self) -> np.ndarray:
        return 1e3 * self.energy_uj / np.maximum(self.serviced, 1.0)

    @property
    def mean_power_w(self) -> np.ndarray:
        return self.energy_uj / self.cfg.duration_us

    @property
    def rho(self) -> np.ndarray:
        return self.grid.rate_mpps / self.cfg.service_rate_mpps

    def reshaped(self, name: str) -> np.ndarray:
        val = getattr(self, name)
        return np.asarray(val).reshape(self.grid.shape)

    def _schedule(self, i: int):
        # mirror _schedule_rows' precedence exactly: a None row inside a
        # scheduled grid falls back to the batch-wide config schedule
        # (which is what the kernel simulated for that row)
        if self.grid.schedules and self.grid.schedules[i] is not None:
            return self.grid.schedules[i]
        return self.cfg.schedule

    def windows(self, i: int) -> WindowedSeries | None:
        """Point ``i``'s windowed series — the same accumulator
        convention (and therefore the same derived-metric /
        ``TrackingStats`` code path) as the event engine's.  ``None``
        when the run was not windowed (``cfg.window_us == 0``).  The
        slot engine keeps no latency samples, so per-window p99 is NaN
        and there is no controller estimate (static timeouts: the grid,
        not a controller, is the adaptation space)."""
        if self.win.size == 0:
            return None
        w = self.win[i]
        return WindowedSeries(
            window_us=float(self.cfg.window_us),
            service_rate_mpps=self.cfg.service_rate_mpps,
            offered=w[:, 0].copy(), served=w[:, 1].copy(),
            lat_area_us=w[:, 2].copy(), awake_us=w[:, 3].copy(),
            energy_uj=w[:, 4].copy())

    def tracking(self, i: int, target_latency_us: float, **kw):
        """``TrackingStats`` for point ``i`` against its schedule's
        transitions — identical computation to the event engine's
        ``stats.windows.tracking(...)``."""
        ws = self.windows(i)
        if ws is None:
            raise ValueError("run was not windowed: set cfg.window_us")
        sched = self._schedule(i)
        trans = (sched.transitions(self.cfg.duration_us)
                 if sched is not None else ())
        return ws.tracking(trans, target_latency_us, **kw)

    def to_run_stats(self, i: int) -> RunStats:
        p = self.grid.point(i)
        mean = float(self.mean_latency_us[i])
        cap = self.cfg.queue_capacity * max(int(p["n_queues"]), 1)
        sched = self._schedule(i)
        return RunStats(
            backend="batched",
            policy=(f"sleepwake(t_s={p['t_s_us']:g},t_l={p['t_l_us']:g},"
                    f"m={p['m']})"),
            workload=f"poisson({p['rate_mpps']:g})",
            schedule=sched.descriptor() if sched is not None else "",
            wakeups=int(self.wakeups[i]), cycles=int(self.cycles[i]),
            busy_tries=int(self.busy_tries[i]),
            items=int(self.serviced[i]), offered=int(self.offered[i]),
            dropped=int(self.dropped[i]),
            awake_ns=round(self.awake_us[i] * 1e3), started_ns=0,
            stopped_ns=round(self.cfg.duration_us * 1e3),
            latency_us=Reservoir(4, seed=int(p["seed"])),
            latency_area_us=float(self.lat_area[i]),
            energy_uj=float(self.energy_uj[i]),
            # no per-packet samples in the slot engine: mean is measured
            # (Little), p99/worst are coarse analytic estimates
            latency_override={
                "mean": mean,
                "p99": mean * 3.0,
                "worst": float(cap / self.cfg.service_rate_mpps
                               + p["t_l_us"]),
            },
            # no per-queue counter breakdown in the slot engine's
            # aggregate stats: leave per_queue empty rather than emit
            # all-zero slices that would break the sums-to-total law
            per_queue=[],
            windows=self.windows(i),
            vacations_us=np.asarray([self.mean_vacation_us[i]]),
            busies_us=np.asarray([self.serviced[i]
                                  / self.cfg.service_rate_mpps
                                  / max(self.cycles[i], 1.0)]),
            n_v=np.asarray([self.mean_nv[i]]),
        )

    def __len__(self) -> int:
        return len(self.grid)


def _build_sweep(n_slots: int, slot_us: float, m_max: int, q_max: int,
                 mu: float, capacity: float, wake_cost_us: float,
                 sleep_params: tuple, interference_params: tuple,
                 energy_params: tuple,
                 n_seg: int = 0, n_windows: int = 0,
                 window_us: float = 0.0):
    """Build + jit the vmapped fixed-slot kernel for one static shape.

    ``n_seg > 0`` compiles the nonstationary variant: each point carries
    a piecewise-constant load schedule as ``(edges, scales)`` rows of
    width ``n_seg``, looked up per slot (the arrival rate becomes
    ``lam * scale(now)``).  ``n_windows > 0`` additionally accumulates
    the per-window [offered, served, lat_area, awake, energy] sums the
    adaptation-tracking layer consumes (same convention as the event
    engine's ``WindowAccum``).  ``energy_params`` is the static
    ``EnergyModel.params()`` tuple; per-arm C-state charges are
    closed-form per point (the targets T_S/T_L are per-point traced
    scalars), so the energy column costs one fused multiply-add per
    slot."""
    base_us, slope, sigma_us, tail_prob, tail_mean_us = sleep_params
    intf_prob, intf_mean_us, stall_rate, stall_mean_us = interference_params
    active_power_w, _dvfs_scale, e_states = energy_params
    # exact per-slot hit probability of the Poisson stall-start process
    stall_p = 1.0 - math.exp(-stall_rate * slot_us) if stall_rate else 0.0
    dt = slot_us
    t_idx = jnp.arange(m_max)
    q_idx = jnp.arange(q_max)

    def one_point(t_s, t_l, m, nq, lam, seed_lo, seed_hi, duration,
                  sched_edges, sched_scales):
        tmask = t_idx < m
        qmask = q_idx < nq
        lam_q = jnp.where(qmask, lam / nq, 0.0)
        # per-arm energy of the point's two sleep classes — the C-state
        # follows the programmed target (event-engine convention), so
        # both charges are point constants hoisted out of the scan
        e_arm_s = energy_arm_cost(t_s, e_states)
        e_arm_l = energy_arm_cost(t_l, e_states)

        # both 32-bit halves of the 64-bit seed are folded in, so seeds
        # differing only in their high bits stay independent
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), seed_lo), seed_hi)
        key, k0 = jax.random.split(key)
        # active launch (event-engine convention): first wakes land
        # uniformly inside one primary timeout, not spread over T_L
        sleep0 = jax.random.uniform(k0, (m_max,)) * t_s
        sleep0 = jnp.where(tmask, jnp.maximum(sleep0, dt), jnp.inf)

        def step(carry, t):
            prev = carry
            (sleep_rem, attached, backlog, vac_timer, arr_res, stall_end,
             S, win_acc) = carry
            now = t.astype(jnp.float32) * dt
            # slots at/past the traced duration are carry-preserving
            # no-ops: the scan length is bucketed (bucket_steps), so one
            # compiled kernel serves every nearby duration
            live = now < duration
            with jax.named_scope("rng"):
                kt_step = jax.random.fold_in(key, t)
                if tail_prob > 0.0:
                    kt_step, kp, ku = jax.random.split(kt_step, 3)
                if intf_prob > 0.0:
                    kt_step, kip, kie = jax.random.split(kt_step, 3)
                if stall_p > 0.0:
                    kt_step, ksp, kse, ksu = jax.random.split(kt_step, 4)
                # one fused normal draw covers arrivals + sleep noise
                zs = jax.random.normal(kt_step, (q_max + m_max,))

            # correlated stall windows: Bernoulli(1-exp(-rate*dt)) opens
            # an Exp(stall_mean)-long system-wide freeze; overlapping
            # windows extend (max), like the event engine's lazy merge
            with jax.named_scope("stall"):
                if stall_p > 0.0:
                    hit_s = jax.random.uniform(ksp, ()) < stall_p
                    win = now + stall_mean_us * jax.random.exponential(
                        kse, ())
                    stall_end = jnp.where(
                        hit_s, jnp.maximum(stall_end, win), stall_end)

            # 1. arrivals: residual-carried Gaussian fluid ~ Poisson,
            # rate modulated by the point's load schedule when one is
            # compiled in (piecewise-constant scale looked up per slot)
            with jax.named_scope("arrivals"):
                if n_seg > 0:
                    si = jnp.clip(
                        jnp.searchsorted(sched_edges, now, side="right") - 1,
                        0, n_seg - 1)
                    mu_a = lam_q * sched_scales[si] * dt
                else:
                    mu_a = lam_q * dt
                raw = arr_res + mu_a + jnp.sqrt(mu_a) * zs[:q_max]
                a = jnp.maximum(raw, 0.0)
                arr_res = jnp.minimum(raw, 0.0)      # deficit carried forward
                room = jnp.maximum(capacity - backlog, 0.0)
                adm = jnp.minimum(a, room)
                backlog = backlog + adm
                offered = a.sum()
                dropped = (a - adm).sum()

            # sleep overshoot draws for this slot (one per thread;
            # static zeros when the model is exact)
            with jax.named_scope("overshoot"):
                over = jnp.full((m_max,), base_us)
                if sigma_us > 0.0:
                    over = over + sigma_us * jnp.abs(zs[q_max:])
                if tail_prob > 0.0:
                    hit = jax.random.uniform(kp, (m_max,)) < tail_prob
                    over = over + hit * tail_mean_us * jax.random.exponential(
                        ku, (m_max,))
                # per-wake OS interference (paper Sec 5.6): each re-sleep is
                # lengthened by Exp(mean) w.p. q — one independent draw per
                # thread per slot, charged only on the slots where a thread
                # actually re-arms (the same per-sleep draw the event engine
                # makes after sampling the sleep model)
                if intf_prob > 0.0:
                    ihit = jax.random.uniform(kip, (m_max,)) < intf_prob
                    over = over + ihit * intf_mean_us * jax.random.exponential(
                        kie, (m_max,))
                slp_s = t_s * (1.0 + slope) + over
                slp_l = t_l * (1.0 + slope) + over

            # 2. countdown + wake + claim (threads in index order)
            with jax.named_scope("wake_claim"):
                sleeping = tmask & (attached < 0)
                sleep_rem = jnp.where(sleeping, sleep_rem - dt, sleep_rem)
                woken = sleeping & (sleep_rem <= 0.0)
                if stall_p > 0.0:
                    # timers expiring inside an open stall window defer to its
                    # end (+U(0,1)us re-arm jitter) and are NOT counted as
                    # wakes — the event engine's deferred-wake semantics
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
                    nv_sum = nv_sum + jnp.where(do_attach, backlog[qi], 0.0)
                    vac_timer = jnp.where(claim_any, 0.0, vac_timer)
                    cycles = cycles + (do_attach | empty_claim)
                    busy_tries = busy_tries + blocked
                    ts_arm = ts_arm + empty_claim
                    attached = attached.at[i].set(
                        jnp.where(do_attach, qi, attached[i]))
                    occ = occ | claim_hot
                    # re-sleep adds onto the (negative) expired-timer
                    # residual: removes the slot-quantization wake-rate bias
                    sleep_rem = sleep_rem.at[i].add(
                        jnp.where(empty_claim, slp_s[i],
                                  jnp.where(blocked, slp_l[i], 0.0)))

            # 3. locked queues drain at mu for the slot
            with jax.named_scope("drain"):
                serve = jnp.where(occ, jnp.minimum(backlog, mu * dt), 0.0)
                backlog = backlog - serve
                served = serve.sum()

            # 4. emptied queues release their thread (fresh T_S sleep)
            with jax.named_scope("release"):
                q_done = occ & (backlog <= 1e-6)
                att_q = jnp.clip(attached, 0, q_max - 1)
                t_done = (attached >= 0) & q_done[att_q]
                ts_arm = ts_arm + t_done.sum()
                sleep_rem = jnp.where(t_done, slp_s, sleep_rem)
                attached = jnp.where(t_done, -1, attached)
                occ = occ & ~q_done

            # 5. vacations tick on unlocked queues; 6. Little integral
            with jax.named_scope("accumulate"):
                vac_timer = vac_timer + jnp.where(qmask & ~occ, dt, 0.0)
                lat_area = backlog.sum() * dt

                # energy: active power over the slot's awake time plus the
                # per-arm C-state charges (blocked wakes re-arm T_L)
                awake_step = n_wake * wake_cost_us + served / mu
                energy_step = (active_power_w * awake_step
                               + ts_arm * e_arm_s + busy_tries * e_arm_l)

                S = _SlotStats(
                    offered=S.offered + offered,
                    dropped=S.dropped + dropped,
                    serviced=S.serviced + served,
                    wakeups=S.wakeups + n_wake,
                    busy_tries=S.busy_tries + busy_tries,
                    cycles=S.cycles + cycles,
                    awake_us=S.awake_us + awake_step,
                    lat_area=S.lat_area + lat_area,
                    vac_sum=S.vac_sum + vac_sum,
                    nv_sum=S.nv_sum + nv_sum,
                    ts_arms=S.ts_arms + ts_arm,
                    energy_uj=S.energy_uj + energy_step,
                )
                if n_windows > 0:
                    # the event engine's WindowAccum convention: raw
                    # [offered, served, lat_area, awake, energy] per window
                    w = jnp.minimum((now / window_us).astype(jnp.int32),
                                    n_windows - 1)
                    win_acc = win_acc.at[w].add(jnp.stack([
                        offered, served, lat_area, awake_step, energy_step]))
            with jax.named_scope("gate"):
                nxt = (sleep_rem, attached, backlog, vac_timer, arr_res,
                       stall_end, S, win_acc)
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
                _SlotStats(z0, z0, z0, z0, z0, z0, z0, z0, z0, z0, z0, z0),
                jnp.zeros((max(n_windows, 1), 5), jnp.float32))
        (_, _, backlog_f, _, _, _, S, win_acc), _ = jax.lax.scan(
            step, init, jnp.arange(n_slots, dtype=jnp.int32))
        return S, win_acc, backlog_f.sum()

    def fixed_sweep(*points):
        return jax.vmap(one_point)(*points)

    # the function's name is the compiled module's: jit_fixed_sweep
    return jax.jit(fixed_sweep)


_compiled_sweep = CompileCache(_build_sweep, maxsize=64,
                               name="batched._compiled_sweep")


_EVENT_ENGINE_ONLY_FIELDS = ("timeseries_bin_us",)
# SimRunConfig fields this engine deliberately does NOT read, named so
# the engine-parity static check (repro.analysis, PARITY001/002) can
# prove the drift guard complete instead of trusting it:
#   - grid-supplied: seed and n_queues come per-point from the
#     SweepGrid row (the grid axis IS the sweep surface; cfg.seed /
#     cfg.n_queues are event-engine inputs only);
#   - sample-path detail: the fixed-slot engine keeps no latency
#     reservoir, so its size knob has no fixed-slot meaning.
_GRID_SUPPLIED_FIELDS = ("seed", "n_queues")
_NO_SAMPLE_PATH_FIELDS = ("latency_reservoir",)


def unsupported_config_fields(cfg: SimRunConfig) -> list[str]:
    """``SimRunConfig`` fields set to values the batched engine cannot
    honor.  Empty list = the config is fully batched-simulable."""
    return [f for f in _EVENT_ENGINE_ONLY_FIELDS if getattr(cfg, f)]


def validate_batched_config(cfg: SimRunConfig) -> None:
    """Raise eagerly — before any compilation or sweep work — if ``cfg``
    sets fields only the event engine honors, naming each offending
    field (so config errors surface at construction sites such as
    ``build_operating_table``, not as a generic mid-run failure)."""
    bad = unsupported_config_fields(cfg)
    if bad:
        raise ValueError(
            "SimRunConfig field(s) not supported by the batched engine: "
            + ", ".join(f"{f}={getattr(cfg, f)!r}" for f in bad)
            + "; use repro.runtime.sim.simulate_run for those studies")


def _schedule_rows(grid: SweepGrid, cfg: SimRunConfig
                   ) -> tuple[int, np.ndarray, np.ndarray]:
    """Compile the batch's load schedules to fixed-width
    ``(edges, scales)`` rows.  Per-point grid schedules win over the
    config-wide one; ``(0, trivial, trivial)`` when every point is
    stationary (the kernel then skips the lookup entirely)."""
    n = len(grid)
    if grid.schedules:
        scheds = list(grid.schedules)
        if cfg.schedule is not None:
            scheds = [s if s is not None else cfg.schedule for s in scheds]
    elif cfg.schedule is not None:
        scheds = [cfg.schedule] * n
    else:
        return 0, np.zeros((n, 1)), np.ones((n, 1))
    n_seg = 1
    for s in scheds:
        if s is not None:
            n_seg = max(n_seg, len(s.segments(cfg.duration_us)[0]))
    n_seg = min(n_seg, _MAX_SCHED_SEGMENTS)
    edges = np.zeros((n, n_seg))
    scales = np.ones((n, n_seg))
    for i, s in enumerate(scheds):
        if s is None:       # stationary row inside a scheduled batch
            edges[i] = np.concatenate(
                [[0.0], cfg.duration_us + 1.0 + np.arange(n_seg - 1)])
        else:
            edges[i], scales[i] = s.compiled(cfg.duration_us, n_seg)
    return n_seg, edges, scales


def simulate_batch(grid: SweepGrid, cfg: SimRunConfig | None = None, *,
                   slot_us: float = 0.5,
                   stepping: str = "fixed") -> BatchStats:
    """Simulate every operating point in ``grid`` — one JIT-compiled,
    vmapped call over the whole batch.

    ``cfg`` supplies the environment (duration, mu, per-queue capacity,
    sleep model, wake cost, OS interference / correlated stalls, load
    schedule, window size); per-point knobs (T_S, T_L, M, n_queues,
    offered Poisson rate, seed, schedule) come from the grid and
    override the config's.  ``cfg.window_us > 0`` turns on the windowed
    adaptation series (``BatchStats.windows(i)``).  Binned time series
    remain event-engine-only and raise (``validate_batched_config``).

    ``stepping`` selects the kernel: ``"fixed"`` (default) scans
    uniform ``slot_us`` slots; ``"adaptive"`` scans event-jump
    macro-slots (see ``batched_adaptive``) — same statistics, same
    parity bands, scan length proportional to event count instead of
    simulated time.

    Spans (``repro.runtime.tracing``): ``batched.sweep`` around the
    call, with counts ``points``, ``scan_steps`` and ``built`` (1 when
    the kernel for this shape was built, and so compiled, by this
    call); inside it ``batched.scan`` (the device scan, until its
    outputs are ready) and ``batched.fetch`` (device-to-host copies and
    ``BatchStats``).  Input staging is the sweep's self time.
    """
    cfg = cfg or SimRunConfig()
    validate_batched_config(cfg)
    if stepping not in ("fixed", "adaptive"):
        raise ValueError(
            f"stepping must be 'fixed' or 'adaptive', got {stepping!r}")
    n = len(grid)
    n_windows = (int(math.ceil(cfg.duration_us / cfg.window_us))
                 if cfg.window_us > 0 else 0)
    n_slots_true = max(int(math.ceil(cfg.duration_us / slot_us)), 1)
    with tracing.span("batched.sweep", points=n, built=0) as sp:
        n_seg, sched_edges, sched_scales = _schedule_rows(grid, cfg)
        if stepping == "adaptive":
            from .batched_adaptive import adaptive_kernel
            fn, scan_len = adaptive_kernel(grid, cfg, float(slot_us), n_seg,
                                           n_windows)
        else:
            scan_len = bucket_steps(n_slots_true)
            sm = cfg.sleep_model
            fn = _compiled_sweep(
                scan_len, float(slot_us), int(grid.m.max()),
                int(grid.n_queues.max()),
                float(cfg.service_rate_mpps), float(cfg.queue_capacity),
                float(cfg.wake_cost_us),
                (float(sm.base_us), float(sm.slope), float(sm.sigma_us),
                 float(sm.tail_prob), float(sm.tail_mean_us)),
                (float(cfg.interference_prob),
                 float(cfg.interference_mean_us),
                 float(cfg.stall_rate_per_us), float(cfg.stall_mean_us)),
                cfg.energy_model.params(),
                n_seg, bucket_steps(n_windows, base=8) if n_windows else 0,
                float(cfg.window_us))
        sp.counts["scan_steps"] = scan_len
        seed64 = np.asarray(grid.seed, dtype=np.uint64)
        args = (jnp.asarray(grid.t_s_us, jnp.float32),
                jnp.asarray(grid.t_l_us, jnp.float32),
                jnp.asarray(grid.m, jnp.int32),
                jnp.asarray(grid.n_queues, jnp.int32),
                jnp.asarray(grid.rate_mpps, jnp.float32),
                jnp.asarray((seed64 & np.uint64(0xFFFFFFFF))
                            .astype(np.uint32)),
                jnp.asarray((seed64 >> np.uint64(32)).astype(np.uint32)),
                # staged from host memory like the other inputs: an eager
                # jnp.full can re-trace on every call (JAX 0.9) once a
                # broadcast to the same shape from another operand ran
                # first in the process, a compile inside a timed window
                jnp.asarray(np.full(n, cfg.duration_us, np.float32)),
                jnp.asarray(sched_edges, jnp.float32),
                jnp.asarray(sched_scales, jnp.float32))
        with tracing.span("batched.scan"):
            out, win, back_f, *simt = jax.block_until_ready(fn(*args))
        with tracing.span("batched.fetch"):
            vals = {k: np.asarray(v, dtype=np.float64)
                    for k, v in out._asdict().items()}
            if stepping == "adaptive":
                n_steps = vals.pop("n_steps")
                forced_steps = vals.pop("forced_steps")
                sim_time_us = np.asarray(simt[0], dtype=np.float64)
            else:
                n_steps = np.full(n, float(n_slots_true))
                forced_steps = np.zeros(n)
                # fixed slots overshoot duration by the ceil slot
                sim_time_us = np.full(n, n_slots_true * slot_us)
            return BatchStats(
                grid=grid, cfg=cfg, slot_us=float(slot_us),
                win=(np.asarray(win, dtype=np.float64)[:, :n_windows]
                     if n_windows else np.empty(0)),
                stepping=stepping, scan_len=scan_len, n_steps=n_steps,
                forced_steps=forced_steps, sim_time_us=sim_time_us,
                final_backlog=np.asarray(back_f, dtype=np.float64), **vals)
