"""Batched fixed-slot JAX simulation engine: thousands of configs per call.

The event engine (``repro.runtime.sim``) is exact but serial — one
(policy, workload, environment) point per call, at interpreter speed.
This engine trades per-event exactness for *throughput*: it discretizes
time into fixed slots, and advances every poller thread and Rx queue
one slot at a time with pure array ops over a whole ``SweepGrid`` of
operating points — (T_S, T_L, M, n_queues, offered load, seed), the
lanes of every array — so a dense parameter sweep is one JIT-compiled
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

Scan shape.  Every quantization caveat in this docstring is a
statement about the per-slot update at ``slot_us`` resolution, with two
refinements of the scan's shape:

  - the scan length is the slot count rounded *up* a geometric ladder
    (``bucket_steps``) and the run duration is a traced input, so
    nearby durations share one compiled kernel (slots past a point's
    duration are carry-preserving no-ops) — numerics are unchanged,
    only recompile churn is;
  - wake-timer / busy-period / stall-window quantization is always
    ``slot_us`` regardless of the padded scan length.

The slot body is written once, lanes-minor (the points are the lanes),
and has two drivers chosen from the backend and the static build: on a
TPU a stationary build without windows runs its slots K at a time in
one Pallas kernel (``repro.kernels.fixed_scan``), with the state kept
on the core; otherwise a ``lax.scan`` runs them one at a time.  Both
draw from the same ``jax.random`` calls and give the same bits.
"""

from __future__ import annotations

import functools
import logging
import math
import operator
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
    # the compiled scan length, and per-point live slots, simulated
    # time, and end-of-run total backlog (the missing term of the
    # offered = served + dropped + backlog law)
    scan_len: int = 0
    n_steps: np.ndarray = field(default_factory=lambda: np.empty(0))
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


class _Point(NamedTuple):
    """A point's constants, one row each (an array over the points)."""
    m: jnp.ndarray             # threads
    nq: jnp.ndarray            # queues
    mu_a: tuple                # per queue: mean arrivals a slot (0 past nq)
    sd_a: tuple                # per queue: sqrt(mu_a), their deviation
    slp_s: jnp.ndarray         # T_S * (1 + slope)
    slp_l: jnp.ndarray         # T_L * (1 + slope)
    e_arm_s: jnp.ndarray       # energy of a T_S arm
    e_arm_l: jnp.ndarray       # energy of a T_L arm
    duration: jnp.ndarray


class _Lanes(NamedTuple):
    """The scan's state, lanes-minor: one row per thread, queue or
    counter, each row an array over the points."""
    sleep_rem: tuple           # per thread: time left on its timer
    attached: tuple            # per thread: the queue it drains, -1 none
    backlog: tuple             # per queue
    vac_timer: tuple           # per queue: time since it was last claimed
    arr_res: tuple             # per queue: arrival deficit carried forward
    stall_end: jnp.ndarray     # end of the open stall window, -1 none
    S: _SlotStats


class _Draws(NamedTuple):
    """One slot's random inputs, one row each."""
    z: tuple                   # per queue: N(0, 1) for the arrivals
    over: tuple                # per thread: sleep overshoot
    stall_len: jnp.ndarray | None  # a window opening now: its length; -inf
    jitter: tuple              # per thread: U(0, 1) re-arm after a stall


def _fold(xs):
    """Left fold of ``+`` in index order: a sum over threads or queues."""
    return functools.reduce(operator.add, xs)


def _any(xs):
    return functools.reduce(operator.or_, xs)


def _fused(n_seg: int, n_windows: int) -> bool:
    """Whether the Pallas kernel (``repro.kernels.fixed_scan``) runs the
    slots: on a TPU, for a stationary build without windows.  Elsewhere
    a ``lax.scan`` runs the same slot body."""
    return jax.default_backend() == "tpu" and not n_seg and not n_windows


# the chunk's draws, in the kernel's two pipeline buffers, fit this much
# VMEM; a chunk is at most _MAX_CHUNK slots
_DRAW_VMEM = 16 << 20
_MAX_CHUNK = 1024


def _build_sweep(n_slots: int, slot_us: float, m_max: int, q_max: int,
                 mu: float, capacity: float, wake_cost_us: float,
                 sleep_params: tuple, interference_params: tuple,
                 energy_params: tuple,
                 n_seg: int = 0, n_windows: int = 0,
                 window_us: float = 0.0, fused: bool = False):
    """Build + jit the fixed-slot kernel for one static shape.

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
    slot.

    The slot body is written once, lanes-minor: every per-thread and
    per-queue quantity is its own row over the points, and the threads
    and queues are short static loops.  ``fused`` picks its driver (see
    ``_fused``): the Pallas kernel, K slots a call inside a ``lax.scan``
    over chunks, with each chunk's draws made first by the same
    ``jax.random`` calls; or a ``lax.scan`` over slots, which also
    serves schedules and windows.  Both give the same bits."""
    base_us, slope, sigma_us, tail_prob, tail_mean_us = sleep_params
    intf_prob, intf_mean_us, stall_rate, stall_mean_us = interference_params
    active_power_w, _dvfs_scale, e_states = energy_params
    # exact per-slot hit probability of the Poisson stall-start process
    stall_p = 1.0 - math.exp(-stall_rate * slot_us) if stall_rate else 0.0
    dt = slot_us
    t_idx = jnp.arange(m_max)
    q_idx = jnp.arange(q_max)

    def point(t_s, t_l, m, nq, lam, seed_lo, seed_hi):
        """One point's key, first timers, arrival rates and arm costs."""
        tmask = t_idx < m
        lam_q = jnp.where(q_idx < nq, lam / nq, 0.0)
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
        return key, sleep0, lam_q, e_arm_s, e_arm_l

    def draw(key, t):
        """One point's draws for slot ``t``: arrival normals (one per
        queue), overshoots (one per thread), the stall window and the
        re-arm jitter."""
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
        # an Exp(stall_mean)-long system-wide freeze
        stall_len, jitter = None, ()
        with jax.named_scope("stall"):
            if stall_p > 0.0:
                stall_len = jnp.where(
                    jax.random.uniform(ksp, ()) < stall_p,
                    stall_mean_us * jax.random.exponential(kse, ()),
                    -jnp.inf)
                jitter = jax.random.uniform(ksu, (m_max,))

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
        return zs[:q_max], over, stall_len, jitter

    def slot(c, s, d, t):
        """Slot ``t`` of every point: the new state, the slot's
        [offered, served, lat_area, awake, energy] and whether it was
        live."""
        now = t.astype(jnp.float32) * dt
        # slots at/past the traced duration are carry-preserving
        # no-ops: the scan length is bucketed (bucket_steps), so one
        # compiled kernel serves every nearby duration
        live = (now < c.duration) & (t < n_slots)
        tmask = [c.m > i for i in range(m_max)]
        qmask = [c.nq > q for q in range(q_max)]

        # overlapping stall windows extend (max), like the event
        # engine's lazy merge
        stall_end = s.stall_end
        with jax.named_scope("stall"):
            if stall_p > 0.0:
                stall_end = jnp.maximum(stall_end, now + d.stall_len)

        # 1. arrivals: residual-carried Gaussian fluid ~ Poisson
        with jax.named_scope("arrivals"):
            raw = [r + mu_a + sd * z for r, mu_a, sd, z
                   in zip(s.arr_res, c.mu_a, c.sd_a, d.z)]
            a = [jnp.maximum(x, 0.0) for x in raw]
            arr_res = tuple(jnp.minimum(x, 0.0) for x in raw)  # deficit
            adm = [jnp.minimum(x, jnp.maximum(capacity - b, 0.0))
                   for x, b in zip(a, s.backlog)]
            backlog = [b + x for b, x in zip(s.backlog, adm)]
            offered = _fold(a)
            dropped = _fold([x - y for x, y in zip(a, adm)])

        with jax.named_scope("overshoot"):
            slp_s = [c.slp_s + o for o in d.over]
            slp_l = [c.slp_l + o for o in d.over]

        # 2. countdown + wake + claim (threads in index order)
        with jax.named_scope("wake_claim"):
            sleep_rem, woken = [], []
            for i in range(m_max):
                sleeping = tmask[i] & (s.attached[i] < 0)
                r = jnp.where(sleeping, s.sleep_rem[i] - dt, s.sleep_rem[i])
                w = sleeping & (r <= 0.0)
                if stall_p > 0.0:
                    # timers expiring inside an open stall window defer to
                    # its end (+U(0,1)us re-arm jitter) and are NOT counted
                    # as wakes — the event engine's deferred-wake semantics
                    push = w & (now < stall_end)
                    w = w & ~push
                    r = jnp.where(push, stall_end - now + d.jitter[i], r)
                sleep_rem.append(r)
                woken.append(w)
            n_wake = _fold([jnp.where(w, 1.0, 0.0) for w in woken])

            attached = list(s.attached)
            vac = list(s.vac_timer)
            occ = [_any([a == q for a in attached]) for q in range(q_max)]
            busy_tries = cycles = vac_sum = nv_sum = ts_arm = 0.0
            for i in range(m_max):
                w = woken[i]
                free = [qm & ~o for qm, o in zip(qmask, occ)]
                claimable = [f & (b >= 1.0) for f, b in zip(free, backlog)]
                # the claimable queue with the longest backlog, the
                # first of equals
                best = jnp.where(claimable[0], backlog[0], -1.0)
                qi = jnp.zeros_like(attached[i])
                for q in range(1, q_max):
                    v = jnp.where(claimable[q], backlog[q], -1.0)
                    longer = v > best
                    qi = jnp.where(longer, q, qi)
                    best = jnp.where(longer, v, best)
                # the first free (empty) queue
                first_free = [free[0]]
                for q in range(1, q_max):
                    first_free.append(free[q] & ~_any(free[:q]))
                any_claimable, any_free = _any(claimable), _any(free)
                do_attach = w & any_claimable
                empty_claim = w & ~any_claimable & any_free
                blocked = w & ~any_free

                claim_hot = [do_attach & (qi == q) for q in range(q_max)]
                claim_any = [h | (empty_claim & f)
                             for h, f in zip(claim_hot, first_free)]
                # one nonzero term at most, so each sum is exact
                vac_sum = vac_sum + _fold(
                    [jnp.where(h, v, 0.0) for h, v in zip(claim_any, vac)])
                nv_sum = nv_sum + _fold(
                    [jnp.where(h, b, 0.0) for h, b in zip(claim_hot, backlog)])
                vac = [jnp.where(h, 0.0, v) for h, v in zip(claim_any, vac)]
                cycles = cycles + jnp.where(do_attach | empty_claim, 1.0, 0.0)
                busy_tries = busy_tries + jnp.where(blocked, 1.0, 0.0)
                ts_arm = ts_arm + jnp.where(empty_claim, 1.0, 0.0)
                attached[i] = jnp.where(do_attach, qi, attached[i])
                occ = [o | h for o, h in zip(occ, claim_hot)]
                # re-sleep adds onto the (negative) expired-timer
                # residual: removes the slot-quantization wake-rate bias
                sleep_rem[i] = sleep_rem[i] + jnp.where(
                    empty_claim, slp_s[i], jnp.where(blocked, slp_l[i], 0.0))

        # 3. locked queues drain at mu for the slot
        with jax.named_scope("drain"):
            serve = [jnp.where(o, jnp.minimum(b, mu * dt), 0.0)
                     for o, b in zip(occ, backlog)]
            backlog = [b - x for b, x in zip(backlog, serve)]
            served = _fold(serve)

        # 4. emptied queues release their thread (fresh T_S sleep)
        with jax.named_scope("release"):
            q_done = [o & (b <= 1e-6) for o, b in zip(occ, backlog)]
            t_done = [_any([(a == q) & qd for q, qd in enumerate(q_done)])
                      for a in attached]
            ts_arm = ts_arm + _fold([jnp.where(x, 1.0, 0.0) for x in t_done])
            sleep_rem = [jnp.where(x, ss, r)
                         for x, ss, r in zip(t_done, slp_s, sleep_rem)]
            attached = [jnp.where(x, -1, a) for x, a in zip(t_done, attached)]
            occ = [o & ~qd for o, qd in zip(occ, q_done)]

        # 5. vacations tick on unlocked queues; 6. Little integral
        with jax.named_scope("accumulate"):
            vac = [v + jnp.where(qm & ~o, dt, 0.0)
                   for v, qm, o in zip(vac, qmask, occ)]
            lat_area = _fold(backlog) * dt

            # energy: active power over the slot's awake time plus the
            # per-arm C-state charges (blocked wakes re-arm T_L)
            awake_step = n_wake * wake_cost_us + served / mu
            energy_step = (active_power_w * awake_step
                           + ts_arm * c.e_arm_s + busy_tries * c.e_arm_l)

            S = s.S
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
        with jax.named_scope("gate"):
            nxt = _Lanes(tuple(sleep_rem), tuple(attached), tuple(backlog),
                         tuple(vac), arr_res, stall_end, S)
            gated = jax.tree_util.tree_map(
                lambda new, old: jnp.where(live, new, old), nxt, s)
        return gated, (offered, served, lat_area, awake_step,
                       energy_step), live

    def slot_draws(z, over, stall_len, jitter):
        """``_Draws`` from one slot's draws, ``(..., points, ...)``."""
        return _Draws(
            z=tuple(z[..., q] for q in range(q_max)),
            over=tuple(over[..., i] for i in range(m_max)),
            stall_len=stall_len,
            jitter=tuple(jitter[..., i] for i in range(m_max))
            if stall_p > 0.0 else ())

    def scan_slots(c, s0, keys, lam_q, sched_edges, sched_scales):
        n = keys.shape[0]

        def step(carry, t):
            s, win_acc = carry
            draws = jax.vmap(draw, in_axes=(0, None))(keys, t)
            # the load schedule, when one is compiled in, scales each
            # point's arrival rate by its piecewise-constant segment
            cs = c
            with jax.named_scope("arrivals"):
                if n_seg > 0:
                    now = t.astype(jnp.float32) * dt
                    scale = jax.vmap(lambda e, sc: sc[jnp.clip(
                        jnp.searchsorted(e, now, side="right") - 1,
                        0, n_seg - 1)])(sched_edges, sched_scales)
                    mu_a = tuple(lam_q[:, q] * scale * dt
                                 for q in range(q_max))
                    cs = c._replace(mu_a=mu_a,
                                    sd_a=tuple(jnp.sqrt(x) for x in mu_a))
            s, vals, live = slot(cs, s, slot_draws(*draws), t)
            if n_windows > 0:
                # the event engine's WindowAccum convention: raw
                # [offered, served, lat_area, awake, energy] per window,
                # one array each: stacked inside the loop, they slowed
                # its steps on XLA's CPU backend some two hundredfold
                now = t.astype(jnp.float32) * dt
                w = jnp.minimum((now / window_us).astype(jnp.int32),
                                n_windows - 1)
                win_acc = tuple(
                    jnp.where(live[:, None], a.at[:, w].add(v), a)
                    for a, v in zip(win_acc, vals))
            return (s, win_acc), None

        win0 = (jnp.zeros((n, max(n_windows, 1)), jnp.float32),) * 5
        (s, win_acc), _ = jax.lax.scan(
            step, (s0, win0), jnp.arange(n_slots, dtype=jnp.int32))
        return s, jnp.stack(win_acc, axis=-1)

    def kernel_slots(c, s0, keys):
        from ..kernels.fixed_scan import LANES, ROWS, advance_slots

        tree = jax.tree_util
        n = keys.shape[0]
        lanes = -(-n // (ROWS * LANES)) * ROWS * LANES

        def tiles(rows):
            """Rows over the points as ``(rows, R, 128)`` tiles."""
            x = jnp.pad(jnp.stack(rows), ((0, 0), (0, lanes - n)))
            return x.reshape(len(rows), lanes // LANES, LANES)

        def untile(x):
            return list(x.reshape(x.shape[0], lanes)[:, :n])

        def draw_rows(key, t):
            return jnp.stack(tree.tree_leaves(slot_draws(*draw(key, t))))

        c_rows, c_def = tree.tree_flatten(c)
        f_rows, f_def = tree.tree_flatten(s0._replace(attached=()))
        d_def = tree.tree_structure(jax.eval_shape(
            lambda k: slot_draws(*draw(k, 0)), keys[0]))
        chunk = min(n_slots, _MAX_CHUNK,
                    _DRAW_VMEM // (2 * d_def.num_leaves * ROWS * LANES * 4))
        # padded points draw from a zero key and are never live
        keys = jnp.pad(keys, ((0, lanes - n), (0, 0)))

        def slot_tiles(c_t, f_t, i_t, d_t, t):
            s = tree.tree_unflatten(f_def, f_t)._replace(attached=tuple(i_t))
            s, _, _ = slot(tree.tree_unflatten(c_def, c_t), s,
                           tree.tree_unflatten(d_def, d_t), t)
            return tree.tree_leaves(s._replace(attached=())), list(s.attached)

        def run_chunk(state, j):
            # the chunk's draws, (chunk, rows, points), by the same
            # jax.random calls as the scan's
            t0 = j * chunk
            d = jax.vmap(jax.vmap(draw_rows, in_axes=(0, None), out_axes=1),
                         in_axes=(None, 0))(
                keys, t0 + jnp.arange(chunk, dtype=jnp.int32))
            d = d.reshape(chunk, d_def.num_leaves, lanes // LANES, LANES)
            return advance_slots(slot_tiles, c_tiles, *state, d, t0), None

        c_tiles = tiles(c_rows)
        (f, i), _ = jax.lax.scan(
            run_chunk, (tiles(f_rows), tiles(s0.attached)),
            jnp.arange(-(-n_slots // chunk), dtype=jnp.int32))
        return tree.tree_unflatten(f_def, untile(f))._replace(
            attached=tuple(untile(i)))

    def fixed_sweep(t_s, t_l, m, nq, lam, seed_lo, seed_hi, duration,
                    sched_edges, sched_scales):
        keys, sleep0, lam_q, e_arm_s, e_arm_l = jax.vmap(point)(
            t_s, t_l, m, nq, lam, seed_lo, seed_hi)
        mu_a = tuple(lam_q[:, q] * dt for q in range(q_max))
        c = _Point(m=m.astype(jnp.float32), nq=nq.astype(jnp.float32),
                   mu_a=mu_a, sd_a=tuple(jnp.sqrt(x) for x in mu_a),
                   slp_s=t_s * (1.0 + slope), slp_l=t_l * (1.0 + slope),
                   e_arm_s=e_arm_s, e_arm_l=e_arm_l, duration=duration)
        zero = jnp.zeros_like(t_s)
        s0 = _Lanes(
            sleep_rem=tuple(sleep0[:, i] for i in range(m_max)),
            attached=(jnp.full(t_s.shape, -1, jnp.int32),) * m_max,
            backlog=(zero,) * q_max, vac_timer=(zero,) * q_max,
            arr_res=(zero,) * q_max,
            stall_end=jnp.full(t_s.shape, -1.0, jnp.float32),
            S=_SlotStats(*(zero,) * len(_SlotStats._fields)))
        if fused:
            s = kernel_slots(c, s0, keys)
            win_acc = jnp.zeros((t_s.shape[0], 1, 5), jnp.float32)
        else:
            s, win_acc = scan_slots(c, s0, keys, lam_q, sched_edges,
                                    sched_scales)
        return s.S, win_acc, _fold(s.backlog)

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
                   slot_us: float = 0.5) -> BatchStats:
    """Simulate every operating point in ``grid`` — one JIT-compiled
    call over the whole batch.

    ``cfg`` supplies the environment (duration, mu, per-queue capacity,
    sleep model, wake cost, OS interference / correlated stalls, load
    schedule, window size); per-point knobs (T_S, T_L, M, n_queues,
    offered Poisson rate, seed, schedule) come from the grid and
    override the config's.  ``cfg.window_us > 0`` turns on the windowed
    adaptation series (``BatchStats.windows(i)``).  Binned time series
    remain event-engine-only and raise (``validate_batched_config``).

    Spans (``repro.runtime.tracing``): ``batched.sweep`` around the
    call, with counts ``points``, ``scan_steps`` and ``built`` (1 when
    the kernel for this shape was built, and so compiled, by this
    call); inside it ``batched.scan`` (the device scan, until its
    outputs are ready; count ``fused``, 1 when the Pallas kernel ran the
    slots, 0 for a ``lax.scan``) and ``batched.fetch`` (device-to-host
    copies and ``BatchStats``).  Input staging is the sweep's self time.
    """
    cfg = cfg or SimRunConfig()
    validate_batched_config(cfg)
    n = len(grid)
    n_windows = (int(math.ceil(cfg.duration_us / cfg.window_us))
                 if cfg.window_us > 0 else 0)
    n_slots_true = max(int(math.ceil(cfg.duration_us / slot_us)), 1)
    with tracing.span("batched.sweep", points=n, built=0) as sp:
        n_seg, sched_edges, sched_scales = _schedule_rows(grid, cfg)
        scan_len = bucket_steps(n_slots_true)
        n_win = bucket_steps(n_windows, base=8) if n_windows else 0
        fused = _fused(n_seg, n_win)
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
            n_seg, n_win, float(cfg.window_us), fused)
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
        with tracing.span("batched.scan", fused=int(fused)):
            out, win, back_f = jax.block_until_ready(fn(*args))
        with tracing.span("batched.fetch"):
            vals = {k: np.asarray(v, dtype=np.float64)
                    for k, v in out._asdict().items()}
            return BatchStats(
                grid=grid, cfg=cfg, slot_us=float(slot_us),
                win=(np.asarray(win, dtype=np.float64)[:, :n_windows]
                     if n_windows else np.empty(0)),
                scan_len=scan_len, n_steps=np.full(n, float(n_slots_true)),
                # the slots overshoot the duration by the ceil slot
                sim_time_us=np.full(n, n_slots_true * slot_us),
                final_backlog=np.asarray(back_f, dtype=np.float64), **vals)
