"""End-to-end serving tests: continuous-batching engine + Metronome server
(the paper's architecture on the serving path)."""

import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import MetronomeConfig
from repro.models import Model
from repro.serving import (
    BusyPollServer,
    EngineConfig,
    InferenceEngine,
    MetronomeServer,
    Request,
)

TINY = dataclasses.replace(
    get_config("granite-3-8b").reduced(), n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=101)


def _make_engine(max_slots=4, max_len=64):
    model = Model(TINY)
    params = model.init(jax.random.PRNGKey(0), max_seq=max_len)
    return InferenceEngine(model, params,
                           EngineConfig(max_slots=max_slots, max_len=max_len,
                                        prefill_buckets=(8, 16)))


def test_engine_generates_deterministically_and_matches_decode_path():
    """Engine output == manual prefill+greedy-decode for the same model."""
    eng = _make_engine()
    prompt = [5, 7, 11, 13]
    req = Request(prompt=list(prompt), max_new_tokens=6)
    eng.submit([req])
    eng.pump()
    assert len(req.tokens) == 6

    # manual reference: prefill then greedy decode with the same model
    import jax.numpy as jnp
    model, params = eng.model, eng.params
    logits, cache = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    def pad(leaf):
        if leaf.ndim >= 3 and leaf.shape[2] == len(prompt):
            pw = [(0, 0)] * leaf.ndim
            pw[2] = (0, 64 - len(prompt))
            return jnp.pad(leaf, pw)
        return leaf
    cache = jax.tree.map(pad, cache)
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    decode = jax.jit(model.decode_step)
    for _ in range(5):
        lg, cache = decode(params, jnp.asarray([toks[-1]], jnp.int32), cache,
                           jnp.asarray([pos], jnp.int32))
        toks.append(int(jnp.argmax(lg[0])))
        pos += 1
    assert req.tokens == toks


def test_engine_continuous_batching_isolation():
    """Concurrent requests must not contaminate each other: answers equal
    the same requests served one-at-a-time."""
    solo = []
    for seed in range(3):
        eng = _make_engine()
        req = Request(prompt=[seed + 1, seed + 2, seed + 3], max_new_tokens=5)
        eng.submit([req])
        eng.pump()
        solo.append(req.tokens)

    eng = _make_engine()
    reqs = [Request(prompt=[s + 1, s + 2, s + 3], max_new_tokens=5)
            for s in range(3)]
    eng.submit(reqs)
    eng.pump()
    for r, expect in zip(reqs, solo):
        assert r.tokens == expect


def test_engine_more_requests_than_slots():
    eng = _make_engine(max_slots=2)
    reqs = [Request(prompt=[i + 1, i + 2], max_new_tokens=4) for i in range(5)]
    eng.submit(reqs)
    eng.pump()
    assert all(len(r.tokens) == 4 for r in reqs)
    assert not eng.has_work


def _drive_server(server_cls, n_req=12, rate_hz=60.0, **kw):
    eng = _make_engine(max_slots=4)
    # warm the jit caches (prefill bucket + decode) so retrieval-latency
    # measurements aren't dominated by first-call compilation
    warm = Request(prompt=[1, 2], max_new_tokens=2)
    eng.submit([warm])
    eng.pump()
    srv = server_cls(eng, **kw)
    srv.start()
    reqs = []
    for i in range(n_req):
        r = Request(prompt=[(i % 90) + 1, (i % 90) + 2], max_new_tokens=4)
        assert srv.submit(r)
        reqs.append(r)
        time.sleep(1.0 / rate_hz)
    for r in reqs:
        assert r.wait(timeout=20.0), "request not completed"
    stats = srv.stop()
    return reqs, stats


def test_metronome_server_serves_everything():
    reqs, stats = _drive_server(
        MetronomeServer,
        cfg=MetronomeConfig(m=3, v_target_us=3_000.0, t_long_us=60_000.0))
    assert all(len(r.tokens) == 4 for r in reqs)
    assert stats.busy_periods > 0
    assert 0 < stats.cpu_fraction < 3.0


def test_metronome_server_cpu_below_busy_poll():
    """Paper Fig 12b on the serving path: Metronome's retrieval burns less
    host CPU than the spinning baseline at the same (light) request load,
    with no requests lost."""
    m_reqs, m_stats = _drive_server(
        MetronomeServer, n_req=10, rate_hz=40.0,
        cfg=MetronomeConfig(m=2, v_target_us=4_000.0, t_long_us=80_000.0))
    b_reqs, b_stats = _drive_server(BusyPollServer, n_req=10, rate_hz=40.0)
    assert all(len(r.tokens) == 4 for r in m_reqs + b_reqs)
    assert m_stats.cpu_fraction < b_stats.cpu_fraction


def test_metronome_server_retrieval_latency_tracks_target():
    """Retrieval latency ~ vacation target, not the backup timeout."""
    reqs, stats = _drive_server(
        MetronomeServer, n_req=10, rate_hz=30.0,
        cfg=MetronomeConfig(m=3, v_target_us=2_000.0, t_long_us=100_000.0))
    assert stats.retrieval_lat_us
    med = float(np.median(stats.retrieval_lat_us))
    assert med < 50_000.0, med   # well below T_L; dominated by engine busy time


def test_server_surfaces_an_engine_failure_from_stop():
    """An exception in ``engine.pump()`` ends a poller; the server keeps
    it as ``error`` and ``stop()`` re-raises it."""
    from repro.runtime import MetronomePolicy
    from repro.serving import Server

    eng = _make_engine()

    def broken_pump():
        raise RuntimeError("device lost")

    eng.pump = broken_pump
    srv = Server(eng, MetronomePolicy(
        MetronomeConfig(m=2, v_target_us=1_000.0, t_long_us=20_000.0)))
    srv.start()
    req = Request(prompt=[1, 2], max_new_tokens=2)
    srv.submit(req)
    deadline = time.monotonic() + 10.0
    while srv.error is None and time.monotonic() < deadline:
        time.sleep(0.005)
    assert isinstance(srv.error, RuntimeError)
    assert not req.wait(0.0)
    with pytest.raises(RuntimeError, match="device lost"):
        srv.stop()


def test_server_shards_ingress_across_queues():
    """Multi-queue serving ingress: requests spread across n_queues with
    stable affinity, every request is served, and the per-queue counters
    sum to the totals."""
    from repro.serving import Server
    from repro.runtime import MetronomePolicy, StealingAssignment

    eng = _make_engine(max_slots=4)
    warm = Request(prompt=[1, 2], max_new_tokens=2)
    eng.submit([warm])
    eng.pump()

    srv = Server(eng,
                 MetronomePolicy(MetronomeConfig(m=3, v_target_us=3_000.0,
                                                 t_long_us=60_000.0)),
                 n_queues=3, assignment=StealingAssignment())
    assert len(srv.queues) == 3
    srv.start()
    reqs = []
    for i in range(12):
        r = Request(prompt=[(i % 90) + 1, (i % 90) + 2], max_new_tokens=4)
        assert srv.submit(r)
        reqs.append(r)
        time.sleep(0.02)
    for r in reqs:
        assert r.wait(timeout=20.0), "request not completed"
    stats = srv.stop()
    assert all(len(r.tokens) == 4 for r in reqs)
    assert len(stats.per_queue) == 3
    assert sum(q.offered for q in stats.per_queue) == stats.offered == 12
    assert sum(q.serviced for q in stats.per_queue) == 12
    assert sum(q.dropped for q in stats.per_queue) == stats.dropped == 0


def test_server_affinity_routes_same_key_to_same_queue():
    """Requests sharing a session attribute always land in one queue."""
    from repro.serving import Server
    from repro.runtime import FixedPeriodPolicy

    class _NullEngine:
        def submit(self, reqs):
            pass

        def pump(self):
            return False

    srv = Server(_NullEngine(), FixedPeriodPolicy(5_000.0), n_queues=4)

    class _KeyedReq:
        def __init__(self, session_id):
            self.session_id = session_id

    # do not start the server: pushed requests stay put, exposing routing
    for _ in range(8):
        srv.submit(_KeyedReq("session-A"))
    occupied = [len(q) for q in srv.queues]
    assert sum(occupied) == 8
    assert max(occupied) == 8    # all eight in a single queue


def test_server_replay_schedules_a_nonstationary_request_stream():
    """Server.replay drives live serving from a Workload x LoadSchedule
    pair: everything submitted is served, and the stats carry the
    schedule descriptor so live runs line up with simulated ones."""
    from repro.runtime import MetronomePolicy, PoissonWorkload, StepSchedule
    from repro.serving import Server

    eng = _make_engine()
    srv = Server(eng, MetronomePolicy(MetronomeConfig(
        m=2, v_target_us=1_000.0, t_long_us=20_000.0)))
    # ~60 requests over 0.3s, rate stepping up 3x halfway through
    sched = StepSchedule(times_us=(0.0, 150_000.0), scales=(0.5, 1.5))
    stats = srv.replay(
        PoissonWorkload(0.0002), duration_us=300_000.0, schedule=sched,
        make_request=lambda i: Request(prompt=[1, 2, 3], max_new_tokens=2))
    assert stats.backend == "server"
    assert stats.schedule.startswith("step[")
    assert stats.workload.startswith("poisson")
    assert stats.offered > 0
    assert stats.items == stats.offered - stats.dropped
    assert stats.dropped == 0
