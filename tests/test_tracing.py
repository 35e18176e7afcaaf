"""The program's spans (``repro.runtime.tracing``) and the named stages
of the scan kernels."""

import contextlib
import re
import threading

import jax
import numpy as np
import pytest

from repro.runtime import (
    SimRunConfig,
    SweepGrid,
    batched,
    batched_adaptive,
    build_operating_table,
    simulate_batch,
    tracing,
)

STAGES = ("rng", "stall", "arrivals", "overshoot", "wake_claim", "drain",
          "release", "accumulate", "gate")
# every stage of the fixed kernel has operations only when the sleep
# model has a tail and the host has interference and stalls
NOISY = SimRunConfig(duration_us=100.0, interference_prob=0.1,
                     interference_mean_us=5.0, stall_rate_per_us=1e-3,
                     stall_mean_us=50.0)


def _children(rec):
    return [r for r in tracing.records() if r.parent == rec.id]


def _last(name):
    return [r for r in tracing.records() if r.name == name][-1]


def test_parent_root_self_time_and_counts():
    with tracing.span("outer", a=1) as outer:
        with tracing.span("inner") as inner:
            tracing.count(b=2)
            tracing.count(b=1)
        with tracing.span("inner") as inner2:
            pass
    with tracing.span("other") as other:
        pass
    assert outer.parent == 0 and outer.root == outer.id
    assert inner.parent == outer.id and inner.root == outer.id
    assert inner2.parent == outer.id and inner2.id != inner.id
    assert other.root == other.id != outer.id
    assert outer.counts == {"a": 1} and inner.counts == {"b": 3}
    assert [r.id for r in _children(outer)] == [inner.id, inner2.id]
    assert tracing.self_ns(outer) == (outer.duration_ns - inner.duration_ns
                                      - inner2.duration_ns)
    assert tracing.self_ns(inner) == inner.duration_ns
    tracing.count(c=1)                      # no span open: nothing to add


def test_self_ns_counts_overlapping_children_once():
    rec = tracing.Span("x")
    rec.start_ns, rec.end_ns = 100, 200
    rec.children = [(150, 190), (110, 160), (120, 130), (195, 250)]
    assert tracing.self_ns(rec) == 100 - (190 - 110) - (200 - 195)


def test_a_thread_starts_its_own_tree():
    seen = {}

    def work():
        with tracing.span("in_thread") as s:
            seen["span"] = s

    with tracing.span("main") as main:
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert seen["span"].parent == 0 and seen["span"].root != main.root
    assert main.children == []


def test_ring_bound_and_totals():
    before = tracing.totals().get("ring_probe", (0, 0))
    for _ in range(tracing.RING + 10):
        with tracing.span("ring_probe"):
            pass
    recs = tracing.records()
    assert len(recs) == tracing.RING
    assert all(r.name == "ring_probe" for r in recs[-tracing.RING:])
    n, total = tracing.totals()["ring_probe"]
    assert n == before[0] + tracing.RING + 10
    assert total >= before[1] + sum(r.duration_ns for r in recs)


@pytest.mark.parametrize("stepping", ["fixed", "adaptive"])
def test_one_sweep_one_scan_one_fetch_and_built(stepping):
    # a shape no other test builds (five threads, 3,210 us), so the
    # first call misses the kernel cache
    cfg = SimRunConfig(duration_us=3210.0)
    grid = SweepGrid.product(t_s_us=[10.0, 20.0], t_l_us=[200.0], m=[5],
                             rate_mpps=[3.0], seeds=(0, 1))
    built = []
    for _ in range(2):
        bs = simulate_batch(grid, cfg, slot_us=0.5, stepping=stepping)
        sweep = _last("batched.sweep")
        kids = _children(sweep)
        assert [k.name for k in kids] == ["batched.scan", "batched.fetch"]
        assert sweep.counts["points"] == len(grid) == 4
        assert sweep.counts["scan_steps"] == bs.scan_len
        built.append(sweep.counts["built"])
        assert sweep.start_ns <= kids[0].start_ns
        assert kids[0].end_ns <= kids[1].start_ns
        assert kids[1].end_ns <= sweep.end_ns
    assert built == [1, 0]


def test_table_spans_spot_checks_under_one_table():
    cfg = SimRunConfig(duration_us=30_000.0)
    lattice = dict(t_s_grid=np.linspace(4.0, 48.0, 6), t_l_grid=[150.0, 500.0],
                   m_grid=(2, 3), seeds=(0,), slot_us=1.0)
    rhos = [0.15, 0.4, 0.65]
    grid = SweepGrid.product(
        t_s_us=lattice["t_s_grid"], t_l_us=lattice["t_l_grid"],
        m=lattice["m_grid"], rate_mpps=np.asarray(rhos) * cfg.service_rate_mpps,
        seeds=(0,))
    bs = simulate_batch(grid, cfg, slot_us=1.0)
    build_operating_table(rhos=rhos, target_mean_latency_us=15.0, cfg=cfg,
                          spot_check=3, sweep=bs, **lattice)
    table = _last("calibrate.table")
    kids = _children(table)
    assert [k.name for k in kids] == ["calibrate.spot_check"] * 3
    assert [k.counts["rho"] for k in kids] == [0, 1, 2]
    assert tracing.self_ns(table) == table.duration_ns - sum(
        k.duration_ns for k in kids)


def test_profiler_trace_holds_the_program_spans(tmp_path):
    cfg = SimRunConfig(duration_us=200.0)
    grid = SweepGrid.product(t_s_us=[10.0], t_l_us=[100.0], m=[2],
                             rate_mpps=[3.0])
    simulate_batch(grid, cfg)               # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        simulate_batch(grid, cfg)
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    names = set()
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            names |= {e.name for ln in plane.lines for e in ln.events}
    assert {"repro.batched.sweep", "repro.batched.scan",
            "repro.batched.fetch"} <= names


class _Captured(Exception):
    pass


def _captured_kernel(monkeypatch, module, attr, build, run):
    """The jitted kernel and the inputs an entry point hands it."""
    seen = {}

    def cache(*static):
        fn = build(*static)

        def call(*args):
            seen["fn"], seen["args"] = fn, args
            raise _Captured
        return call

    monkeypatch.setattr(module, attr, cache)
    with pytest.raises(_Captured):
        run()
    return seen["fn"], seen["args"]


def _kernel(monkeypatch, kind):
    """The jitted kernel of ``kind`` and its inputs, at a tiny shape."""
    from repro.runtime import fleet
    from repro.runtime.simcore import FleetConfig

    grid = SweepGrid.product(t_s_us=[10.0], t_l_us=[100.0], m=[2],
                             rate_mpps=[3.0])
    if kind == "fleet":
        fgrid = fleet.FleetGrid.product(
            fleet=FleetConfig(n_hosts=2, near_cost_us=1.0), t_s_us=[10.0],
            t_l_us=[100.0], rate_mpps=[3.0], hedge_deadline_us=[20.0])
        return _captured_kernel(
            monkeypatch, fleet, "_compiled_fleet_sweep",
            fleet._build_fleet_sweep,
            lambda: fleet.simulate_fleet(fgrid, NOISY, shard=False))
    if kind == "adaptive":
        return _captured_kernel(
            monkeypatch, batched_adaptive, "_compiled_adaptive_sweep",
            batched_adaptive._build_adaptive_sweep,
            lambda: simulate_batch(grid, NOISY, stepping="adaptive"))
    return _captured_kernel(
        monkeypatch, batched, "_compiled_sweep", batched._build_sweep,
        lambda: simulate_batch(grid, NOISY))


def _program_text(compiled_text: str) -> str:
    """A compiled module's computations without their metadata: the
    stack-frame tables before the first computation and each
    instruction's ``metadata={...}`` go, and names are numbered in the
    order they first appear (the suffixes XLA gives count every op
    traced, scopes included)."""
    lines = compiled_text.splitlines()
    first = next(i for i, ln in enumerate(lines) if ln.startswith("%"))
    body = re.sub(r", metadata=\{[^}]*\}", "",
                  "\n".join(lines[:1] + lines[first:]))
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  body)


def test_stage_names_and_module_names(monkeypatch):
    stages = {"fixed": STAGES,
              "adaptive": STAGES + ("jump",),
              "fleet": STAGES + ("balancer", "topology", "hedge")}
    modules = set()
    for kind in stages:
        fn, args = _kernel(monkeypatch, kind)
        text = fn.lower(*args).as_text(debug_info=True)
        modules.add(re.search(r"module @(\w+)", text).group(1))
        for stage in stages[kind]:
            # a stage inside the fleet's host vmap reads vmap(<stage>)
            assert re.search(rf'loc\("(vmap\()?{stage}\)?/', text), \
                (kind, stage)
    assert modules == {"jit_fixed_sweep", "jit_adaptive_sweep",
                       "jit_fleet_sweep"}


def test_named_scopes_are_metadata_only(monkeypatch):
    fn, args = _kernel(monkeypatch, "fixed")
    scoped = fn.lower(*args).compile().as_text()
    assert "/wake_claim/" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    fn, args = _kernel(monkeypatch, "fixed")
    plain = fn.lower(*args).compile().as_text()
    assert "/wake_claim/" not in plain
    assert _program_text(scoped) == _program_text(plain)
