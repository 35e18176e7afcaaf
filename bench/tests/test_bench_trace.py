"""``bench.trace``: the busy-union and idle-share arithmetic, on a
hand-made case and on a trace recorded on a TPU v5e chip."""

import gzip
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / "calib-tiny.xplane.pb.gz"


def test_union_of_overlapping_intervals():
    # [0,4) and [2,5) overlap, [5,6) touches, [8,9) is apart, [12,20)
    # sticks out of the window [0, 10)
    s = [2.0, 0.0, 8.0, 5.0, 12.0]
    e = [5.0, 4.0, 9.0, 6.0, 20.0]
    a, b = tr.merged(s, e, 0.0, 10.0)
    np.testing.assert_array_equal(a, [0.0, 8.0])
    np.testing.assert_array_equal(b, [6.0, 9.0])
    assert tr.covered(s, e, 0.0, 10.0) == 7.0
    gs, ge = tr.gaps(s, e, 0.0, 10.0)
    np.testing.assert_array_equal(gs, [6.0, 9.0])
    np.testing.assert_array_equal(ge, [8.0, 10.0])
    assert tr.idle_share(7.0, 10.0) == pytest.approx(30.0)


def test_nested_events_count_once():
    # a loop op spanning its body's ops: busy is the loop's extent
    s, e = [0.0, 1.0, 3.0], [10.0, 2.0, 4.0]
    assert tr.covered(s, e, -5.0, 15.0) == 10.0
    assert tr.covered([], [], 0.0, 1.0) == 0.0
    gs, ge = tr.gaps([], [], 0.0, 1.0)
    assert list(zip(gs, ge)) == [(0.0, 1.0)]


def test_gaps_go_to_the_innermost_host_span():
    spans = [("bench.sweep", 0.0, 10.0), ("bench.table_distill", 10.0, 14.0),
             ("bench.grid_prep", 14.0, 15.0), ("bench.outer", 0.0, 20.0)]
    gs = np.asarray([1.0, 11.0, 14.2, 16.0])
    ge = np.asarray([2.0, 13.0, 14.6, 19.0])
    got = tr.label_gaps(gs, ge, spans)
    assert got == pytest.approx({"sweep": 1.0, "table_distill": 2.0,
                                 "grid_prep": 0.4, "outer": 3.0})


@pytest.fixture(scope="module")
def recorded():
    import jax

    return jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes()))


def test_recorded_trace(recorded):
    """Two tiny calibration requests traced on one chip: the reduction
    agrees with the union computed here from the same events."""
    got = tr.reduce_planes(recorded.planes, 1)
    assert got is not None and got["n_devices"] == 1
    dev = next(p for p in recorded.planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in dev.lines if ln.name == tr.OPS_LINE)
    host = [e for p in recorded.planes if not p.name.startswith("/device:")
            for ln in p.lines for e in ln.events if e.name == tr.WINDOW]
    lo, hi = host[0].start_ns, host[0].start_ns + host[0].duration_ns
    ivs = sorted((max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
                 for e in ops.events)
    busy, cur = 0.0, None
    for a, b in ivs:
        if b <= a:
            continue
        if cur is None or a > cur[1]:
            busy += 0.0 if cur is None else cur[1] - cur[0]
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    busy += cur[1] - cur[0]
    assert got["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert got["busy_s"] == pytest.approx(busy / 1e9)
    assert 0.0 < got["busy_s"] < got["window_s"]
    share = tr.idle_share(got["busy_s"], got["window_s"])
    assert 0.0 < share < 100.0
    idle = sum(v for _, v in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)
    assert {n for n, _ in got["idle_gaps"]} <= {
        "sweep", "table_distill", "grid_prep", "untraced host"}
    assert got["device_ops"] and all(v > 0 for _, v in got["device_ops"])
