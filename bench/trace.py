"""The reduction from a profiler trace to device numbers.

A trace is the ``.xplane.pb`` file that ``jax.profiler`` writes.  Its
device planes (``/device:TPU:<n>``) hold the operations that ran on each
chip, on the line ``XLA Ops``; the host planes hold the benchmark's
``TraceAnnotation`` spans (``bench.<name>``) on the same clock.  From
them:

  - ``window_s``: the length of the ``bench.trace_window`` span;
  - ``busy_s``: per chip, the union of the intervals in which an
    operation ran, inside the window, averaged over the chips;
  - ``device_ops``: device time per operation, inside the window, named
    by the HLO instruction (the text before `` = ``);
  - ``idle_gaps``: the window's idle time on the first chip, put down to
    the innermost benchmark span the host was in at each gap's middle.

A scan runs one device program whose loop body is tens of operations,
so one request can leave millions of events: they are read once, into
arrays.
"""

from __future__ import annotations

import numpy as np

WINDOW = "bench.trace_window"
OPS_LINE = "XLA Ops"


def merged(starts, ends, lo: float, hi: float):
    """Disjoint, sorted (starts, ends) of the union of intervals,
    clipped to [lo, hi]."""
    a = np.clip(np.asarray(starts, dtype=np.float64), lo, hi)
    b = np.clip(np.asarray(ends, dtype=np.float64), lo, hi)
    keep = b > a
    a, b = a[keep], b[keep]
    if a.size == 0:
        return a, b
    order = np.argsort(a, kind="stable")
    a, b = a[order], b[order]
    reach = np.maximum.accumulate(b)
    new = np.empty(a.size, dtype=bool)
    new[0] = True
    new[1:] = a[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, a.size - 1)
    return a[first], reach[last]


def covered(starts, ends, lo: float, hi: float) -> float:
    """Length of [lo, hi] covered by at least one interval."""
    a, b = merged(starts, ends, lo, hi)
    return float((b - a).sum())


def gaps(starts, ends, lo: float, hi: float):
    """(starts, ends) of the idle intervals of [lo, hi]."""
    a, b = merged(starts, ends, lo, hi)
    gs = np.concatenate([[lo], b])
    ge = np.concatenate([a, [hi]])
    keep = ge > gs
    return gs[keep], ge[keep]


def idle_share(busy_s: float, window_s: float) -> float:
    """Percent of the window in which no operation ran."""
    return 100.0 * (1.0 - busy_s / window_s)


def op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0]


def _read_line(line, names: dict):
    """Starts, ends and name ids of a line's events (ns)."""
    starts, ends, ids = [], [], []
    for e in line.events:
        s = e.start_ns
        starts.append(s)
        ends.append(s + e.duration_ns)
        ids.append(names.setdefault(op_name(e.name), len(names)))
    return (np.asarray(starts, dtype=np.float64),
            np.asarray(ends, dtype=np.float64), np.asarray(ids, dtype=np.int64))


def label_gaps(gs, ge, spans) -> dict:
    """Idle seconds per innermost host span at each gap's middle."""
    mid = 0.5 * (gs + ge)
    label = np.full(mid.size, -1)
    names = []
    for n, a, b in sorted(spans, key=lambda s: s[1] - s[2]):  # longest first
        names.append(n[len("bench."):])
        label[(mid >= a) & (mid < b)] = len(names) - 1
    out = {}
    length = ge - gs
    for k in np.unique(label):
        name = names[k] if k >= 0 else "untraced host"
        out[name] = out.get(name, 0.0) + float(length[label == k].sum())
    return out


def reduce_planes(planes, n_devices: int) -> dict | None:
    """Device numbers from parsed planes, or ``None`` where the trace
    has no window span or no device plane."""
    host, devs = [], []
    for p in planes:
        if p.name.startswith("/device:TPU:"):
            devs.append(p)
            continue
        if p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith("bench."):
                    host.append((e.name, float(e.start_ns),
                                 float(e.start_ns + e.duration_ns)))
    win = [(a, b) for n, a, b in host if n == WINDOW]
    devs = sorted(devs, key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devs = devs[:n_devices]
    if not win or not devs:
        return None
    lo, hi = win[0]
    names: dict = {}
    busy, op_ns, idle = [], None, {}
    for k, p in enumerate(devs):
        lines = [ln for ln in p.lines if ln.name == OPS_LINE]
        if not lines:
            return None
        s, e, ids = _read_line(lines[0], names)
        busy.append(covered(s, e, lo, hi))
        dur = np.clip(e, lo, hi) - np.clip(s, lo, hi)
        per = np.bincount(ids, weights=np.maximum(dur, 0.0),
                          minlength=len(names))
        op_ns = per if op_ns is None else (
            np.pad(op_ns, (0, per.size - op_ns.size)) + per)
        if k == 0:
            idle = label_gaps(*gaps(s, e, lo, hi),
                              [h for h in host if h[0] != WINDOW])
    by_id = {v: k for k, v in names.items()}
    ops = {by_id[i]: float(v) for i, v in enumerate(op_ns) if v > 0}
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": top({k: v / len(devs) for k, v in ops.items()}),
            "idle_gaps": top(idle), "n_devices": len(devs)}


def reduce_file(path, n_devices: int) -> dict | None:
    import jax

    return reduce_planes(jax.profiler.ProfileData.from_file(str(path)).planes,
                         n_devices)
