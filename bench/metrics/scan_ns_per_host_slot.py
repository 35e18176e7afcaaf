"""``scan_ns_per_host_slot``: the program's ``batched.scan`` span (the
device scan, from the jitted call until its outputs are ready) per
simulated host-slot of the problem: points x hosts x duration / slot.
Padding and a kernel's own step count stay inside the number.

``window_records`` is the selection the program-span readers share."""


def window_records(run):
    """The program's span records of the window's requests: those that
    start at or after the first benchmark span of a request with index
    >= 0 (the warm-up request has -1), on the shared ``perf_counter``
    clock.  ``None`` where the program keeps no spans of its own."""
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    starts = [a for _, r, a, _ in run.spans.records if r >= 0]
    if not starts:
        return None
    t0_ns = min(starts) * 1e9
    return [s for s in tracing.records() if s.start_ns >= t0_ns]


def read(run):
    recs = window_records(run) or []
    scans = [s.duration_ns for s in recs if s.name == "batched.scan"]
    if not scans:
        return None
    return sum(scans) / (len(scans) * run.request.host_slots)
