"""``spot_check_ns_per_event``: the program's ``calibrate.spot_check``
spans, summed, over the event engine's work in them, summed: the wakes
and the drain rounds that each run counts on its span.  It reads the
spot checks' cost whichever rungs a request's table picks, as those
differ in how many events they take.  ``None`` where the spans carry no
such counts."""

from bench.metrics.scan_ns_per_host_slot import window_records


def read(run):
    checks = [s for s in window_records(run) or []
              if s.name == "calibrate.spot_check"]
    events = sum(s.counts.get("wakes", 0) + s.counts.get("drain_rounds", 0)
                 for s in checks)
    if not events:
        return None
    return sum(s.duration_ns for s in checks) / events
