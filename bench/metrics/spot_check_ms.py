"""``spot_check_ms``: mean time per table of the program's
``calibrate.spot_check`` spans, the event-engine runs that re-examine
the table's rungs."""

from bench.metrics.scan_ns_per_host_slot import window_records


def read(run):
    recs = window_records(run) or []
    tables = sum(1 for s in recs if s.name == "calibrate.table")
    if not tables:
        return None
    checks = sum(s.duration_ns for s in recs
                 if s.name == "calibrate.spot_check")
    return checks / tables / 1e6
