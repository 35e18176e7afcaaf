"""``fleet_tail_ms``: mean time per request of the program's
``fleet.tail`` spans (the measured quantiles of every point, first
completion wins) plus its ``fleet.rollup`` spans (``to_run_stats`` of
every point).  A request is one ``fleet.sweep``."""

from bench.metrics.scan_ns_per_host_slot import window_records


def read(run):
    recs = window_records(run) or []
    requests = sum(1 for s in recs if s.name == "fleet.sweep")
    if not requests:
        return None
    ns = sum(s.duration_ns for s in recs
             if s.name in ("fleet.tail", "fleet.rollup"))
    return ns / requests / 1e6
