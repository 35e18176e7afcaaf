"""``distill_ms``: mean self time per table of the program's
``calibrate.table`` span: the seed averages, the analytic guard and the
argmin, without its spot checks or a sweep of its own."""

from bench.metrics.scan_ns_per_host_slot import window_records


def read(run):
    recs = window_records(run) or []
    tables = [s for s in recs if s.name == "calibrate.table"]
    if not tables:
        return None
    from repro.runtime.tracing import self_ns

    return sum(self_ns(s) for s in tables) / len(tables) / 1e6
