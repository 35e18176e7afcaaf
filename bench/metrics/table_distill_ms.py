"""``table_distill_ms``: mean host-clock time per request of the
``table_distill`` span, around ``build_operating_table(sweep=...)``:
the seed averages, the analytic guard, the argmin and the event-engine
spot checks."""


def read(run):
    n = run.spans.count("table_distill")
    if not n:
        return None
    return 1e3 * run.spans.total("table_distill") / n
