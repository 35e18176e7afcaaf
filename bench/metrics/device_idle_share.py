"""``device_idle_share``: percent of the traced window in which no
operation ran on the device, averaged over the chips (``bench.trace``)."""

from bench.trace import idle_share


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return idle_share(run.trace["busy_s"], run.trace["window_s"])
