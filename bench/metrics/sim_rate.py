"""``sim_rate``: simulated host-seconds of every request completed in
the window, over the window's wall time (host clock)."""


def read(run):
    return run.host_seconds_done / run.window_s
