"""``setup_s``: process start to window start (host clock): imports,
device, compilation-cache reads or compiles, and the warm-up request."""


def read(run):
    return run.setup_s
