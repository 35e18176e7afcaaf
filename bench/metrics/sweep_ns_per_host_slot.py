"""``sweep_ns_per_host_slot``: host-clock time of the benchmark's
``sweep`` span (the scan call, results on the host) per simulated
host-slot of the problem: points x hosts x duration / slot.  Padding and
a kernel's own step count stay inside the number."""


def read(run):
    n = run.spans.count("sweep")
    if not n:
        return None
    return 1e9 * run.spans.total("sweep") / (n * run.request.host_slots)
