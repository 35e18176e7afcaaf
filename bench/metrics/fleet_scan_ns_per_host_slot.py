"""``fleet_scan_ns_per_host_slot``: the program's ``fleet.scan`` span
(the fleet kernel, from the jitted call until its outputs are ready) per
simulated host-slot of the request: points x hosts x duration / slot.
Padding and the kernel's own step count stay inside the number."""

from bench.metrics.scan_ns_per_host_slot import window_records


def read(run):
    recs = window_records(run) or []
    scans = [s.duration_ns for s in recs if s.name == "fleet.scan"]
    if not scans:
        return None
    return sum(scans) / (len(scans) * run.request.host_slots)
