"""``fleet_host_ms``: mean host time per sweep of the program's
``fleet.sweep`` span outside its ``fleet.scan`` child: building or
finding the kernel, staging the inputs, the device-to-host copies and
building the ``FleetStats``."""

from bench.metrics.scan_ns_per_host_slot import window_records


def read(run):
    recs = window_records(run) or []
    sweeps = [s for s in recs if s.name == "fleet.sweep"]
    if not sweeps:
        return None
    scan_ns = {}
    for s in recs:
        if s.name == "fleet.scan":
            scan_ns[s.parent] = scan_ns.get(s.parent, 0) + s.duration_ns
    host_ns = sum(s.duration_ns - scan_ns.get(s.id, 0) for s in sweeps)
    return host_ns / len(sweeps) / 1e6
