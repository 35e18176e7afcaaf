#!/usr/bin/env python3
"""Run one benchmark cell once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's pieces are found by name (``bench/cell.py``).  A run:

  1. set-up: refuses without a TPU holding the cell's chips (no CPU
     fallback, no result line), turns on JAX's persistent compilation
     cache (``enable_cache``), and runs one warm-up request with the
     cell's own shapes;
  2. the window: requests back to back, one client, each with fresh
     seeds from ``--seed`` and its index.  Once ``--seconds`` have
     passed, the request in flight finishes and the window closes at its
     completion.  With ``--trace 1`` the profiler records the window's
     first ``trace_requests`` requests;
  3. after the window: device peak memory, the trace reduction, and the
     comparison with the plain reference, which decides ``correct``.

Standard output ends with ``window_compiles=<n>`` (compile events inside
the window; 0 when set-up warmed everything) and then one JSON line.
The numbers compared, each beside its limit, are the last lines of
standard error and the last key of the JSON line.
"""

from __future__ import annotations

import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout's root in place of this script's directory, whose
# trace.py would otherwise shadow the standard library's
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

from bench import cell as cells  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.spans import CompileCounter, Spans  # noqa: E402


class Run:
    """What a finished run hands to the metric readers."""

    def __init__(self, cell, request, records, spans, setup_s, window_s,
                 trace):
        self.cell = cell
        self.request = request
        self.records = records
        self.spans = spans
        self.setup_s = setup_s
        self.window_s = window_s
        self.trace = trace

    @property
    def host_seconds_done(self) -> float:
        return len(self.records) * self.request.host_seconds


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs[:chips]]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def read_metrics(entries: list, run: Run) -> dict:
    out = {}
    for m in entries:
        v = cells.load_module("metrics", m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def enable_cache() -> str:
    """JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``.jax_cache/`` in the
    checkout (a fixed path: the path is part of the cache's key)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def set_up(cell, seed: int):
    """Compilation cache, compile counter and one warm-up request with
    the cell's own shapes; returns (request runner, spans, counter)."""
    enable_cache()
    counter = CompileCounter()
    spans = Spans()
    req = cell.request()
    req.run(-1, seed, spans)
    return req, spans, counter


def window(req, seed: int, seconds: float, spans, log=sys.stderr,
           on_request=None):
    """Requests back to back until ``seconds`` have passed and the one
    in flight has finished.  Returns (records, attempted, failed, wall
    seconds of the window)."""
    records, attempted, failed = [], 0, 0
    t_w0 = time.perf_counter()
    while True:
        spans.request = attempted
        attempted += 1
        try:
            records.append(req.run(attempted - 1, seed, spans))
        except Exception:
            failed += 1
            traceback.print_exc(file=log)
        if on_request is not None:
            on_request(attempted)
        if time.perf_counter() - t_w0 >= seconds:
            break
    return records, attempted, failed, time.perf_counter() - t_w0


def compare(req, records, seed: int, log=sys.stderr) -> dict:
    """The numbers compared with the plain reference, each beside its
    limit: those the workload sets a limit for."""
    worst: dict = {}
    readings = req.readings(records, seed, worst=worst)
    for k, (p, got, ref) in worst.items():
        print(f"worst {k}: point {p} program {got} reference {ref}",
              file=log)
    return {k: {"value": readings[k], "limit": v}
            for k, v in req.limits.items()}


class Tracer:
    """The profiler over the window's first ``n`` requests, writing
    outside the checkout (``TMPDIR``)."""

    def __init__(self, n: int):
        import jax

        self.n = n
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the benchmark's spans suffice
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.ann = jax.profiler.TraceAnnotation(tr.WINDOW)
        self.ann.__enter__()
        self.on = True

    def after(self, spans, attempted: int) -> None:
        if self.on and attempted >= self.n:
            self.stop(spans)

    def stop(self, spans) -> None:
        import jax

        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = spans.tracing = False

    def reduce(self, spans, chips: int, log) -> dict | None:
        if self.on:
            self.stop(spans)
        found = sorted(Path(self.dir).rglob("*.xplane.pb"))
        out = None
        if found:
            t0 = time.perf_counter()
            out = tr.reduce_file(found[-1], chips)
            print(f"trace_bytes={found[-1].stat().st_size} "
                  f"reduce_s={time.perf_counter() - t0}", file=log)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float, log=sys.stderr) -> dict:
    """Set-up, window and comparison of one run; returns the result."""
    req, spans, counter = set_up(cell, seed)
    compiles_setup = counter.events
    tracer = None
    if trace:
        tracer = Tracer(int(cell.workload.get("trace_requests", 1)))
        spans.tracing = True
    t_w0 = time.perf_counter()
    records, attempted, failed, window_s = window(
        req, seed, seconds, spans, log,
        on_request=(lambda a: tracer.after(spans, a)) if tracer else None)
    print(f"window_compiles={counter.events - compiles_setup} "
          f"setup_compile_s={counter.seconds}", flush=True)
    device = device_info(cell.chips)

    reduced = tracer.reduce(spans, cell.chips, log) if tracer else None
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    run = Run(cell, req, records, spans, t_w0 - t_start, window_s, reduced)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, run)

    checks = compare(req, records, seed, log)
    ok = bool(records) and bool(checks) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}", file=log)
    log.flush()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"the program is not in this checkout ({ROOT / 'src'})",
              file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"{args.workload} needs {cell.chips} TPU chip(s); JAX sees "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
