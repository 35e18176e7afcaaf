"""Benchmark harness: one function per paper table/figure (+ the
batched sweep frontier + the nonstationary adaptation matrix).  Prints
``name,us_per_call,derived`` CSV; ``--json out.json`` additionally
writes every row machine-readably (derived ``k=v;k=v`` strings parsed
into dicts — so policy/workload labels, p50/p99
latencies, CPU fractions, and the adaptation rows' ``schedule``
descriptor plus tracking fields — conv_us, overshoot_us,
violation_frac, rho_rmse — land as fields) for a ``BENCH_*.json`` perf
trajectory across PRs.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only substr]
                                          [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import math
import platform
import sys
import time


def _parse_derived(derived: str) -> dict:
    """``k=v;k=v`` strings -> dict (numbers coerced); anything else is
    kept whole under ``note``."""
    out: dict = {}
    parts = [p for p in str(derived).split(";") if p]
    for p in parts:
        if "=" not in p:
            out.setdefault("note", []).append(p)
            continue
        k, v = p.split("=", 1)
        try:
            out[k] = int(v)
        except ValueError:
            try:
                fv = float(v)
                out[k] = fv if math.isfinite(fv) else v   # strict JSON
            except ValueError:
                out[k] = {"True": True, "False": False}.get(v, v)
    if isinstance(out.get("note"), list):
        out["note"] = "; ".join(out["note"])
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="",
                    help="write all rows to this file as JSON")
    args = ap.parse_args()

    from benchmarks.adaptation import adaptation
    from benchmarks.cpu_sharing import cpu_sharing
    from benchmarks.fleet import fleet_bench
    from benchmarks.policy_matrix import matrix_policies_workloads
    from benchmarks.power import power
    from benchmarks.rss_skew import matrix_rss_skew
    from benchmarks.stepping import stepping_compare
    from benchmarks.sweep_frontier import sweep_frontier
    from benchmarks.paper_tables import (
        fig2_sleep_cpu,
        fig5_vacation_pdf,
        fig7_tl_sweep,
        fig8_m_sweep,
        fig11_adaptation,
        fig12_dpdk_compare,
        fig15_applications,
        table1_sleep_precision,
        table2_vbar_tuning,
        table3_nanosleep_loss,
    )

    def compile_caches(quick: bool = False):
        """JIT compile-cache counters across everything that ran above —
        hits/misses/evictions per registered ``CompileCache`` (the
        batched and fleet sweep caches), so cache behavior lands in the
        perf trajectory next to the numbers it explains.  Must stay the
        LAST suite."""
        from repro.runtime import compile_cache_stats

        return [(f"cache/{s['name']}", float(s["hits"]),
                 f"misses={s['misses']};evictions={s['evictions']};"
                 f"currsize={s['currsize']};maxsize={s['maxsize']}")
                for s in compile_cache_stats()]

    suites = [
        table1_sleep_precision, fig2_sleep_cpu, fig5_vacation_pdf,
        table2_vbar_tuning, fig7_tl_sweep, fig8_m_sweep,
        table3_nanosleep_loss, fig11_adaptation, fig12_dpdk_compare,
        matrix_policies_workloads, matrix_rss_skew, sweep_frontier,
        cpu_sharing, adaptation, fig15_applications, fleet_bench,
        stepping_compare, power, compile_caches,
    ]
    print("name,us_per_call,derived")
    failures = 0
    records: list[dict] = []
    for suite in suites:
        if args.only and args.only not in suite.__name__:
            continue
        t0 = time.time()
        try:
            for name, us, derived in suite(quick=args.quick):
                print(f"{name},{us:.3f},{derived}")
                records.append({"suite": suite.__name__, "name": name,
                                # NaN/inf rows (e.g. "no verdict") must
                                # stay strict-JSON parseable: use null
                                "value": us if math.isfinite(us) else None,
                                "derived": _parse_derived(derived)})
        except Exception as e:  # keep the harness going; report at exit
            failures += 1
            print(f"{suite.__name__}/ERROR,0,{type(e).__name__}:{e}",
                  file=sys.stderr)
            records.append({"suite": suite.__name__, "name": "ERROR",
                            "value": None,
                            "derived": {"error": f"{type(e).__name__}: {e}"}})
        sys.stdout.flush()
        print(f"# {suite.__name__} done in {time.time() - t0:.1f}s",
              file=sys.stderr)
    if args.json:
        payload = {
            "schema": "repro-bench/1",
            "created_unix": time.time(),
            "host": platform.node(),
            "python": platform.python_version(),
            "quick": bool(args.quick),
            "only": args.only,
            "failures": failures,
            "rows": records,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(records)} rows to {args.json}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch import jax_cache

    jax_cache.enable()
    main()
