#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds <n,n,...> --control-seeds <n,n,...>

One process on the chip, one set-up.  For each of ``--seeds`` it runs a
window as a benchmark run does and reads the numbers compared with the
plain reference (the lower readings: sound runs of the program).  For
each of ``--control-seeds`` it runs a window and reads the same numbers
with the control in the program's place: the reference computed in
bfloat16 (the upper readings).  The benchmark's own runs never run this.
Prints one JSON line per seed, then the largest program reading and the
smallest control reading of each number.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402

from bench import cell as cells  # noqa: E402
from bench.run import device_info, set_up, window  # noqa: E402


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def readings(cell, seconds: float, program: list, control: list,
             log=sys.stderr, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise SystemExit(f"{cell.name} needs {cell.chips} TPU chip(s)")
    req, spans, _ = set_up(cell, (program + control)[0])
    lower, upper = {}, {}
    for kind, seed in ([("program", s) for s in program]
                       + [("control", s) for s in control]):
        records, attempted, failed, wall = window(req, seed, seconds, spans,
                                                  log)
        t0 = time.perf_counter()
        got = req.readings(records, seed, control=(kind == "control"))
        line = {"kind": kind, "seed": seed, "requests": attempted,
                "failed": failed, "window_s": wall,
                "compare_s": time.perf_counter() - t0, "readings": got}
        print(json.dumps(line), flush=True)
        into, pick = (lower, max) if kind == "program" else (upper, min)
        for k, v in got.items():
            into[k] = pick(into.get(k, v), v)
    out = {"lower": lower, "upper": upper, "device": device_info(cell.chips)}
    out["separates"] = {k: (upper.get(k, math.inf) >= 3 * lower[k])
                        for k in lower}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    print(json.dumps(readings(cell, args.seconds, args.seeds,
                              args.control_seeds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
