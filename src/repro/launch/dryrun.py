import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks the device count on first
# init).  This module is the ONLY place the 512 placeholder devices exist;
# tests/benches see the real single device.

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs.base import SHAPES, cells, get_config  # noqa: E402
from repro.launch.inputs import build_cell                # noqa: E402
from repro.launch.mesh import make_production_mesh        # noqa: E402
from repro.roofline.analysis import analyze_compiled, model_flops  # noqa: E402

"""Multi-pod dry-run (spec deliverable e).

For every (architecture x input shape) cell, lower + compile the step
function for the production mesh — single-pod 16x16 and multi-pod 2x16x16 —
and print memory_analysis() / cost_analysis() plus the parsed collective
schedule.  A failure here (sharding mismatch, OOM at compile, unsupported
collective) is a bug in the system.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod --json out.json
"""


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True, extra: dict | None = None,
             probes: bool = True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh, **(extra or {}))
    lowered = cell.lower()
    compiled = lowered.compile()
    dt = time.time() - t0

    import functools
    from repro.models import Model
    from repro.roofline.analysis import collective_bytes
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    params_sds = jax.eval_shape(
        functools.partial(Model(cfg).init,
                          max_seq=shape.seq_len if not cfg.use_rope else 4096),
        jax.random.PRNGKey(0))
    mf = model_flops(cfg, shape, params_sds)

    rep = analyze_compiled(arch, shape_name, mesh_name, compiled,
                           model_flops_global=mf,
                           n_devices=mesh.devices.size, compile_s=dt)

    if probes:
        # XLA's HloCostAnalysis counts while-loop bodies ONCE (not x trip
        # count), so the scan-over-layers module under-reports.  Compile
        # two scan-UNROLLED probes with k=1 and k=2 layer groups (full
        # width, same mesh/shapes) and extrapolate linearly:
        #   F(G) = F(1) + (G-1) * (F(2) - F(1))
        # — exact, since cost is affine in the group count.
        groups = cfg.n_layers // cfg.scan_unit()

        def probe(k):
            c = build_cell(arch, shape_name, mesh, probe_groups=k,
                           **(extra or {}))
            comp = c.lower().compile()
            ca = comp.cost_analysis()
            coll = collective_bytes(comp.as_text())
            return (float(ca.get("flops", 0.0)),
                    float(ca.get("bytes accessed", 0.0)),
                    float(coll["total"]))

        f1, b1, c1 = probe(1)
        f2, b2, c2 = probe(2)
        rep.flops_per_dev = f1 + (groups - 1) * (f2 - f1)
        rep.bytes_per_dev = b1 + (groups - 1) * (b2 - b1)
        rep.coll_bytes_per_dev = c1 + (groups - 1) * (c2 - c1)
        from repro.roofline.analysis import roofline_terms
        rep.terms = roofline_terms(rep.flops_per_dev, rep.bytes_per_dev,
                                   rep.coll_bytes_per_dev)
    if verbose:
        print(f"== {arch} x {shape_name} @ {mesh_name} "
              f"(compile {dt:.1f}s) ==")
        print("   memory_analysis:", compiled.memory_analysis())
        ca = compiled.cost_analysis()
        print(f"   cost_analysis: flops/dev={ca.get('flops', 0):.3e} "
              f"bytes/dev={ca.get('bytes accessed', 0):.3e}")
        print(f"   collectives/dev: {rep.coll_detail}")
        t = rep.terms
        print(f"   roofline: compute={t['compute_s']:.4f}s "
              f"memory={t['memory_s']:.4f}s collective={t['collective_s']:.4f}s "
              f"-> dominant={t['dominant']} "
              f"fraction={t['roofline_fraction']:.3f} "
              f"useful_flops_ratio={rep.useful_flops_ratio:.3f}")
        sys.stdout.flush()
    return rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true",
                    help="every runnable (arch x shape) cell")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--json", help="append JSONL reports here")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    metavar="LOGICAL=PHYSICAL",
                    help="logical-axis rule override for perf experiments, "
                         "e.g. --override seq=model (sequence parallelism)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (perf experiment B2)")
    ap.add_argument("--kv-ring", action="store_true",
                    help="ring-buffer local-window KV (perf experiment C1)")
    ap.add_argument("--ssm-chunk", type=int, default=0,
                    help="override SSD chunk length (perf experiment D1)")
    args = ap.parse_args(argv)

    overrides = {}
    for ov in args.override:
        k, _, v = ov.partition("=")
        overrides[k] = None if v in ("", "none", "None") else v

    todo = []
    if args.all:
        todo = [(a, s) for a, s, skip in cells() if not skip]
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        todo = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    reports = []
    for arch, shape in todo:
        for mp in meshes:
            try:
                extra = {"remat": not args.no_remat} \
                    if SHAPES[shape].kind == "train" else {}
                if overrides:
                    extra["rule_overrides"] = overrides
                cfg_ov = {}
                if args.kv_quant:
                    cfg_ov["kv_quant"] = True
                if args.kv_ring:
                    cfg_ov["kv_ring"] = True
                if args.ssm_chunk:
                    cfg_ov["ssm_chunk"] = args.ssm_chunk
                if cfg_ov:
                    extra["cfg_overrides"] = cfg_ov
                rep = run_cell(arch, shape, multi_pod=mp, extra=extra)
                reports.append(rep)
                if args.json:
                    with open(args.json, "a") as f:
                        row = rep.row()
                        row["coll_detail"] = {
                            k: v for k, v in rep.coll_detail.items()}
                        f.write(json.dumps(row) + "\n")
            except Exception:
                failures.append((arch, shape, mp))
                print(f"!! FAILED {arch} x {shape} multi_pod={mp}")
                traceback.print_exc()

    print(f"\n{len(reports)} cells compiled OK, {len(failures)} failed")
    for f in failures:
        print("  FAILED:", f)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
