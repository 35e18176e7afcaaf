"""Three-term roofline from the compiled dry-run artifact (spec §Roofline).

Per (arch x shape x mesh) cell, from the SPMD-partitioned (= per-device)
module:

    compute    = HLO_FLOPs_per_device   / peak_FLOP/s_per_chip
    memory     = HLO_bytes_per_device   / HBM_byte/s_per_chip
    collective = coll_bytes_per_device  / ICI_byte/s_per_link

cost_analysis() on the partitioned module reports *per-device* numbers
(verified empirically: a (64,256)@(256,512) matmul over an 8-device 2x4
mesh reports 2.1 MFLOP = global/8), so no division by chip count.

collective_bytes parses the optimized HLO text: every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
instruction contributes the byte size of its *operands* (looked up from an
instruction-name -> shape index, since operands print as bare %refs).

Hardware constants: TPU v5e-class chip — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (from the assignment).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

HW = {
    "peak_flops": 197e12,     # bf16 FLOP/s per chip
    "hbm_bw": 819e9,          # bytes/s per chip
    "link_bw": 50e9,          # bytes/s per ICI link
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0,
}

# '%name = type[dims]{layout} opcode(...)'   (also tuple results)
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[a-z0-9]+\[[^=]*?)\s+([\w\-]+)\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string (sums tuple components)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-type operand bytes (per device) + instruction counts."""
    shapes: dict[str, str] = {}
    lines = hlo_text.splitlines()
    for ln in lines:
        m = _DEF_RE.match(ln)
        if m:
            shapes[m.group(1)] = m.group(2)
    out = {op: 0 for op in _COLL_OPS}
    counts = {op: 0 for op in _COLL_OPS}
    for ln in lines:
        m = _DEF_RE.match(ln)
        if not m:
            continue
        opcode = m.group(3)
        base = opcode.replace("-start", "").replace("-done", "")
        if base not in _COLL_OPS or opcode.endswith("-done"):
            continue
        counts[base] += 1
        # operands are inside the parens following the opcode
        paren = ln[ln.index(opcode + "(") + len(opcode) + 1:]
        depth, i = 1, 0
        while i < len(paren) and depth:
            if paren[i] == "(":
                depth += 1
            elif paren[i] == ")":
                depth -= 1
            i += 1
        ops = _OPERAND_RE.findall(paren[:i - 1])
        got = sum(_shape_bytes(shapes.get(o, "")) for o in ops)
        if got == 0:
            # operands printed with inline types (older format)
            got = _shape_bytes(paren[:i - 1])
        out[base] += got
    out["total"] = sum(out[o] for o in _COLL_OPS)
    out["counts"] = counts
    return out


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float) -> dict:
    t_c = flops_per_dev / HW["peak_flops"]
    t_m = bytes_per_dev / HW["hbm_bw"]
    t_x = coll_bytes_per_dev / HW["link_bw"]
    terms = {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom.replace("_s", "")
    terms["bound_s"] = max(t_c, t_m, t_x)
    # roofline fraction: how much of the binding resource the useful
    # (compute) work occupies if perfectly overlapped
    terms["roofline_fraction"] = t_c / max(terms["bound_s"], 1e-30)
    return terms


def count_params(params_tree) -> tuple[int, int]:
    """(total, active) parameter counts from an eval_shape params tree."""
    import jax

    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_tree)[0]:
        n = int(np.prod(leaf.shape))
        total += n
    return total, active


def model_flops(cfg, shape, params_tree) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (forward) with N = active params.

    Active params: MoE expert weights count k/E of their size (top-k of E
    experts touched per token); everything else counts fully.
    """
    import jax

    total = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_tree)[0]:
        names = [getattr(k, "key", "") for k in path]
        n = float(np.prod(leaf.shape))
        stacked = 1 if "blocks" in names else 0
        is_moe_w = (cfg.n_experts and leaf.ndim - stacked == 3
                    and names[-1] in ("w_gate", "w_up", "w_down"))
        if is_moe_w:
            n *= cfg.experts_per_token / cfg.n_experts
        if names[-1] in ("embed", "pos_embed") :
            continue  # gather, not matmul
        if names[-1] == "lm_head":
            pass
        total += n
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * total * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * total * tokens
    # decode: one token per sequence
    return 2.0 * total * shape.global_batch


@dataclass
class CellReport:
    arch: str
    shape: str
    mesh: str
    flops_per_dev: float = 0.0
    bytes_per_dev: float = 0.0
    coll_bytes_per_dev: float = 0.0
    coll_detail: dict = field(default_factory=dict)
    terms: dict = field(default_factory=dict)
    model_flops_global: float = 0.0
    arg_bytes: int = 0
    temp_bytes: int = 0
    peak_bytes: int = 0
    out_bytes: int = 0
    compile_s: float = 0.0
    n_devices: int = 0

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO flops aggregated over chips)."""
        agg = self.flops_per_dev * max(self.n_devices, 1)
        return self.model_flops_global / agg if agg else 0.0

    def row(self) -> dict:
        t = self.terms
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "compute_s": t.get("compute_s", 0), "memory_s": t.get("memory_s", 0),
            "collective_s": t.get("collective_s", 0),
            "dominant": t.get("dominant", "?"),
            "roofline_fraction": t.get("roofline_fraction", 0),
            "useful_flops_ratio": self.useful_flops_ratio,
            "arg_gb": self.arg_bytes / 1e9, "temp_gb": self.temp_bytes / 1e9,
            "peak_gb": self.peak_bytes / 1e9,
            "compile_s": self.compile_s,
        }


def analyze_compiled(arch, shape_name, mesh_name, compiled, *,
                     model_flops_global: float, n_devices: int,
                     compile_s: float = 0.0) -> CellReport:
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    rep = CellReport(
        arch=arch, shape=shape_name, mesh=mesh_name,
        flops_per_dev=float(ca.get("flops", 0.0)),
        bytes_per_dev=float(ca.get("bytes accessed", 0.0)),
        coll_bytes_per_dev=float(coll["total"]),
        coll_detail=coll,
        model_flops_global=model_flops_global,
        n_devices=n_devices,
        compile_s=compile_s,
    )
    if ma is not None:
        rep.arg_bytes = int(getattr(ma, "argument_size_in_bytes", 0))
        rep.temp_bytes = int(getattr(ma, "temp_size_in_bytes", 0))
        rep.peak_bytes = int(getattr(ma, "peak_memory_in_bytes", 0))
        rep.out_bytes = int(getattr(ma, "output_size_in_bytes", 0))
    rep.terms = roofline_terms(rep.flops_per_dev, rep.bytes_per_dev,
                               rep.coll_bytes_per_dev)
    return rep
