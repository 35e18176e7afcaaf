"""Engine-parity pass: the two simulation engines share one config.

``SimRunConfig`` (defined in ``simcore.py``) is the single environment
surface for both the exact event engine and the batched JAX engine.
PR 3/4 kept them in sync with a hand-maintained drift guard
(``unsupported_config_fields`` over module-level ``*_FIELDS`` tuples in
``batched.py``); this pass derives the guard instead of trusting it:

  - **PARITY001** — a ``SimRunConfig`` field is neither read as
    ``cfg.<field>`` in the batched engine module nor named in one of
    its module-level ``*_FIELDS`` tuples.  Adding a config knob that
    the event engine honors and the batched engine silently ignores is
    exactly how the engines drift apart.
  - **PARITY002** — a ``*_FIELDS`` entry is stale: it names something
    that is no longer a ``SimRunConfig`` field, or a field the batched
    engine now *does* read (the declaration claims unsupported, the
    code says otherwise).

File discovery is structural, not hard-wired: any scanned file defining
``class SimRunConfig`` is paired with the sibling engine module
``batched.py`` in the same directory, so fixture mini-repos exercise the
pass the same way ``src/repro/runtime`` does.
"""

from __future__ import annotations

import ast

from .core import ERROR, AnalysisPass, Finding, SourceFile, register

__all__ = ["EngineParityPass"]

CONFIG_CLASS = "SimRunConfig"
ENGINE_BASENAMES = ("batched.py",)
# attribute bases that denote "the config object" in the engine module
CONFIG_BASES = ("cfg", "config")


def _config_fields(sf: SourceFile) -> dict[str, int] | None:
    """``{field: lineno}`` for the config dataclass, or None if this
    file doesn't define it.  Fields are the class body's annotated
    assignments — properties and methods are behavior, not config."""
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.ClassDef) and node.name == CONFIG_CLASS:
            return {st.target.id: st.lineno for st in node.body
                    if isinstance(st, ast.AnnAssign)
                    and isinstance(st.target, ast.Name)
                    and not st.target.id.startswith("_")}
    return None


def _is_config_base(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return node.id in CONFIG_BASES
    if isinstance(node, ast.Attribute):          # self.cfg.<field>
        return node.attr in CONFIG_BASES
    return False


def _engine_reads(sf: SourceFile) -> set[str]:
    """Field names the engine module reads off a config object, either
    as ``cfg.<field>`` attribute access or dynamically via
    ``getattr(cfg, <literal>)``."""
    reads: set[str] = set()
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Attribute) and _is_config_base(node.value):
            reads.add(node.attr)
        elif (isinstance(node, ast.Call)
              and isinstance(node.func, ast.Name)
              and node.func.id == "getattr"
              and len(node.args) >= 2
              and _is_config_base(node.args[0])
              and isinstance(node.args[1], ast.Constant)
              and isinstance(node.args[1].value, str)):
            reads.add(node.args[1].value)
    return reads


def _declared_fields(sf: SourceFile) -> dict[str, tuple[int, str]]:
    """Entries of module-level ``*_FIELDS`` tuple assignments:
    ``{field: (lineno, tuple_name)}``."""
    out: dict[str, tuple[int, str]] = {}
    for node in sf.tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
            continue
        tgt = node.targets[0]
        if not (isinstance(tgt, ast.Name) and tgt.id.endswith("_FIELDS")):
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            for elt in node.value.elts:
                if (isinstance(elt, ast.Constant)
                        and isinstance(elt.value, str)):
                    out[elt.value] = (elt.lineno, tgt.id)
    return out


@register
class EngineParityPass(AnalysisPass):
    name = "engine-parity"
    rules = {
        "PARITY001": ("SimRunConfig field is neither read by the "
                      "batched engine module nor declared in one of "
                      "its *_FIELDS tuples"),
        "PARITY002": ("stale *_FIELDS entry: not a SimRunConfig field, "
                      "or a field the batched engine actually reads"),
    }

    def run(self, files: list[SourceFile]) -> list[Finding]:
        findings: list[Finding] = []
        by_dir = {}
        for sf in files:
            by_dir.setdefault(sf.path.parent, []).append(sf)
        for sf in files:
            fields = _config_fields(sf)
            if fields is None:
                continue
            engines = [e for e in by_dir.get(sf.path.parent, [])
                       if e.path.name in ENGINE_BASENAMES]
            for engine in engines:
                findings.extend(self._check_pair(sf, engine, fields))
        return findings

    def _check_pair(self, config_sf: SourceFile, engine_sf: SourceFile,
                    fields: dict[str, int]) -> list[Finding]:
        reads = _engine_reads(engine_sf)
        declared = _declared_fields(engine_sf)
        out: list[Finding] = []
        for fld, lineno in sorted(fields.items()):
            if fld not in reads and fld not in declared:
                out.append(Finding(
                    rule="PARITY001", severity=ERROR, path=config_sf.rel,
                    line=lineno, col=0,
                    message=(f"{CONFIG_CLASS}.{fld} is not read by "
                             f"{engine_sf.rel} and not declared in any "
                             "of its *_FIELDS tuples: the batched "
                             "engine would silently ignore it")))
        for fld, (lineno, tup) in sorted(declared.items()):
            if fld not in fields:
                out.append(Finding(
                    rule="PARITY002", severity=ERROR, path=engine_sf.rel,
                    line=lineno, col=0,
                    message=(f"stale {tup} entry '{fld}': no such "
                             f"{CONFIG_CLASS} field in {config_sf.rel}")))
            elif fld in reads:
                out.append(Finding(
                    rule="PARITY002", severity=ERROR, path=engine_sf.rel,
                    line=lineno, col=0,
                    message=(f"stale {tup} entry '{fld}': the engine "
                             "module reads this field, so the "
                             "declaration no longer matches the code")))
        return out
