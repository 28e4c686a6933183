"""recompile-risk: every jitted dispatch is covered by the warmup lattice.

PR 6 killed the 120 s live-traffic p99 by enumerating the padded
shape-bucket lattice in ``engine/precompile.py`` and compiling it before
``/ready`` flips. That guarantee is structural, not magical: it holds
exactly as long as (a) every jitted dispatch derives its telemetry shape
key through the registered bucket helpers (so warmup and live traffic
land on the SAME key and the compile-detection registry treats warmed
shapes as seen), and (b) every dispatch's bucket family is enumerated by
``enumerate_lattice``. A new jit site, or a family quietly dropped from
the enumeration, reintroduces the cold tail with zero failing tests —
until a bench run eats it. This check fails the diff instead.

Rules (scope: ``engine/``):

1. **Lattice families.** ``enumerate_lattice`` in ``precompile.py`` must
   construct ``Bucket("<kind>", ...)`` literals; the set of kinds is the
   registered family set.
2. **Dispatch families.** Every ``ENGINE_TELEMETRY.record_dispatch`` /
   ``_record_warmup`` / ``_dispatching`` (the runner's block around a live
   dispatch: kind, key, who pays, label) call site's bucket family — derived from the
   ``batch_bucket`` label grammar (``b{N}`` decode, ``b{N}xn{S}``
   decode_burst, ``b{N}xt{C}`` prefill, ``b{N}xk{K}`` spec_verify,
   ``t{T}`` encode) — must be a registered family.
3. **Shape keys.** The ``key`` argument of every dispatch-recording call
   must derive from a registered bucket helper (``_tel_key`` /
   ``_prefill_tel``), be a tuple rooted at ``self._tel_scope``, or be
   forwarded by a registered forwarder (``_record_warmup``).
4. **Jit registration.** Every ``jax.jit(...)`` call site in ``engine/``
   must carry ``# pstlint: jit-family=<family>[,<family>...]`` naming
   registered families the warmup lattice drives through it (on the call
   line or the line above), or a justified suppression for deliberate
   one-time compiles.
5. **Warmup drivers.** For every registered family, the runner must
   define ``_warmup_<family>`` so the lattice walk can actually compile
   it.

Suppress with ``# pstlint: disable=recompile-risk(<reason>)``.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from ..core import (
    Finding,
    FunctionStack,
    Project,
    SourceFile,
    assignments_in,
    dotted_name,
    keyword_arg,
    literal_str,
)

CHECK_ID = "recompile-risk"
DESCRIPTION = (
    "jitted dispatches must use registered shape-key helpers and be "
    "covered by precompile.py's lattice enumeration"
)

_KEY_HELPERS = {"_tel_key", "_prefill_tel"}
_KEY_FORWARDERS = {"_record_warmup"}
_DISPATCH_FUNCS = {"record_dispatch", "_record_warmup", "_dispatching"}
_SCOPE_ATTR = "_tel_scope"

# The shape_bucket label grammar (mirrors Bucket.label in precompile.py).
_LABEL_FAMILIES: Tuple[Tuple[re.Pattern, str], ...] = (
    (re.compile(r"^b\{?.*xn"), "decode_burst"),
    (re.compile(r"^b\{?.*xt"), "prefill"),
    (re.compile(r"^b\{?.*xk"), "spec_verify"),
    (re.compile(r"^b"), "decode"),
    (re.compile(r"^t"), "encode"),
)


def _label_pattern(node: ast.AST) -> Optional[str]:
    """Static skeleton of a bucket label: literal parts of an f-string
    with ``{`` marking interpolations (``f"b{B}xn{n}"`` -> ``b{xn{``)."""
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            else:
                parts.append("{")
        return "".join(parts)
    lit = literal_str(node)
    return lit


def _family_of_label(pattern: str) -> Optional[str]:
    for rx, family in _LABEL_FAMILIES:
        if rx.search(pattern):
            return family
    return None


def lattice_families(precompile: SourceFile) -> Tuple[Set[str], int]:
    """(families constructed inside enumerate_lattice, its line)."""
    families: Set[str] = set()
    line = 1
    if precompile.tree is None:
        return families, line
    for node in ast.walk(precompile.tree):
        if isinstance(node, ast.FunctionDef) and node.name == "enumerate_lattice":
            line = node.lineno
            for call in ast.walk(node):
                if (
                    isinstance(call, ast.Call)
                    and (dotted_name(call.func) or "").split(".")[-1] == "Bucket"
                    and call.args
                ):
                    kind = literal_str(call.args[0])
                    if kind is None:
                        kind = next((
                            literal_str(kw.value) for kw in call.keywords
                            if kw.arg == "kind"
                        ), None)
                    if kind:
                        families.add(kind)
    return families, line


class _DispatchVisitor(FunctionStack):
    """Collects dispatch-recording call sites and jit call sites."""

    def __init__(self, src: SourceFile) -> None:
        super().__init__()
        self.src = src
        self.dispatches: List[Tuple[ast.Call, Optional[ast.AST]]] = []
        self.jit_sites: List[ast.Call] = []

    def visit_Call(self, node: ast.Call) -> None:
        name = dotted_name(node.func)
        last = (name or "").split(".")[-1]
        if last in _DISPATCH_FUNCS:
            self.dispatches.append((node, self.current_function))
        if last == "jit" and name in ("jax.jit", "jit"):
            self.jit_sites.append(node)
        self.generic_visit(node)


def _is_registered_key(
    node: ast.AST, func: Optional[ast.AST], depth: int = 0
) -> bool:
    """Does the shape-key expression derive from a registered helper?"""
    if depth > 3:
        return False
    if isinstance(node, ast.Call):
        last = (dotted_name(node.func) or "").split(".")[-1]
        return last in _KEY_HELPERS
    if isinstance(node, ast.Tuple) and node.elts:
        head = dotted_name(node.elts[0])
        return head is not None and head.endswith("." + _SCOPE_ATTR)
    if isinstance(node, ast.Name) and func is not None:
        # Parameter of a registered forwarder?
        if isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if func.name in _KEY_FORWARDERS:
                params = {a.arg for a in func.args.args}
                if node.id in params:
                    return True
        rhs = assignments_in(func).get(node.id)
        if rhs is not None and not (
            isinstance(rhs, ast.Name) and rhs.id == node.id
        ):
            return _is_registered_key(rhs, func, depth + 1)
    return False


def _dispatch_family(
    call: ast.Call, func: Optional[ast.AST]
) -> Tuple[Optional[str], Optional[str]]:
    """(family, how) for a dispatch call, from the batch_bucket label
    grammar, falling back to the literal ``kind`` argument."""
    bucket = keyword_arg(call, "batch_bucket")
    if bucket is None and len(call.args) >= 4:
        bucket = call.args[3]
    if bucket is not None:
        if isinstance(bucket, ast.Name) and func is not None:
            rhs = assignments_in(func).get(bucket.id)
            if rhs is not None:
                bucket = rhs
        pattern = _label_pattern(bucket)
        if pattern is not None:
            fam = _family_of_label(pattern)
            if fam is not None:
                return fam, "label %r" % pattern
    kind = literal_str(call.args[0]) if call.args else None
    if kind is not None:
        return kind, "kind literal %r" % kind
    return None, None


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    engine_files = [
        f for f in project.in_dir("engine") if f.tree is not None
    ]
    if not engine_files:
        return findings
    # Cross-file anchors resolve from the repo root so a subset lint
    # (a single engine file) sees the same lattice and warmup drivers a
    # full-tree lint does.
    precompile = project.resolve("engine/precompile.py")
    if precompile is None:
        # An engine without a lattice enumeration has no warmup story at
        # all — flag once, on any engine file.
        findings.append(Finding(
            CHECK_ID, engine_files[0].rel, 1, 0,
            "no engine/precompile.py found: jitted dispatches have no "
            "ahead-of-time lattice to be covered by",
        ))
        return findings
    runner = project.resolve("engine/runner.py")
    anchor_rels = {f.rel for f in engine_files}
    for anchor in (precompile, runner):
        if anchor is not None and anchor.rel not in anchor_rels:
            engine_files.append(anchor)
            anchor_rels.add(anchor.rel)

    families, lattice_line = lattice_families(precompile)
    if not families:
        findings.append(Finding(
            CHECK_ID, precompile.rel, lattice_line, 0,
            "enumerate_lattice constructs no Bucket(<kind>) literals — "
            "the warmup lattice is empty and every live shape recompiles",
        ))

    warmup_methods: Set[str] = set()
    for src in engine_files:
        tree = src.tree
        if tree is None:  # a resolved anchor may fail to parse
            continue
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if node.name.startswith("_warmup_"):
                    warmup_methods.add(node.name[len("_warmup_"):])

    for src in engine_files:
        tree = src.tree
        if tree is None:
            continue
        v = _DispatchVisitor(src)
        v.visit(tree)

        for call, func in v.dispatches:
            last = (dotted_name(call.func) or "").split(".")[-1]
            # Shape-key derivation (rule 3). record_dispatch(kind, key, ...)
            # and _record_warmup(kind, key, seconds, label) both carry the
            # key at positional index 1.
            key = call.args[1] if len(call.args) >= 2 else keyword_arg(call, "key")
            if key is None or not _is_registered_key(key, func):
                findings.append(Finding(
                    CHECK_ID, src.rel, call.lineno, call.col_offset,
                    "%s call's shape key does not derive from a registered "
                    "bucket helper (%s) — warmup and live traffic would "
                    "disagree on shape identity and the compile registry "
                    "stops being trustworthy"
                    % (last, "/".join(sorted(_KEY_HELPERS))),
                ))
            # Family coverage (rule 2). Registered forwarders relay their
            # caller's kind/label parameters verbatim — the family is
            # checked at each caller, not inside the forwarder.
            if (
                isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef))
                and func.name in _KEY_FORWARDERS
            ):
                continue
            family, how = _dispatch_family(call, func)
            if family is None:
                findings.append(Finding(
                    CHECK_ID, src.rel, call.lineno, call.col_offset,
                    "%s call's bucket family is not statically resolvable "
                    "(batch_bucket is neither an f-string label nor "
                    "traceable) — annotate or restructure so the lattice "
                    "coverage is checkable" % last,
                ))
            elif families and family not in families:
                findings.append(Finding(
                    CHECK_ID, src.rel, call.lineno, call.col_offset,
                    "dispatch family %r (from %s) is not enumerated by "
                    "enumerate_lattice in %s — live traffic on this path "
                    "compiles AFTER /ready flips"
                    % (family, how, precompile.rel),
                ))

        # Jit registration (rule 4).
        for call in v.jit_sites:
            ann = src.annotation_at(call.lineno, "jit-family")
            if ann is None:
                findings.append(Finding(
                    CHECK_ID, src.rel, call.lineno, call.col_offset,
                    "jax.jit call site carries no '# pstlint: "
                    "jit-family=<family>' annotation — new jit sites must "
                    "name the lattice family whose warmup compiles them "
                    "(or carry a justified suppression for a deliberate "
                    "one-time compile)",
                ))
                continue
            for fam in (f.strip() for f in ann.split(",")):
                if families and fam not in families:
                    findings.append(Finding(
                        CHECK_ID, src.rel, call.lineno, call.col_offset,
                        "jit-family annotation names %r, which "
                        "enumerate_lattice does not construct — either "
                        "the family was removed from the lattice (cold "
                        "tail regression) or the annotation is stale"
                        % fam,
                    ))

    # Warmup drivers (rule 5).
    for fam in sorted(families):
        if fam not in warmup_methods:
            findings.append(Finding(
                CHECK_ID, precompile.rel, lattice_line, 0,
                "lattice family %r has no _warmup_%s driver in the runner "
                "— enumerate_lattice promises coverage the warmup walk "
                "cannot deliver" % (fam, fam),
            ))
    return findings
