"""Deprecation hygiene: shims warn correctly, and nothing in-repo still
calls them.

deprecation-stacklevel
    Every ``warnings.warn(..., DeprecationWarning)`` must pass
    ``stacklevel`` pointing past the shim (a constant >= 2, or a
    variable — ``runtime.resolve`` threads the caller's depth through).
    ``stacklevel=1`` (or the default) blames the shim itself, so the
    caller's filter/``-W error`` machinery and the test suite's
    ``pytest.warns`` matching see the wrong frame.

deprecated-call
    The deprecated entry points — ``core.fit_krk_picard`` / ``fit_em`` /
    ``fit_joint_picard`` — exist so external code keeps importing;
    in-repo code must target the engines/facade they delegate to.
    Flagged: importing one of these names from the shim module
    (``repro.core`` or relative equivalents) anywhere outside the modules
    that define or re-export them. Tests are exempt (they pin the shims'
    warning behavior deliberately).
"""

from __future__ import annotations

import ast

from ..registry import register
from ..visitors import in_library, qualname

#: deprecated name -> replacement hint
_DEPRECATED = {
    "fit_krk_picard": "repro.dpp: model.fit / learning.api.fit_krk",
    "fit_em": "repro.learning engines (learning.api)",
    "fit_joint_picard": "repro.learning engines (learning.api)",
}

#: modules whose ``from X import name`` re-export is the deprecated shim.
#: Importing the same name from the defining submodule (core.krk_picard,
#: core.em, ...) is the sanctioned internal route and not flagged.
_SHIM_MODULES = {"repro.core", "core"}

#: files allowed to reference the deprecated names: definers + re-exporters
_DEFINING_FILES = {"krk_picard.py", "em.py", "joint_picard.py",
                   "__init__.py"}


def _module_of(node: ast.ImportFrom, parts) -> str:
    if node.level:  # relative: resolve against this file's package path
        pkg = list(parts[:-1])  # the package dir (level-1 target)
        if node.level > 1:
            pkg = pkg[:len(pkg) - (node.level - 1)]
        base = ".".join(p for p in pkg if p)
        mod = node.module or ""
        return f"{base}.{mod}".strip(".") if mod else base
    return node.module or ""


@register(
    "deprecation-stacklevel",
    "warnings.warn(..., DeprecationWarning) must pass stacklevel>=2 so the "
    "warning blames the caller, not the shim",
    "PR 5/8 shim convention; runtime.resolve threads a caller-depth "
    "variable and is accepted as-is")
def check(ctx):
    if not in_library(ctx.parts):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        q = qualname(node.func) or ""
        if q.split(".")[-1] != "warn" or not (
                q.endswith("warnings.warn") or q.endswith("_warnings.warn")
                or q == "warn"):
            continue
        is_dep = any(
            isinstance(a, ast.Name) and a.id == "DeprecationWarning"
            for a in node.args) or any(
            kw.arg == "category" and isinstance(kw.value, ast.Name)
            and kw.value.id == "DeprecationWarning"
            for kw in node.keywords)
        if not is_dep:
            continue
        sl = None
        for kw in node.keywords:
            if kw.arg == "stacklevel":
                sl = kw.value
        if sl is None:
            yield node.lineno, (
                "DeprecationWarning without stacklevel — the warning blames "
                "the shim frame; pass stacklevel=2 (or thread the caller's "
                "depth like runtime.resolve)")
        elif isinstance(sl, ast.Constant) and isinstance(sl.value, int) \
                and sl.value < 2:
            yield node.lineno, (
                f"DeprecationWarning with stacklevel={sl.value} still blames "
                f"the shim frame; use stacklevel>=2")


@register(
    "deprecated-call",
    "no in-repo caller imports a deprecated entry point (core.fit_*) "
    "from its shim module",
    "the shims exist for external callers; in-repo code targets the "
    "facade/engines they delegate to (scan migrated from the "
    "no-deprecated-internals CI job's ad-hoc grep)")
def check_callers(ctx):
    if ctx.is_test or not in_library(ctx.parts):
        return
    if ctx.name in _DEFINING_FILES and "core" in ctx.parts:
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom):
            mod = _module_of(node, ctx.parts)
            if mod not in _SHIM_MODULES and not mod.endswith(".core"):
                continue
            for alias in node.names:
                if alias.name in _DEPRECATED:
                    yield node.lineno, (
                        f"imports deprecated {alias.name!r} from {mod!r} — "
                        f"use {_DEPRECATED[alias.name]}")
        elif isinstance(node, ast.Attribute):
            q = qualname(node) or ""
            parts = q.split(".")
            if len(parts) >= 2 and parts[-1] in _DEPRECATED \
                    and parts[-2] == "core":
                yield node.lineno, (
                    f"references deprecated {q!r} — use "
                    f"{_DEPRECATED[parts[-1]]}")
