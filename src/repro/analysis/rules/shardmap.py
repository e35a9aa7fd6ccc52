"""shardmap-sort: no sort-lowering ops inside a ``shard_map`` region.

The PR 5 footgun, now machine-checked: on jax 0.4.x the SPMD partitioner
miscompiles sort-based ops on shard-varying values inside
``jit(shard_map(...))`` — ``jax.random.choice(replace=False)`` /
``permutation`` lower to a sort of random keys, the selected rows feed
downstream consumers garbage while the selection itself reads back
correctly (verified empirically under 8 forced host devices; see
``core.distributed.shard_select_no_replace``'s docstring). ``sort``,
``argsort``, ``top_k``, ``unique`` hit the same lowering.

Lexical approximation: any sort-based op *textually inside* a function
passed to ``shard_map`` (or ``shard_map_unchecked`` / ``Mesh.shard_map``)
is flagged, shard-varying or not — a shard-invariant use is the rare
case and takes a justified ``# repro: ignore[shardmap-sort]``. Functions
the rule cannot resolve (parameters, attributes) are skipped; when the
item-axis sharding PR lands, its new shard_map regions must keep their
bodies resolvable (local ``def``s) so this rule sees them.
"""

from __future__ import annotations

import ast

from ..registry import register
from ..visitors import (is_test_path, qualname, resolve_func_arg, under,
                        walk_scope)

#: callee qualnames (last component) that open a shard_map region
_SHARD_MAP_NAMES = {"shard_map", "shard_map_unchecked"}

#: sort-lowering ops: flagged by trailing attribute path
_SORT_SUFFIXES = ("sort", "argsort", "lexsort", "top_k", "unique",
                  "partition", "argpartition")
_SORT_RANDOM = ("choice", "permutation", "shuffle")


def _is_sort_call(call: ast.Call):
    q = qualname(call.func)
    if q is None:
        return None
    parts = q.split(".")
    if parts[-1] in _SORT_SUFFIXES:
        return q
    if parts[-1] in _SORT_RANDOM and "random" in parts[:-1]:
        return q
    return None


@register(
    "shardmap-sort",
    "no sort-based ops (jax.random.choice/permutation, sort, argsort, "
    "top_k, unique) inside a shard_map region",
    "PR 5: jax 0.4.x SPMD partitioner miscompiles sort lowerings on "
    "shard-varying values inside jit(shard_map); use "
    "core.distributed.shard_select_no_replace instead")
def check(ctx):
    if is_test_path(ctx.parts) or not (under(ctx.parts, "repro")
                                       or under(ctx.parts, "examples")
                                       or under(ctx.parts, "benchmarks")):
        return
    seen = set()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        q = qualname(node.func) or ""
        if q.split(".")[-1] not in _SHARD_MAP_NAMES or not node.args:
            continue
        body = resolve_func_arg(node.args[0], ctx.functions, ctx.assignments)
        if body is None or id(body) in seen:
            continue
        seen.add(id(body))
        for inner in walk_scope(body):
            if isinstance(inner, ast.Call):
                sq = _is_sort_call(inner)
                if sq is not None:
                    yield inner.lineno, (
                        f"{sq} inside a shard_map region: sort lowerings "
                        f"on shard-varying values miscompile under "
                        f"jit(shard_map) on jax 0.4.x (PR 5) — use "
                        f"shard_select_no_replace / a psum'd reformulation, "
                        f"or suppress with a shard-invariance justification")
