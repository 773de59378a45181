"""HOTSYNC — host syncs inside the paper's critical decode path.

SwiftSpec's round overlaps draft and target work via async dispatch; ONE
designated host sync per round (the verified-token transfer) is the
contract.  Any other ``jax.device_get`` / ``block_until_ready`` / implicit
array-``__bool__`` inside the round loop serializes the very overlap the
system exists to create — and on a fast engine a single stray sync is a
double-digit-percent regression that no test catches.

Scope: the hot round methods only —

  * every ``EngineSession`` / ``ChainSession`` method (the bound round API),
  * every ``EngineStepper`` method (the per-round admit/absorb/retire path),
  * ``ServingRuntimeBase.run`` (the fleet round loop).

The intentional per-round sync point carries an inline
``# repro: disable=HOTSYNC`` with its justification; everything else is a
finding.
"""

from __future__ import annotations

import ast
import fnmatch

from repro.analysis.core import FileContext, Finding, ImportMap, Rule, register

# (class glob, method glob) pairs defining the hot path
HOT_SCOPES = (
    ("EngineSession", "*"),      # the bound round API: every phase method is hot
    ("ChainSession", "*"),
    ("EngineStepper", "*"),
    ("ServingRuntimeBase", "run"),
    ("*Runtime", "run"),
)

_SYNC_CALLS = frozenset({
    "jax.device_get", "jax.block_until_ready",
})


def _in_scope(cls_name: str, meth_name: str) -> bool:
    return any(fnmatch.fnmatch(cls_name, cg) and fnmatch.fnmatch(meth_name, mg)
               for cg, mg in HOT_SCOPES)


@register
class HotSyncRule(Rule):
    name = "HOTSYNC"
    description = ("device_get / block_until_ready / implicit array bool "
                   "inside the hot decode round")

    def check(self, ctx: FileContext, project) -> list[Finding]:
        imports = ImportMap(ctx.tree)
        jnp_aliases = {local for local, canon in imports.names.items()
                       if canon in ("jax.numpy", "jnp")}
        findings: list[Finding] = []
        for cls in ast.walk(ctx.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            for meth in cls.body:
                if not isinstance(meth, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if not _in_scope(cls.name, meth.name):
                    continue
                findings.extend(
                    self._check_method(ctx, imports, jnp_aliases, cls, meth))
        return findings

    def _check_method(self, ctx, imports, jnp_aliases, cls, meth) -> list[Finding]:
        out = []
        where = f"{cls.name}.{meth.name}"
        for node in ast.walk(meth):
            if isinstance(node, ast.Call):
                resolved = imports.resolve(node.func)
                if resolved in _SYNC_CALLS:
                    out.append(ctx.finding(
                        self.name, node,
                        f"`{resolved}` inside hot path {where} forces a host "
                        f"sync — keep it to the designated per-round sync "
                        f"point"))
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr == "block_until_ready"):
                    out.append(ctx.finding(
                        self.name, node,
                        f"`.block_until_ready()` inside hot path {where} "
                        f"stalls async dispatch"))
            elif isinstance(node, (ast.If, ast.While)):
                for sub in ast.walk(node.test):
                    if isinstance(sub, ast.Call):
                        root = sub.func
                        while isinstance(root, ast.Attribute):
                            root = root.value
                        if isinstance(root, ast.Name) and root.id in jnp_aliases:
                            out.append(ctx.finding(
                                self.name, sub,
                                f"branching on a device array in hot path "
                                f"{where} triggers implicit __bool__ — a "
                                f"blocking transfer"))
        return out
