"""JXL006: direct lax collectives outside the exchange layer.

Collectives rendezvous by program order, not by name: two collectives
with no data dependency between them may be scheduled in different
interleavings on different devices — garbage or deadlock on XLA:CPU
meshes (the PR-5 race), an ICI stall hazard on chips. The repo's
contract is that cross-shard communication routes through
``parallel/exchange.py``, whose ``chain_after`` pins a total order via
``optimization_barrier``.

This rule flags a direct ``jax.lax`` collective call (``psum``,
``ppermute``, ``all_gather``, ``all_to_all``, ...) in any other module
when no enclosing function also calls ``exchange.chain_after`` — a
function that threads a chain token is visibly participating in the
ordering protocol and is trusted (the trace-level JXA201 audit then
PROVES the order on the jaxpr). Purely data-chained collective pyramids
(e.g. the multipole upsweep) suppress inline with a reason.
"""

from __future__ import annotations

import ast
from pathlib import PurePosixPath
from typing import Dict, List

from sphexa_tpu.devtools.primitives import COLLECTIVE_PRIMS
from sphexa_tpu.devtools.lint.core import Finding, ModuleInfo, register
from sphexa_tpu.devtools.lint.trace_scope import build_parent_map

_CHAIN = "sphexa_tpu.parallel.exchange.chain_after"
_COLLECTIVE_QUALNAMES = {f"jax.lax.{p}" for p in COLLECTIVE_PRIMS}
_FUNCTION_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


@register(
    "JXL006",
    "unchained-collective",
    "direct jax.lax collective outside parallel/exchange.py in a function "
    "that never pins order with exchange.chain_after",
)
def check(mod: ModuleInfo) -> List[Finding]:
    if PurePosixPath(mod.path).parts[-2:] == ("parallel", "exchange.py"):
        return []
    parents = build_parent_map(mod.tree)
    chains: Dict[ast.AST, bool] = {}

    def calls_chain_after(fn: ast.AST) -> bool:
        if fn not in chains:
            chains[fn] = any(
                isinstance(sub, ast.Call)
                and mod.qualname(sub.func) == _CHAIN
                for sub in ast.walk(fn)
            )
        return chains[fn]

    out: List[Finding] = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        q = mod.qualname(node.func)
        if q not in _COLLECTIVE_QUALNAMES:
            continue
        cur = parents.get(node)
        exempt = False
        while cur is not None:
            if isinstance(cur, _FUNCTION_NODES) and calls_chain_after(cur):
                exempt = True
                break
            cur = parents.get(cur)
        if exempt:
            continue
        out.append(mod.finding(
            "JXL006",
            node,
            f"direct `{q}(...)` outside parallel/exchange.py with no "
            f"exchange.chain_after in the enclosing function: an "
            f"order-unconstrained collective is the XLA rendezvous-race "
            f"class. Thread a chain token through "
            f"exchange.chain_after, or suppress with a reason if data "
            f"dependencies already pin a total order.",
        ))
    return out
