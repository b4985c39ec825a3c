"""JXA204: two-point tree-growth probe for the JXA202 rescale exemption.

JXA202's campaign rescale multiplies only EXTENSIVE buffers (whole
per-device particle slabs: elems a multiple of the slab rows S); scan
accumulators, cell-grid tiles and O(tree) coarse arrays stay at traced
size. docs/NEXT.md round-10 carried the caution: a tree that grows
SUPERLINEARLY in N hides inside that exemption — its buffers stay
"traced size" in the estimate while really exploding at campaign N.

This closes it with a two-point probe: entries that declare a ``grow``
builder (the same case at a larger toy N) are retraced at both sizes
and the summed bytes of the exempt buffer class are compared. The
exempt class must scale no worse than linearly in N
(``growth <= n_ratio x AuditContext.tree_growth_slack``) — an N^2 pair
matrix or a superlinear tree build mislabeled as "fixed-size work
buffer" fails the gate, and the JXA202 campaign estimate for it can no
longer be trusted silently. Entries without a ``grow`` builder are not
probed.
"""

from __future__ import annotations

from typing import List

from sphexa_tpu.devtools.audit.core import (
    EntryTrace,
    audit_context,
    register,
)
from sphexa_tpu.devtools.audit.spmd import aval_bytes, format_bytes, slab_rows
from sphexa_tpu.devtools.common import Finding
from sphexa_tpu.devtools.primitives import sub_jaxprs


def _exempt_bytes(jaxpr, s_toy: int) -> int:
    """Summed bytes of distinct rescale-EXEMPT buffers across the
    program, nested jaxprs included; pallas kernel bodies are VMEM
    views and are skipped.

    Extensive means a whole multiple of the slab rows OR of the padded
    particle capacity (next power of two >= slab) — the neighbor-list
    working set is capacity-padded, so without the pow2 candidate its
    classification flips with the slab's divisors and the two probe
    points would not be comparable."""
    candidates = [s for s in (
        s_toy, 1 << max(int(s_toy) - 1, 0).bit_length() if s_toy else 0,
    ) if s]
    seen = set()
    total = 0

    def visit(v):
        nonlocal total
        if id(v) in seen:
            return
        seen.add(id(v))
        aval = getattr(v, "aval", None)
        b = aval_bytes(aval)
        if not b:
            return
        itemsize = getattr(getattr(aval, "dtype", None), "itemsize", 1) or 1
        elems = b // itemsize
        if not any(elems >= s and elems % s == 0 for s in candidates):
            total += b

    def walk(jx):
        for v in (*jx.invars, *jx.constvars):
            visit(v)
        for eqn in jx.eqns:
            for ov in eqn.outvars:
                visit(ov)
            if eqn.primitive.name == "pallas_call":
                continue
            for sj in sub_jaxprs(eqn):
                walk(sj)

    walk(jaxpr)
    return total


@register(
    "JXA204", "tree-growth",
    "rescale-exempt (non-slab) buffer bytes grow superlinearly in N "
    "between the entry's two growth-probe trace points",
)
def check(trace: EntryTrace) -> List[Finding]:
    if trace.case.grow is None:
        return []
    ctx = audit_context()
    grown_case, n_ratio = trace.case.grow()
    grown = EntryTrace(trace.entry, grown_case)

    jx1 = trace.closed_jaxpr.jaxpr
    jx2 = grown.closed_jaxpr.jaxpr
    e1 = _exempt_bytes(jx1, slab_rows(jx1))
    e2 = _exempt_bytes(jx2, slab_rows(jx2))
    if e1 <= 0:
        return []
    growth = e2 / e1
    allowed = float(n_ratio) * ctx.tree_growth_slack
    if growth <= allowed:
        return []
    return [trace.finding(
        "JXA204",
        f"rescale-exempt buffers grew {growth:.2f}x "
        f"({format_bytes(e1)} -> {format_bytes(e2)}) across a "
        f"{n_ratio:.2f}x N growth probe (allowed <= {allowed:.2f}x = "
        f"linear x slack {ctx.tree_growth_slack:g}) — an O(tree) or "
        f"work-buffer array is scaling superlinearly in N, so JXA202's "
        f"traced-size exemption under-estimates its campaign HBM; make "
        f"the buffer extensive (slab-multiple) or cap its growth.",
    )]
