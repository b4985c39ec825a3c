"""JXA401: bitwise-nondeterminism audit (the replay-contract class).

The repo's lowering lock (``lowerdiff.py``) pins WHAT program runs; this
rule pins that the program is bitwise-replayable at all. Three lowering
shapes break replay even with an identical jaxpr digest:

- a float ``scatter-add``/``scatter-mul`` carrying BOTH
  ``unique_indices=False`` and ``indices_are_sorted=False``: XLA may
  combine colliding updates in any order, and float addition does not
  commute in rounding. The gravity upsweeps accumulate children into
  parents with duplicate indices on purpose — they stay silent here
  because the level-ordered layout makes parent rows non-decreasing and
  the scatters honestly declare ``indices_are_sorted=True``, fixing the
  segment order (gravity/traversal.py, gravity/spherical.py).
- a ``reduce_precision`` eqn: the deliberate-precision-drop escape hatch
  is banned from audited entries (dtype policy lives in util/dtypes.py,
  not in per-eqn rounding).
- a float-REDUCTION collective (``primitives.FLOAT_REDUCTION_COLLECTIVES``
  — not pmax/pmin, whose results are order-insensitive) that participates
  in a JXA201 unordered pair: with no proven total order the reduction
  tree may associate differently per run. Chained collectives
  (exchange.chain_after) are already excluded by the spmd dependency
  walk.
"""

from __future__ import annotations

from typing import List

import numpy as np

from sphexa_tpu.devtools.audit.core import (
    EntryTrace,
    audit_context,
    register,
)
from sphexa_tpu.devtools.audit.spmd import spmd_report
from sphexa_tpu.devtools.common import Finding
from sphexa_tpu.devtools.primitives import (
    FLOAT_REDUCTION_COLLECTIVES,
    walk_eqns,
)

#: scatter variants whose combiner is order-sensitive on floats
_UNORDERED_SCATTERS = ("scatter-add", "scatter-mul")


def _is_float(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and np.issubdtype(dtype, np.inexact)


@register(
    "JXA401", "nondeterminism",
    "bitwise-replay hazards: unordered float scatter accumulation, "
    "reduce_precision, float-reduction collectives outside a proven "
    "order",
)
def check(trace: EntryTrace) -> List[Finding]:
    findings: List[Finding] = []
    scatters = 0
    scatter_example = ""
    precisions = 0
    for eqn in walk_eqns(trace.closed_jaxpr.jaxpr):
        prim = eqn.primitive.name
        if prim in _UNORDERED_SCATTERS:
            if (not eqn.params.get("unique_indices", False)
                    and not eqn.params.get("indices_are_sorted", False)
                    and any(_is_float(v.aval) for v in eqn.outvars)):
                scatters += 1
                if not scatter_example:
                    scatter_example = (
                        f"{prim} -> "
                        f"{getattr(eqn.outvars[0], 'aval', '?')}")
        elif prim == "reduce_precision":
            precisions += 1
    if scatters:
        findings.append(trace.finding(
            "JXA401",
            f"{scatters} float {'/'.join(_UNORDERED_SCATTERS)} eqn(s) "
            f"with unique_indices=False AND indices_are_sorted=False "
            f"(e.g. {scatter_example}) — colliding updates may combine "
            f"in any order and float addition does not commute in "
            f"rounding, so replays are not bitwise. Declare "
            f"indices_are_sorted=True where a segment order is "
            f"guaranteed (the gravity-upsweep pattern), "
            f"unique_indices=True where indices cannot collide, or "
            f"restructure as a segment_sum.",
        ))
    if precisions:
        findings.append(trace.finding(
            "JXA401",
            f"{precisions} reduce_precision eqn(s) — per-eqn rounding "
            f"drops bits outside the util/dtypes.py policy and breaks "
            f"bitwise replay; lower the dtype of the array instead.",
        ))

    rep = spmd_report(trace, audit_context())
    if rep.unordered_pairs:
        hazard = sorted({
            f"{rep.collectives[cid].prim}#{cid}"
            f"[{rep.collectives[cid].where}]"
            for pair in rep.unordered_pairs for cid in pair
            if rep.collectives[cid].prim in FLOAT_REDUCTION_COLLECTIVES})
        if hazard:
            findings.append(trace.finding(
                "JXA401",
                f"{len(hazard)} float-reduction collective(s) in "
                f"mutually order-unconstrained pairs: "
                f"{'; '.join(hazard[:4])}"
                + (f"; +{len(hazard) - 4} more" if len(hazard) > 4 else "")
                + " — with no proven total order the cross-device "
                  "reduction may associate differently per run. Pin the "
                  "order with exchange.chain_after (also clears JXA201).",
            ))
    return findings
