"""JXA106: collective-axis audit against the entry's declared sharding.

Every collective in the traced body names a mesh axis;
the registry entry declares which axes its sharding provides
(``mesh_axes=("p",)``). An axis outside the declaration means the code
and the registry disagree about the mesh — either a renamed axis that a
copy-pasted collective still references (it resolves fine against an
unrelated axis of the same name on a larger mesh and reduces over the
WRONG devices), or a collective that escaped into an entry registered as
unsharded. shard_map eqns are cross-checked the same way: the mesh they
bind must only carry declared axes.
"""

from __future__ import annotations

from typing import Dict, List

from sphexa_tpu.devtools.audit.core import EntryTrace, register
from sphexa_tpu.devtools.common import Finding
from sphexa_tpu.devtools.primitives import collective_axes, walk_eqns


@register(
    "JXA106", "collective-axis",
    "collective over an axis name outside the entry's declared mesh "
    "sharding",
)
def check(trace: EntryTrace) -> List[Finding]:
    declared = set(trace.entry.mesh_axes)
    unknown: Dict[str, str] = {}  # axis -> first primitive
    for eqn in walk_eqns(trace.closed_jaxpr.jaxpr):
        names = list(collective_axes(eqn))
        mesh = eqn.params.get("mesh")
        if mesh is not None and hasattr(mesh, "axis_names"):
            names += [a for a in mesh.axis_names if isinstance(a, str)]
        for name in names:
            if name not in declared and name not in unknown:
                unknown[name] = eqn.primitive.name
    return [
        trace.finding(
            "JXA106",
            f"`{prim}` uses axis {name!r} but the registry declares "
            f"mesh_axes={tuple(sorted(declared))} for this entry — the "
            f"code and the declared sharding disagree; fix the axis name "
            f"or the registration.",
        )
        for name, prim in sorted(unknown.items())
    ]
