"""JXA101: dtype promotion above the dtypes.py policy in a traced entry.

The package policy (sphexa_tpu/dtypes.py) is 32-bit everywhere on device:
f32 coordinates/hydro fields, i32 indices, u32 SFC keys. A 64-bit (or
c128) value anywhere in a hot jaxpr means either an explicit f64 request
or a silent promotion (np.float64 scalar, Python int too big for i32,
x64-enabled run) — on TPU that's a big slowdown (no fast f64) and off-TPU
it silently doubles memory traffic and de-synchronizes CI numerics from
chip numerics.

With x64 DISABLED jax demotes f64 requests on the spot, so the rule can
only fire under ``jax.enable_x64`` — entries opt in via
``x64=True`` (the fixture does; package entries trace under the ambient
config so this is the forward guard for x64-enabled diagnostics runs).

One finding per offending dtype per entry (first offending primitive
named), not one per eqn — a single upcast usually cascades through the
rest of the step.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from sphexa_tpu.devtools.audit.core import EntryTrace, register
from sphexa_tpu.devtools.common import Finding
from sphexa_tpu.devtools.primitives import walk_eqns

_MAX_ITEMSIZE = 4  # the dtypes.py policy is 32-bit device values


def _offending(dtype) -> bool:
    kind = getattr(dtype, "kind", None)
    if kind in ("f", "i", "u"):
        return dtype.itemsize > _MAX_ITEMSIZE
    if kind == "c":
        return dtype.itemsize > 2 * _MAX_ITEMSIZE  # complex128
    return False


def _scan_aval(aval, where: str, hits: Dict[str, Tuple[str, int]]):
    dtype = getattr(aval, "dtype", None)
    if dtype is not None and _offending(dtype):
        key = str(dtype)
        if key not in hits:
            hits[key] = (where, 0)
        hits[key] = (hits[key][0], hits[key][1] + 1)


@register(
    "JXA101", "dtype-promotion",
    "64-bit value in a traced entry (dtypes.py policy is 32-bit on device)",
)
def check(trace: EntryTrace) -> List[Finding]:
    closed = trace.closed_jaxpr
    hits: Dict[str, Tuple[str, int]] = {}
    for aval in closed.in_avals:
        _scan_aval(aval, "entry input", hits)
    for c in closed.consts:
        _scan_aval(c, "jaxpr constant", hits)
    for eqn in walk_eqns(closed.jaxpr):
        for var in eqn.outvars:
            _scan_aval(getattr(var, "aval", None),
                       f"`{eqn.primitive.name}` output", hits)
    return [
        trace.finding(
            "JXA101",
            f"{dtype} appears in the traced body ({count} value(s); first "
            f"at {where}) — above the 32-bit dtypes.py policy. Pin the "
            f"input/constant to a policy dtype or cast at the host "
            f"boundary.",
        )
        for dtype, (where, count) in sorted(hits.items())
    ]
