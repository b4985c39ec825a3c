"""JXA104: host-boundary leaks inside a traced entry.

A callback / device_put / infeed primitive inside the hot jaxpr means the
step round-trips to the host (or re-places a buffer) EVERY iteration —
the per-step analog of the JXL002 host-sync class, but visible only after
tracing (the AST pass cannot see a callback smuggled in through a helper
in another module). Debug prints count too: ``jax.debug.print`` lowers to
a callback primitive of its own and serializes the device stream.

``with_sharding_constraint``/collectives are NOT flagged — they are
device-side. ``jax.named_scope`` (the sphexa/<phase> attribution
scopes, util/phases.py) never appears here at all: it pushes a
tracing-time name stack and lowers to NO primitive, so the phase
taxonomy is invisible to this rule by construction (pinned by the
audit gate staying at zero findings with every step entry scoped).
The deny set is ``primitives.HOST_BOUNDARY_PRIMS``, the
callback/transfer family. ``device_put`` needs care: jax stages
``jnp.asarray(np_constant)`` inside a traced body as a device_put eqn with no target (``devices=[None]``, alias
semantics) — that is constant staging, not a transfer (JXA105 budgets
its size instead). Only device_put with an EXPLICIT placement target is
a re-placement inside the hot body and gets flagged.
"""

from __future__ import annotations

from collections import Counter
from typing import List

from sphexa_tpu.devtools.audit.core import EntryTrace, register
from sphexa_tpu.devtools.common import Finding
from sphexa_tpu.devtools.primitives import HOST_BOUNDARY_PRIMS, walk_eqns


def _is_constant_staging(eqn) -> bool:
    """device_put with no explicit target = jax staging an np constant."""
    devices = eqn.params.get("devices", ())
    srcs = eqn.params.get("srcs", ())
    return all(d is None for d in devices) and all(s is None for s in srcs)


@register(
    "JXA104", "host-boundary",
    "callback/device_put/infeed primitives inside the traced body "
    "(per-step host round trip)",
)
def check(trace: EntryTrace) -> List[Finding]:
    counts: Counter = Counter()
    for eqn in walk_eqns(trace.closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in HOST_BOUNDARY_PRIMS:
            if name == "device_put" and _is_constant_staging(eqn):
                continue
            counts[name] += 1
    return [
        trace.finding(
            "JXA104",
            f"`{name}` x{n} in the traced body — "
            f"{HOST_BOUNDARY_PRIMS[name]}. Move it to the driver loop "
            f"(Simulation host code) or behind a debug-only flag.",
        )
        for name, n in sorted(counts.items())
    ]
