"""JXA105: large constants captured in the jaxpr.

A host array closed over by a jitted function is baked into the program
as a CONSTANT: it is re-uploaded per compiled executable, bloats the
serialized computation, defeats donation (constants are never donated),
and — the sneaky variant — a whole particle array accidentally captured
by closure instead of passed as an argument silently freezes step-1 data
into every later step. Entries budget constants via
``const_bytes_limit`` (default 1 MiB: lookup tables are legal, particle
arrays are not).

Constants of nested jit bodies are walked too — that is where closure
captures of inner jitted helpers land.
"""

from __future__ import annotations

from typing import List

from sphexa_tpu.devtools.audit.core import EntryTrace, register
from sphexa_tpu.devtools.audit.spmd import aval_bytes
from sphexa_tpu.devtools.common import Finding
from sphexa_tpu.devtools.primitives import walk_consts


@register(
    "JXA105", "const-bloat",
    "constant above the entry's size budget captured in the jaxpr "
    "(closure-baked array)",
)
def check(trace: EntryTrace) -> List[Finding]:
    limit = trace.entry.const_bytes_limit
    out: List[Finding] = []
    seen = set()
    for c in walk_consts(trace.closed_jaxpr):
        if id(c) in seen:
            continue
        seen.add(id(c))
        # shape x itemsize: jax's own constant wrapper has no .nbytes
        nbytes = aval_bytes(c)
        if nbytes > limit:
            out.append(trace.finding(
                "JXA105",
                f"constant {getattr(c, 'dtype', '?')}"
                f"{tuple(getattr(c, 'shape', ()))} of {nbytes} bytes "
                f"baked into the jaxpr (budget {limit}). Pass it as "
                f"an argument (pytree leaf) instead of closing over "
                f"it.",
            ))
    return out
