"""What the installed jax calls things: the one vocabulary of primitive
names and the one jaxpr walker behind every trace-level analyzer.

jax renames primitives between releases (``jax.debug.print`` lowered to
``debug_callback`` and now to ``debug_print``; ``lax.psum`` under a
checked ``shard_map`` was ``psum2`` and is now ``psum_invariant``; a
nested jit was ``pjit`` and is now ``jit``; ``shard_map`` carried
``in_names`` and now carries ``in_specs``). A rule that matches a
literal name goes blind, silently, the day the name moves. So the names
live here and nowhere else in ``devtools``, every older spelling stays
in its set (they cost nothing), and ``tests/test_primitives.py`` traces
one minimal program per construct on the jax that is installed and
asserts the primitive it emitted is classified as that construct.

Jax-free at import: everything walks already-traced jaxprs.
"""

from __future__ import annotations

from typing import Any, Iterator, List, Tuple

__all__ = [
    "COLLECTIVE_PRIMS",
    "FLOAT_REDUCTION_COLLECTIVES",
    "HOST_CALLBACK_PRIMS",
    "HOST_BOUNDARY_PRIMS",
    "CALL_PRIMS",
    "sub_jaxprs",
    "walk_eqns",
    "walk_consts",
    "collective_axes",
    "shard_map_operand_axes",
]

# Primitives that communicate over a NAMED mesh axis. The lax API names
# that lower to another primitive (pmean, pshuffle, psum_scatter) stay:
# JXL006 matches `jax.lax.<name>` calls in source against this set.
COLLECTIVE_PRIMS = frozenset({
    # all-reduce: lax.psum is `psum` under shard_map(check_vma=False) and
    # pmap, `psum_invariant` under a checked shard_map (`psum2` before);
    # lax.pmean is one of those followed by a div
    "psum", "psum2", "psum_invariant", "unreduced_psum", "pmean",
    "pmax", "pmin",
    # lax.pshuffle lowers to ppermute
    "ppermute", "pshuffle", "psend", "precv",
    "all_gather", "all_gather_invariant", "all_gather_reduced", "pgather",
    "all_to_all", "ragged_all_to_all",
    # lax.psum_scatter lowers to reduce_scatter
    "psum_scatter", "reduce_scatter", "unreduced_reduce_scatter",
})

# The subset whose cross-device combiner is order-sensitive on floats
# (pmax/pmin results do not depend on the association order).
FLOAT_REDUCTION_COLLECTIVES = frozenset({
    "psum", "psum2", "psum_invariant", "unreduced_psum", "pmean",
    "psum_scatter", "reduce_scatter", "unreduced_reduce_scatter",
})

# Host round trips inside a traced body, with what each costs.
HOST_CALLBACK_PRIMS = {
    "pure_callback": "host callback per step",
    "io_callback": "host IO callback per step",
    "callback": "host callback per step",
    # jax.debug.print: `debug_print` now, `debug_callback` before;
    # jax.debug.callback is `debug_callback` still
    "debug_print": "debug print serializes the device stream",
    "debug_callback": "debug print/callback serializes the device stream",
    "infeed": "host infeed per step",
    "outfeed": "host outfeed per step",
}
HOST_BOUNDARY_PRIMS = {
    **HOST_CALLBACK_PRIMS,
    "device_put": "explicitly re-places a buffer inside the traced body",
}

# Call-like primitives: the eqn is a call whose body is a nested jaxpr
# (control flow — scan/while/cond — and shard_map/pallas_call are not
# calls: their bodies run under other semantics).
CALL_PRIMS = frozenset({
    "jit", "pjit", "xla_call", "core_call", "closed_call",
    "remat", "remat2", "checkpoint",
    "custom_jvp_call", "custom_jvp_call_jaxpr",
    "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_vmap_call",
})

_AXIS_PARAM_KEYS = ("axes", "axis_name")


def _flat(v) -> tuple:
    return tuple(v) if isinstance(v, (list, tuple)) else (v,)


def _param_jaxprs(eqn) -> Iterator[Tuple[Any, tuple]]:
    """``(raw jaxpr, consts)`` for every jaxpr among an eqn's params, in
    key-sorted param order (the order the lowering lock's digests are
    built on). A ClosedJaxpr gives its ``.jaxpr`` and its ``.consts``; a
    raw Jaxpr gives ``()``: it has constvars, but their values live on
    an enclosing ClosedJaxpr."""
    for key in sorted(eqn.params, key=str):
        for w in _flat(eqn.params[key]):
            # ClosedJaxpr forwards .eqns, so unwrap it FIRST
            inner = getattr(w, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner, tuple(getattr(w, "consts", ()))
            elif hasattr(w, "eqns"):
                yield w, ()


def sub_jaxprs(eqn) -> List[Any]:
    """The raw jaxprs nested in one eqn: jit/custom_* bodies,
    scan/while/cond branches, shard_map and pallas_call bodies."""
    return [sub for sub, _consts in _param_jaxprs(eqn)]


def walk_eqns(jaxpr) -> Iterator:
    """Every eqn of ``jaxpr`` (raw or closed), each followed depth-first
    by the eqns of its nested jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in sub_jaxprs(eqn):
            yield from walk_eqns(sub)


def walk_consts(closed) -> Iterator:
    """Every constant VALUE baked into ``closed`` or into a ClosedJaxpr
    nested anywhere below it (where an inner jit's closure captures
    land); a body that several call eqns share gives its consts once."""
    yield from closed.consts
    seen = set()
    for eqn in walk_eqns(closed.jaxpr):
        for sub, consts in _param_jaxprs(eqn):
            if consts and id(sub) not in seen:
                seen.add(id(sub))
                yield from consts


def collective_axes(eqn) -> Tuple[str, ...]:
    """Mesh-axis names an eqn's ``axes`` / ``axis_name`` params carry."""
    return tuple(a for key in _AXIS_PARAM_KEYS if key in eqn.params
                 for a in _flat(eqn.params[key]) if isinstance(a, str))


def shard_map_operand_axes(eqn) -> List[Tuple[str, ...]]:
    """Per operand of a ``shard_map`` eqn, the mesh axes it is sharded
    over; ``()`` = the operand enters fully replicated. Reads
    ``in_specs`` (PartitionSpecs, one entry per dim) and the older
    ``in_names`` (``{dim: axes}`` dicts)."""
    if "in_specs" in eqn.params:
        return [tuple(a for dim in spec if dim is not None
                      for a in _flat(dim))
                for spec in eqn.params["in_specs"]]
    return [tuple(a for axes in names.values() for a in _flat(axes))
            for names in eqn.params.get("in_names", ())]
