"""The devtools vocabulary, checked against the jax that is installed.

``sphexa_tpu/devtools/primitives.py`` is the one place that knows what
this jax calls a collective, a host callback, a nested call, and where it
keeps a nested body's constants. Each case here traces the smallest
program that holds one construct and asserts that the primitive jax
ACTUALLY emitted is classified as that construct — so the next rename
fails one case by name instead of silently closing the auditor's eyes
(the state of the tree from the seed to PR 26: ``debug_print``,
``psum_invariant`` and ``jit`` were in no rule's name set).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.experimental import io_callback
from jax.sharding import PartitionSpec as P

from sphexa_tpu.devtools.audit.spmd import aval_bytes
from sphexa_tpu.devtools.primitives import (
    CALL_PRIMS,
    COLLECTIVE_PRIMS,
    FLOAT_REDUCTION_COLLECTIVES,
    HOST_BOUNDARY_PRIMS,
    HOST_CALLBACK_PRIMS,
    collective_axes,
    shard_map_operand_axes,
    sub_jaxprs,
    walk_consts,
    walk_eqns,
)

# Primitives that name a mesh axis and move no data: axis_index reads the
# coordinate, the rest retype a value's varying/reduced-ness for
# shard_map's checker. Kept here so the cases below can tell "known, not a
# collective" from "new in this jax, nobody has looked".
NON_COMM_AXIS_PRIMS = frozenset({
    "axis_index", "pbroadcast", "pvary", "preduced",
    "vary_unreduced_cast_p", "reduced_vary_cast_p",
})

_X = jnp.ones(8)
_TABLE = np.arange(4096, dtype=np.float32)  # 16 KiB


def _names(closed):
    return [eqn.primitive.name for eqn in walk_eqns(closed)]


# ---------------------------------------------------------------------------
# collectives, in a two-device shard_map with and without the vma checker
# (the package's stages run unchecked; a checked body renames psum) and
# under pmap
# ---------------------------------------------------------------------------

_COLLECTIVES = {
    # construct: (per-shard body, reduces floats order-sensitively)
    "psum": (lambda x: jax.lax.psum(x, "p"), True),
    "pmean": (lambda x: jax.lax.pmean(x, "p"), True),
    "psum_scatter": (
        lambda x: jnp.tile(jax.lax.psum_scatter(x, "p", tiled=True), 2),
        True),
    "pmax": (lambda x: jax.lax.pmax(x, "p"), False),
    "pmin": (lambda x: jax.lax.pmin(x, "p"), False),
    "ppermute": (
        lambda x: jax.lax.ppermute(x, "p", [(0, 1), (1, 0)]), False),
    "pshuffle": (lambda x: jax.lax.pshuffle(x, "p", [1, 0]), False),
    "all_gather": (
        lambda x: jax.lax.all_gather(x, "p", tiled=True)[:4], False),
    "all_to_all": (
        lambda x: jax.lax.all_to_all(
            x.reshape(2, 2), "p", 0, 0).reshape(4), False),
}


def _trace_collective(construct, how):
    body, _ = _COLLECTIVES[construct]
    if how == "pmap":
        return jax.make_jaxpr(jax.pmap(body, axis_name="p"))(
            jnp.ones((2, 4)))
    mesh = jax.make_mesh((2,), ("p",))
    return jax.make_jaxpr(shard_map(
        body, mesh=mesh, in_specs=P("p"), out_specs=P("p"),
        check_vma=(how == "checked")))(_X)


@pytest.mark.parametrize("how", ["checked", "unchecked", "pmap"])
@pytest.mark.parametrize("construct", sorted(_COLLECTIVES))
def test_collective_is_classified(construct, how):
    closed = _trace_collective(construct, how)
    # everything in the body that names the mesh axis and is not a known
    # no-traffic primitive is what jax emitted for this collective
    emitted = [eqn.primitive.name for eqn in walk_eqns(closed)
               if collective_axes(eqn)
               and eqn.primitive.name not in NON_COMM_AXIS_PRIMS]
    assert emitted, f"no axis-naming primitive in {_names(closed)}"
    unknown = set(emitted) - COLLECTIVE_PRIMS
    assert not unknown, (
        f"jax {jax.__version__} lowers lax.{construct} ({how}) to "
        f"{sorted(unknown)}, which primitives.COLLECTIVE_PRIMS does not "
        f"hold: JXA106/201/203/401, the cost model's ICI bytes and the "
        f"lowering lock's collective count cannot see it")
    reduces = _COLLECTIVES[construct][1]
    assert all((p in FLOAT_REDUCTION_COLLECTIVES) == reduces
               for p in emitted), (emitted, reduces)


def test_every_parallel_primitive_of_this_jax_is_known():
    """A collective jax adds is classified on purpose, not left out of
    COLLECTIVE_PRIMS by default."""
    from jax._src.core import Primitive
    from jax._src.lax import parallel

    prims = {v.name for v in vars(parallel).values()
             if isinstance(v, Primitive)}
    assert prims & COLLECTIVE_PRIMS and prims & NON_COMM_AXIS_PRIMS
    unknown = prims - COLLECTIVE_PRIMS - NON_COMM_AXIS_PRIMS
    assert not unknown, (
        f"jax {jax.__version__} defines {sorted(unknown)}: add each to "
        f"primitives.COLLECTIVE_PRIMS, or to NON_COMM_AXIS_PRIMS above if "
        f"it moves no data")
    assert not COLLECTIVE_PRIMS & NON_COMM_AXIS_PRIMS
    assert FLOAT_REDUCTION_COLLECTIVES <= COLLECTIVE_PRIMS


# ---------------------------------------------------------------------------
# host boundary
# ---------------------------------------------------------------------------


def _debug_print(x):
    jax.debug.print("x = {}", x)
    return x


def _debug_callback(x):
    jax.debug.callback(lambda v: None, x)
    return x


_HOST = {
    "debug_print": _debug_print,
    "debug_callback": _debug_callback,
    "pure_callback": lambda x: jax.pure_callback(
        np.asarray, jax.ShapeDtypeStruct(x.shape, x.dtype), x),
    "io_callback": lambda x: io_callback(
        np.asarray, jax.ShapeDtypeStruct(x.shape, x.dtype), x),
}


@pytest.mark.parametrize("construct", sorted(_HOST))
def test_host_callback_is_classified(construct):
    names = _names(jax.make_jaxpr(_HOST[construct])(_X))
    assert names and set(names) <= set(HOST_CALLBACK_PRIMS), (
        f"jax {jax.__version__} lowers {construct} to {names}; "
        f"primitives.HOST_CALLBACK_PRIMS does not hold it, so JXA104 and "
        f"JXA502 cannot see a host round trip in a step")


def test_explicit_device_put_is_a_boundary_not_a_callback():
    names = _names(jax.make_jaxpr(
        lambda x: jax.device_put(x, jax.devices()[0]))(_X))
    assert names and set(names) <= set(HOST_BOUNDARY_PRIMS)
    assert not set(names) & set(HOST_CALLBACK_PRIMS)


# ---------------------------------------------------------------------------
# calls: the eqn that holds a nested body, and the walk into it
# ---------------------------------------------------------------------------


@jax.custom_jvp
def _cjvp(x):
    return x * 2.0


_cjvp.defjvp(lambda p, t: (_cjvp(p[0]), t[0] * 2.0))


@jax.custom_vjp
def _cvjp(x):
    return x * 2.0


_cvjp.defvjp(lambda x: (_cvjp(x), None), lambda _res, ct: (ct * 2.0,))

_CALLS = {
    "jit": lambda x: jax.jit(lambda y: y * 2.0)(x),
    "checkpoint": jax.checkpoint(lambda x: x * 2.0),
    "custom_jvp": _cjvp,
    "custom_vjp": _cvjp,
}


@pytest.mark.parametrize("construct", sorted(_CALLS))
def test_call_is_classified_and_walked(construct):
    closed = jax.make_jaxpr(_CALLS[construct])(_X)
    (call,) = closed.jaxpr.eqns
    assert call.primitive.name in CALL_PRIMS, (
        f"jax {jax.__version__} stages {construct} as "
        f"`{call.primitive.name}`, which primitives.CALL_PRIMS does not hold")
    assert len(sub_jaxprs(call)) == 1
    assert "mul" in _names(closed)[1:]     # the walk reaches the body


# ---------------------------------------------------------------------------
# where the constants and the operand shardings live
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nested", [False, True], ids=["top", "inner_jit"])
def test_closed_over_constant_is_found_with_its_size(nested):
    fn = lambda x: x + jnp.asarray(_TABLE)[:8]  # noqa: E731
    closed = jax.make_jaxpr(jax.jit(fn) if nested else fn)(_X)
    consts = list(walk_consts(closed))
    assert [aval_bytes(c) for c in consts] == [_TABLE.nbytes], (
        f"jax {jax.__version__}: the captured table is not where "
        f"walk_consts looks (or has no shape/dtype), so JXA105 and the "
        f"lowering lock's const_bytes cannot see it")


def test_shard_map_operand_axes():
    mesh = jax.make_mesh((2,), ("p",))
    closed = jax.make_jaxpr(partial(
        shard_map, mesh=mesh, in_specs=(P("p"), P()), out_specs=P("p"),
        check_vma=False)(lambda a, b: a + b.sum()))(_X, _X)
    (sm,) = [e for e in walk_eqns(closed) if e.primitive.name == "shard_map"]
    assert shard_map_operand_axes(sm) == [("p",), ()]
