"""JXA502 fixtures: entries that break or degrade under jax.vmap.

``vmap_trace_break``: a primitive with no batching rule — the vmapped
trace raises, captured as a finding. (A fixture-owned primitive, so the
case does not lean on which of jax's own primitives lack a rule this
release: optimization_barrier had none once and has one now.)
``vmap_callback``: a debug print lowers to a host-callback primitive,
which under vmap serializes per member. ``vmap_serialized``: a sequential_vmap
custom-batched inner fn — the batch rule is an explicit member loop, so
the vmapped jaxpr gains a scan the base jaxpr does not have.
``vmap_clean`` is the honest twin: plain elementwise math batches into
one fused program.

Run by tests/test_statecheck.py with ``vmap_members=2`` set on the
audit context (the rule is off at the default ``vmap_members=0``, so
these entries are invisible to the package gate).
"""

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive

from sphexa_tpu.devtools.audit.core import EntryCase, entrypoint

_unbatchable_p = Primitive("fixture_unbatchable")
_unbatchable_p.def_abstract_eval(lambda x: x)


@entrypoint("vmap_trace_break", phase_coverage_min=0.0)  # expect: JXA502
def vmap_trace_break():
    def fn(x):
        return _unbatchable_p.bind(x * 2.0)

    return EntryCase(fn=fn, args=(jnp.zeros(8, jnp.float32),))


@entrypoint("vmap_callback", phase_coverage_min=0.0)  # expect: JXA502
def vmap_callback():
    def fn(x):
        jax.debug.print("x0={v}", v=x[0])
        return x * 2.0

    return EntryCase(fn=fn, args=(jnp.zeros(8, jnp.float32),))


@entrypoint("vmap_serialized", phase_coverage_min=0.0)  # expect: JXA502
def vmap_serialized():
    @jax.custom_batching.sequential_vmap
    def inner(x):
        return x * 2.0

    def fn(x):
        return inner(x)

    return EntryCase(fn=fn, args=(jnp.zeros(8, jnp.float32),))


@entrypoint("vmap_clean", phase_coverage_min=0.0)
def vmap_clean():
    def fn(x):
        return jnp.sin(x) * 2.0, x.sum()

    return EntryCase(fn=fn, args=(jnp.zeros(8, jnp.float32),))
