"""JXA101 fixture: deliberate f64 in a traced body.

With x64 disabled jax silently demotes f64 requests, so these entries
opt into ``x64=True`` — the auditor traces them under
``jax.enable_x64`` (the config a conservation-diagnostics
run would use) where the cast really produces float64.
"""

import jax.numpy as jnp

from sphexa_tpu.devtools.audit.core import EntryCase, entrypoint


@entrypoint("bad_f64_cast", x64=True, phase_coverage_min=0.0)  # expect: JXA101
def bad_f64_cast():
    def fn(x):
        return (x.astype(jnp.float64) * 2.0).sum()

    return EntryCase(fn=fn, args=(jnp.zeros(8, jnp.float32),))


@entrypoint("clean_f32", x64=True, phase_coverage_min=0.0)
def clean_f32():
    def fn(x):
        return (x * 2.0).sum()

    return EntryCase(fn=fn, args=(jnp.zeros(8, jnp.float32),))
